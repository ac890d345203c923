"""A table of TPC-DS `store_sales` that a streaming sink keeps:
`gen/deltalog.py`'s log (100 actions a commit, 20% removes, a checkpoint
in a writer's row order, staged commits), with the fact table's 23
columns in `schemaString` (twelve of them `decimal(7,2)`) and, on every
add, the stats a Delta writer leaves for all 23.

A micro-batch (one commit) is 80 files of `rows_per_file` rows (1,000):
80,000 rows. Tickets arrive in order, so batch `v` is a run of
`ss_ticket_number` and a piece of a sold date: scale factor 1,000 has
2.88 billion rows over 1,823 sold dates, 1,579,806 rows a date, 19.75
batches. Every file of a batch holds a slice of the batch's rows (a
streaming sink's layout, as `gen/deltastream.py`). One file in
`SMALL_ONE_IN` (8) is a small one, as a sink's tasks leave them where a
trigger found their partition nearly empty: 1, 2, 4, 8, 16 or 32 rows
(`SMALL_ROWS`, by equal shares; never more than `rows_per_file`), drawn
as rows of the batch (a place in it, which gives date, time and ticket;
keys; a priced row of the pool), so that a file of one row has every
least equal to its most. Those are the files a bucket of Query 28 can
rule out. A full file's stats are, each drawn per file from the seed:

- `ss_sold_date_sk`: the one or two dates the batch's rows fall on;
  `ss_sold_time_sk`: the batch's piece of the day's 86,400 seconds (the
  whole day where the batch runs over midnight); `ss_ticket_number`:
  the batch's run of tickets (`ITEMS_PER_TICKET` rows a ticket), each
  end drawn within `ENDS` of it.
- the other seven identifiers and `ss_quantity`: the least and the most
  of `rows_per_file` uniform draws over the column's domain at scale
  factor 1,000.
- the twelve money columns, by dsdgen's pricing (`wholesale_cost`
  1.00-100.00, `list_price` = cost x (1 + markup up to 200%),
  `sales_price` = list x (1 - discount up to 100%), `ext_*` = x
  quantity, a coupon on one row in five, tax up to 9%, `net_profit`
  negative where the discount was deep): a pool of `POOL` rows is priced
  once a seed, and a file's least and most of a column are the pool's
  quantiles at `1 - u^(1/rows)` and `u^(1/rows)`, which is how the
  extremes of that many draws are distributed. At 1,000 rows a file a
  money column spans nearly its whole range: a column that skips
  nothing, as in life. Written as JSON numbers with two places
  (`"ss_list_price":18.01`), as a Delta writer writes a decimal.
- `nullCount`: Binomial(rows, `NULL_SHARE`) in the 21 nullable columns
  (never every row of a file, so none in a file of one row), 0 in
  `ss_item_sk` and `ss_ticket_number`; `numRecords` the file's rows.

Every number of a file comes from a hash of (seed, file id), so the
manifest answers `scan_expected` for a window's files alone: what a
plan of a range of sold dates, with or without one of Query 28's
buckets, has to read. Everything but the stats and the schema is
`deltalog`'s own code, run as a private copy (as `gen/nexmark_bids.py`
runs one), so `deltalog` itself is not touched; the checkpoint's `add`
column is built and written a block of files at a time, a row group
each, so that no chunk of its stats strings passes 2 GiB.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import importlib.util
import json
import time
import types

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from chipbench.gen import deltalog
from chipbench.gen.nexmark_bids import _hash_by_id

# TPC-DS v2/v3 at scale factor 1,000 (recalled; `assumed` in the
# configuration's file)
ROWS_AT_SF1000 = 2_879_987_999
SOLD_DATES = 1_823                  # 1998-01-02 .. 2002-12-31 (julian days)
FIRST_DATE_SK = 2_450_816
SECONDS_A_DAY = 86_400              # t_time_sk 0 .. 86,399
ITEMS_PER_TICKET = 12               # rows of one ss_ticket_number, on average
SF1000_BATCH_ROWS = 80_000          # 80 files x 1,000 rows
NULL_SHARE = 0.04
ENDS = 8                            # an end of a run lies this near it
POOL = 1 << 18                      # priced rows a seed
SMALL_ONE_IN = 8                    # one file in eight is a small one
SMALL_ROWS = (1, 2, 4, 8, 16, 32)   # its rows, by equal shares

KEYS = (  # the identifiers with no order in a batch, and their domains
    ("ss_item_sk", 300_000), ("ss_customer_sk", 12_000_000),
    ("ss_cdemo_sk", 1_920_800), ("ss_hdemo_sk", 7_200),
    ("ss_addr_sk", 6_000_000), ("ss_store_sk", 1_002),
    ("ss_promo_sk", 1_500))
QUANTITY = 100                      # ss_quantity 1 .. 100
MONEY = ("ss_wholesale_cost", "ss_list_price", "ss_sales_price",
         "ss_ext_discount_amt", "ss_ext_sales_price",
         "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
         "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
         "ss_net_profit")
MONEY_PRECISION = 7                 # decimal(7,2)
NEVER_NULL = ("ss_item_sk", "ss_ticket_number")


def columns_of(money_precision: int = MONEY_PRECISION) -> tuple:
    """(name, Delta type) of the 23 columns, in the table's order."""
    return (("ss_sold_date_sk", "integer"), ("ss_sold_time_sk", "integer"),
            ("ss_item_sk", "integer"), ("ss_customer_sk", "integer"),
            ("ss_cdemo_sk", "integer"), ("ss_hdemo_sk", "integer"),
            ("ss_addr_sk", "integer"), ("ss_store_sk", "integer"),
            ("ss_promo_sk", "integer"), ("ss_ticket_number", "long"),
            ("ss_quantity", "integer")) + tuple(
                (name, f"decimal({money_precision},2)") for name in MONEY)


COLUMNS = columns_of()


def schema_string(money_precision: int = MONEY_PRECISION) -> str:
    return json.dumps({"type": "struct", "fields": [
        {"name": name, "type": kind, "nullable": name not in NEVER_NULL,
         "metadata": {}} for name, kind in columns_of(money_precision)]},
        separators=(",", ":"))


@dataclasses.dataclass(frozen=True)
class Batch:
    """What one micro-batch of `adds` files of `rows` rows is, in
    TPC-DS's units."""

    adds: int
    rows: int

    @property
    def batch_rows(self) -> int:
        return self.adds * self.rows

    @property
    def rows_a_day(self) -> int:
        """A sold date's rows: scale factor 1,000's, in batches of this
        size (19.75 of them), so that a table of smaller files keeps
        the ratio of a batch to a day."""
        return (self.batch_rows * (ROWS_AT_SF1000 // SOLD_DATES)
                // SF1000_BATCH_ROWS)

    def day(self, v):
        """`ss_sold_date_sk` of batch `v`'s first row."""
        return FIRST_DATE_SK + v * self.batch_rows // self.rows_a_day

    def last_day(self, v):
        return FIRST_DATE_SK + ((v + 1) * self.batch_rows - 1) \
            // self.rows_a_day

    def batches_of(self, day_lo: int, day_hi: int):
        """The batches with a row on a date of `day_lo..day_hi`, as a
        half-open range (a batch too many at either end does no harm)."""
        first = (day_lo - FIRST_DATE_SK) * self.rows_a_day // self.batch_rows
        last = (day_hi + 1 - FIRST_DATE_SK) * self.rows_a_day \
            // self.batch_rows + 1
        return max(0, first - 1), max(0, last + 1)


def _priced_pool(seed: int) -> dict:
    """`POOL` rows of `ss_quantity` and the twelve money columns in
    cents, as they were priced: dsdgen's pricing, recalled
    (`assumed.pricing`)."""
    rng = np.random.default_rng([seed, 28])
    cost = rng.integers(100, 10_001, POOL)                  # 1.00 .. 100.00
    lst = cost * (100 + rng.integers(0, 201, POOL)) // 100  # markup <= 200%
    sales = lst * (100 - rng.integers(0, 101, POOL)) // 100
    qty = rng.integers(1, QUANTITY + 1, POOL)
    ext_sales, ext_cost, ext_list = sales * qty, cost * qty, lst * qty
    coupon = np.where(rng.random(POOL) < 0.2,
                      (ext_sales * rng.random(POOL)).astype(np.int64), 0)
    net_paid = ext_sales - coupon
    tax = net_paid * rng.integers(0, 10, POOL) // 100
    columns = (cost, lst, sales, ext_list - ext_sales, ext_sales, ext_cost,
               ext_list, tax, coupon, net_paid, net_paid + tax,
               net_paid - ext_cost)
    return {"ss_quantity": qty, **dict(zip(MONEY, columns))}


def _binomial_cdf(n: int, p: float) -> np.ndarray:
    """P(X <= k) for k = 0..n-1 of Binomial(n, p), by the recurrence."""
    pmf = np.empty(n + 1)
    pmf[0] = (1 - p) ** n
    for k in range(n):
        pmf[k + 1] = pmf[k] * (n - k) / (k + 1) * p / (1 - p)
    return np.cumsum(pmf)[:n]


def _number(a: np.ndarray) -> pa.Array:
    return pc.cast(pa.array(a), pa.string())


def _money(cents: np.ndarray) -> list:
    """The pieces of a decimal(7,2) as JSON: the units with their sign,
    then the point and two places."""
    units, places = np.divmod(np.abs(cents), 100)
    point = np.empty((len(cents), 3), np.uint8)
    point[:, 0] = ord(".")
    point[:, 1], point[:, 2] = 48 + places // 10, 48 + places % 10
    behind = pa.FixedSizeBinaryArray.from_buffers(
        pa.binary(3), len(cents), [None, pa.py_buffer(point)]).cast(
            pa.binary()).cast(pa.string())
    if not (cents < 0).any():
        return [_number(units), behind]
    return [pa.array(np.where(cents < 0, "-", "")), _number(units), behind]


class FileStats:
    """The stats of every file id, from the seed: the numbers a block
    of ids at a time (`values`), the JSON strings of them (`strings`)."""

    def __init__(self, adds_per_commit: int, rows_per_file: int, seed: int,
                 money_base: int = 0):
        self.batch = Batch(adds_per_commit, rows_per_file)
        self.seed = seed
        # whole units of currency under every money value: 0 in the
        # deployment; the tests' table of decimal(18,2) sets it where no
        # double holds a value's cents
        self.money_base = money_base
        self.priced = _priced_pool(seed)
        self.pool = {name: np.sort(self.priced[name]) for name in MONEY}
        self.small_rows = np.minimum(SMALL_ROWS, rows_per_file)
        self.null_cdf = {int(r): _binomial_cdf(int(r), NULL_SHARE)
                         for r in {rows_per_file, *self.small_rows}}
        self.names = [name for name, _ in COLUMNS]
        self.tickets = max(self.batch.batch_rows // ITEMS_PER_TICKET,
                           2 * ENDS + 1)            # a batch's

    def rows_of(self, ids: np.ndarray) -> np.ndarray:
        """`numRecords` of the file ids `ids`."""
        u = (_hash_by_id(self.seed, ids, 1)[:, 0] >> np.uint64(11)).astype(
            np.float64) / (1 << 53) * SMALL_ONE_IN
        which = np.minimum((u * len(SMALL_ROWS)).astype(np.int64),
                           len(SMALL_ROWS) - 1)
        return np.where(u < 1.0, self.small_rows[which], self.batch.rows)

    def _small(self, ids: np.ndarray, rows: np.ndarray) -> dict:
        """{column: (min, max)} of the small files `ids` of `rows` rows,
        each row a place in its batch, seven keys and a priced row of
        the pool."""
        batch, n_cols, most_rows = self.batch, len(COLUMNS), max(SMALL_ROWS)
        words = _hash_by_id(self.seed, ids, 3 * n_cols + 3 * most_rows)[
            :, 3 * n_cols:].reshape(len(ids), most_rows, 3)

        def field(j):       # 21 bits, three to a word
            return ((words[..., j // 3] >> np.uint64(21 * (j % 3)))
                    & np.uint64((1 << 21) - 1)).astype(np.int64)

        held = np.arange(most_rows) < rows[:, None]     # the file's rows
        top = np.iinfo(np.int64).max

        def ends(values):
            return (np.where(held, values, top).min(axis=1),
                    np.where(held, values, -top).max(axis=1))

        v = ids.astype(np.int64) // batch.adds
        at = v[:, None] * batch.batch_rows + (field(0) * batch.batch_rows >> 21)
        found = {
            "ss_sold_date_sk": FIRST_DATE_SK + at // batch.rows_a_day,
            "ss_sold_time_sk": at % batch.rows_a_day * SECONDS_A_DAY
            // batch.rows_a_day,
            "ss_ticket_number": 1 + at * self.tickets // batch.batch_rows}
        for j, (name, domain) in enumerate(KEYS):
            found[name] = 1 + (field(1 + j) * domain >> 21)
        row = field(8) * POOL >> 21                     # of the priced pool
        found["ss_quantity"] = self.priced["ss_quantity"][row]
        for name in MONEY:
            found[name] = 100 * self.money_base + self.priced[name][row]
        return {name: ends(values) for name, values in found.items()}

    def values(self, ids: np.ndarray) -> dict:
        """{column: (min, max, nullCount)} of the file ids `ids`, money
        in cents, and under `"numRecords"` their rows."""
        batch, rows = self.batch, self.rows_of(ids)
        n_cols = len(COLUMNS)
        words = _hash_by_id(self.seed, ids, 3 * n_cols)
        u = (words >> np.uint64(11)).astype(np.float64) / (1 << 53)
        least = 1.0 - u[:, :n_cols] ** (1.0 / batch.rows)  # quantile of the min
        most = u[:, n_cols:2 * n_cols] ** (1.0 / batch.rows)    # and of the max
        v = ids.astype(np.int64) // batch.adds
        near = lambda k: (u[:, k] * ENDS).astype(np.int64)    # noqa: E731
        out = {}
        out["ss_sold_date_sk"] = batch.day(v), batch.last_day(v)
        first_row = v * batch.batch_rows
        second = lambda row: row % batch.rows_a_day * SECONDS_A_DAY \
            // batch.rows_a_day                                 # noqa: E731
        t_lo, t_hi = second(first_row), second(first_row + batch.batch_rows - 1)
        over_midnight = out["ss_sold_date_sk"][0] != out["ss_sold_date_sk"][1]
        out["ss_sold_time_sk"] = (
            np.where(over_midnight, 0, t_lo) + near(1),
            np.where(over_midnight, SECONDS_A_DAY - 1, t_hi)
            - near(n_cols + 1))
        out["ss_ticket_number"] = (1 + v * self.tickets + near(9),
                                   (v + 1) * self.tickets - near(n_cols + 9))
        for name, domain in KEYS + (("ss_quantity", QUANTITY),):
            k = self.names.index(name)
            out[name] = (1 + (least[:, k] * domain).astype(np.int64),
                         1 + np.minimum((most[:, k] * domain).astype(np.int64),
                                        domain - 1))
        for name in MONEY:
            k, sorted_ = self.names.index(name), self.pool[name]
            out[name] = (
                100 * self.money_base
                + sorted_[(least[:, k] * POOL).astype(np.int64)],
                100 * self.money_base
                + sorted_[np.minimum((most[:, k] * POOL).astype(np.int64),
                                     POOL - 1)])
        out = {name: (np.minimum(lo, hi), hi)
               for name, (lo, hi) in out.items()}
        small = np.flatnonzero(rows < batch.rows)
        if small.size:
            for name, ends in self._small(ids[small], rows[small]).items():
                out[name][0][small], out[name][1][small] = ends
        nulls = np.zeros((len(ids), n_cols), np.int64)
        for r, cdf in self.null_cdf.items():
            of_r = rows == r
            nulls[of_r] = np.minimum(np.searchsorted(
                cdf, u[of_r, 2 * n_cols:], side="right"), r - 1)
        nulls[:, [self.names.index(name) for name in NEVER_NULL]] = 0
        out = {name: out[name] + (nulls[:, k],)
               for k, name in enumerate(self.names)}
        out["numRecords"] = rows
        return out

    def strings(self, ids: np.ndarray) -> pa.Array:
        """The stats JSON of the file ids `ids`, in the form and key
        order a Delta writer gives them."""
        found = self.values(ids)
        pieces = ['{"numRecords":', _number(found["numRecords"])]
        for group, at in (("minValues", 0), ("maxValues", 1),
                          ("nullCount", 2)):
            pieces.append(',"%s":{' % group)
            for k, (name, kind) in enumerate(COLUMNS):
                pieces.append('%s"%s":' % ("," if k else "", name))
                pieces += (_money(found[name][at])
                           if kind.startswith("decimal") and at < 2
                           else [_number(found[name][at])])
            pieces.append("}")
        pieces.append("}")
        # constants that meet are one argument
        args, text = [], ""
        for piece in pieces:
            if isinstance(piece, str):
                text += piece
            else:
                args += [text, piece]
                text = ""
        return pc.binary_join_element_wise(*args, text, "")


@dataclasses.dataclass
class Manifest(deltalog.Manifest):
    adds_per_commit: int = 0
    stats: FileStats = None

    def scan_expected(self, day_lo: int, day_hi: int,
                      bucket=None) -> np.ndarray:
        """Ids of the live files whose stats admit a row with
        `day_lo <= ss_sold_date_sk <= day_hi` and, with `bucket`
        (`(q_lo, q_hi, p, c, w)`, whole numbers as `query28.tpl`
        substitutes them, the three amounts over the table's
        `money_base`), `ss_quantity BETWEEN q_lo AND q_hi AND
        (ss_list_price BETWEEN p AND p+10 OR ss_coupon_amt BETWEEN c AND
        c+1000 OR ss_wholesale_cost BETWEEN w AND w+20)`; with a list of
        buckets, any of them, as the query's six. From the window's
        files' numbers alone, money in cents."""
        first, last = self.stats.batch.batches_of(day_lo, day_hi)
        first = min(first * self.adds_per_commit, len(self.alive))
        last = min(last * self.adds_per_commit, len(self.alive))
        if last <= first:
            return np.empty(0, np.int64)
        found = self.stats.values(np.arange(first, last))
        base = self.stats.money_base

        def between(name, lo, hi, unit=1):
            least, most, _ = found[name]
            return (most >= lo * unit) & (least <= hi * unit)

        keep = self.alive[first:last] & between("ss_sold_date_sk",
                                                day_lo, day_hi)
        if bucket is not None:
            any_of = np.zeros(last - first, bool)
            for q_lo, q_hi, p, c, w in ([bucket] if isinstance(bucket, tuple)
                                        else bucket):
                p, c, w = base + p, base + c, base + w
                any_of |= between("ss_quantity", q_lo, q_hi) & (
                    between("ss_list_price", p, p + 10, 100)
                    | between("ss_coupon_amt", c, c + 1000, 100)
                    | between("ss_wholesale_cost", w, w + 20, 100))
            keep &= any_of
        return first + np.flatnonzero(keep)


class _StatsByBlock:
    """`stats_of(fid)` for `deltalog`'s commit lines: the strings of a
    block of ids made at the first call for one of them."""

    BLOCK = 1 << 13

    def __init__(self, stats: FileStats):
        self.stats, self.blocks = stats, {}

    def __call__(self, fid: int) -> str:
        block = fid // self.BLOCK
        if block not in self.blocks:
            ids = np.arange(block * self.BLOCK, (block + 1) * self.BLOCK)
            self.blocks[block] = self.stats.strings(ids).to_pylist()
        return self.blocks[block][fid % self.BLOCK]


# rows of the checkpoint a block, and a row group: ~0.2 GB of stats
# strings at this width, far under the 2 GiB a chunk's offsets reach
CHECKPOINT_BLOCK = 1 << 17
BLOCK_THREADS = 8       # blocks in the making at a time: ~2 GB held


def generate(root: str, params: dict, seed: int) -> Manifest:
    """`deltalog.generate` with this module's schema and stats. `params`
    as there, and `rows_per_file` (1,000); for the tests' table of
    `decimal(18,2)`, `money_precision` and `money_base`."""
    per_commit = int(params["actions_per_commit"])
    n_add = per_commit - int(per_commit * float(params["remove_fraction"]))
    t0 = time.perf_counter()
    stats = FileStats(n_add, int(params.get("rows_per_file", 1000)), seed,
                      int(params.get("money_base", 0)))
    schema = schema_string(int(params.get("money_precision",
                                          MONEY_PRECISION)))
    spec = importlib.util.find_spec("chipbench.gen.deltalog")
    private = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(private)
    private.SCHEMA_STRING = schema
    private.METADATA = private.METADATA.replace(
        json.dumps(deltalog.SCHEMA_STRING), json.dumps(schema))
    private.stats_of = _StatsByBlock(stats)
    rows_of = private._checkpoint_table
    spent = {}

    def checkpoint_rows(live: np.ndarray, adds_per_commit: int):
        """In `deltalog._checkpoint_table`'s place: what `write_blocks`
        below needs to make its rows, a block of files at a time (the
        whole table at once is 4.6 GB that the process would keep)."""
        return live, adds_per_commit

    def write_blocks(rows, where, **options):
        """In `pq.write_table`'s place: `deltalog._checkpoint_table`'s
        two head rows and then the add rows made as arrays, a block of
        files a row group, `BLOCK_THREADS` blocks in the making at a
        time (numpy and Arrow leave the interpreter to the next)."""
        t1 = time.perf_counter()
        live, adds_per_commit = rows
        head = rows_of(np.empty(0, np.int64), adds_per_commit)
        add_type = head.schema.field("add").type

        def rows_from(lo: int) -> pa.Table:
            ids = live[lo:lo + CHECKPOINT_BLOCK]
            n = len(ids)
            number = pc.utf8_lpad(pc.cast(pa.array(ids), pa.string()), 10,
                                  "0")
            no_partition = pa.MapArray.from_arrays(
                pa.array(np.zeros(n + 1, np.int32)),
                pa.array([], pa.string()), pa.array([], pa.string()))
            add = pa.StructArray.from_arrays(
                [pc.binary_join_element_wise("part-", number, ".parquet", ""),
                 no_partition,
                 pa.array(np.full(n, deltalog.FILE_SIZE, np.int64)),
                 pa.array(ids // adds_per_commit), pa.array(np.ones(n, bool)),
                 stats.strings(ids)],
                fields=list(add_type))
            return pa.table({name: add if name == "add"
                             else pa.nulls(n, head.schema.field(name).type)
                             for name in head.column_names})

        starts = list(range(0, len(live), CHECKPOINT_BLOCK))
        with pq.ParquetWriter(where, head.schema, **options) as writer, \
                concurrent.futures.ThreadPoolExecutor(BLOCK_THREADS) as pool:
            writer.write_table(head)
            for at in range(0, len(starts), BLOCK_THREADS):
                for block in pool.map(rows_from,
                                      starts[at:at + BLOCK_THREADS]):
                    writer.write_table(block)
        spent["checkpoint rows made and written"] = time.perf_counter() - t1

    private._checkpoint_table = checkpoint_rows
    private.pq = types.SimpleNamespace(write_table=write_blocks)
    spent["the priced pool"] = time.perf_counter() - t0
    made = private.generate(root, params, seed)
    print(f"store_sales: a batch of {n_add} files is "
          f"{stats.batch.batch_rows} rows, a sold date "
          f"{stats.batch.rows_a_day}; set-up paid "
          f"{time.perf_counter() - t0:.2f} s here, "
          + ", ".join(f"{k} {v:.2f} s" for k, v in spent.items()),
          flush=True)
    return Manifest(**vars(made), adds_per_commit=n_add, stats=stats)
