"""A table of NEXmark bids that a streaming sink keeps: `gen/deltalog.py`'s
log (100 actions a commit, 20% removes, a checkpoint in a writer's row
order, staged commits), with the bid's seven columns in `schemaString`
and, on every add, the stats a Delta writer leaves for them.

A micro-batch (one commit) is 80 files of 1,000 bids: 80,000 bids, which
the generator's 1:3:46 of persons, auctions and bids makes 86,957
events, 8.6957 s of event time at 10,000 events a second. Batch `v`
starts at `t_v = BASE_TIME + v * BATCH_US`; while it runs, 5,217
auctions and 1,739 persons are opened. Every file of a batch holds a
slice of the batch's bids (a streaming sink's layout, as
`gen/deltastream.py`), so its stats are, each drawn per file from the
seed:

- `dateTime` (a Delta `timestamp`, the event time): min `t_v - d`, `d`
  the largest delay among the file's late events (each of 1,000 late
  with probability `PROB_DELAYED_EVENT`, by up to
  `OCCASIONAL_DELAY_SEC`: late data reaches back into the batch
  before); max within the batch's last 8.7 ms. Written as a Delta
  writer writes them, `yyyy-MM-dd'T'HH:mm:ss.SSS'Z'`: truncated to the
  millisecond.
- `auction`: from an auction in flight when the batch began
  (`NUM_IN_FLIGHT_AUCTIONS` before its first) to one of its last;
  `bidder` likewise among `NUM_ACTIVE_PEOPLE`.
- `price`: the generator's `10^(6u) * 100`, so every file spans nearly
  the whole range and the column skips nothing, as in life.
- `channel`, `url`, `extra`: text, min and max cut to the 32 characters
  upstream keeps of a string stat.

The manifest keeps, per file id, the stored `dateTime` and `auction`
bounds, and answers `scan_expected` from them alone: what a plan of an
event-time window, with or without a list of auctions, has to read.
Everything but the stats and the schema is `deltalog`'s own code, run
as a private copy (as `gen/deltastream.py` runs one), so `deltalog`
itself is not touched; the stats strings are built as Arrow arrays, a
block of file ids at a time, never by a Python call a file.
"""

from __future__ import annotations

import dataclasses
import datetime
import importlib.util
import json
import time
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from chipbench.gen import deltalog

# Apache Beam, sdks/java/testing/nexmark: NexmarkConfiguration and
# GeneratorConfig (recalled; `assumed` in the configuration's file)
FIRST_EVENT_RATE = 10_000           # events a second
PERSON_PROPORTION, AUCTION_PROPORTION, BID_PROPORTION = 1, 3, 46
FIRST_AUCTION_ID = FIRST_PERSON_ID = 1000
NUM_IN_FLIGHT_AUCTIONS = 100
NUM_ACTIVE_PEOPLE = 1000
PROB_DELAYED_EVENT = 0.1
OCCASIONAL_DELAY_SEC = 3
BASE_TIME_MS = 1436918400000        # 2015-07-15T00:00:00.000Z
HOT_CHANNELS = ("Google", "Facebook", "Baidu", "Apple")
CHANNELS_NUMBER = 10_000
BASE_URL = "https://www.nexmark.com/"

BIDS_PER_FILE = 1000                # deltalog's numRecords
STRING_PREFIX = 32                  # DataSkippingReader's prefix length
MAX_SLACK_US = 1000                 # a stored max stands for its millisecond
ENDS = 8                            # an end of an id range lies this near it

SCHEMA_STRING = json.dumps({"type": "struct", "fields": [
    {"name": name, "type": kind, "nullable": True, "metadata": {}}
    for name, kind in (("auction", "long"), ("bidder", "long"),
                       ("price", "long"), ("channel", "string"),
                       ("url", "string"), ("dateTime", "timestamp"),
                       ("extra", "string"))]}, separators=(",", ":"))
UTC = datetime.timezone.utc
EPOCH = datetime.datetime(1970, 1, 1, tzinfo=UTC)


@dataclasses.dataclass(frozen=True)
class Batch:
    """What one micro-batch of `adds` files is, in NEXmark's units."""

    adds: int

    @property
    def events(self) -> int:
        total = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION
        return -(-self.adds * BIDS_PER_FILE * total // BID_PROPORTION)

    @property
    def width_us(self) -> int:
        return self.events * 1_000_000 // FIRST_EVENT_RATE

    @property
    def auctions(self) -> int:
        return self.events * AUCTION_PROPORTION // 50

    @property
    def persons(self) -> int:
        return self.events * PERSON_PROPORTION // 50

    def start_us(self, v) -> int:
        """Event time at which batch `v` begins, microseconds since 1970."""
        return BASE_TIME_MS * 1000 + v * self.width_us

    def first_auction(self, v) -> int:
        return FIRST_AUCTION_ID + v * self.auctions

    def first_person(self, v) -> int:
        return FIRST_PERSON_ID + v * self.persons


def instant(us: int) -> datetime.datetime:
    """The UTC `datetime` of `us` microseconds since 1970."""
    return EPOCH + datetime.timedelta(microseconds=int(us))


def _draws(seed: int, tag: str, n: int) -> np.ndarray:
    return np.random.default_rng([seed, zlib.crc32(tag.encode())]).random(n)


def _hash_by_id(seed: int, ids: np.ndarray, columns: int) -> np.ndarray:
    """`columns` 64-bit words a file id, the same whichever ids are
    asked for beside it: splitmix64 of (seed, id, column)."""
    with np.errstate(over="ignore"):
        x = (ids.astype(np.uint64)[:, None] * np.uint64(0x9E3779B97F4A7C15)
             + np.arange(1, columns + 1, dtype=np.uint64)
             * np.uint64(0xD1B54A32D192ED03)
             + np.uint64(seed) * np.uint64(0x94D049BB133111EB))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def _fixed_text(n: int, *parts) -> pa.Array:
    """One string a row of a fixed width: each part a constant (`str`)
    or a `[n, k]` matrix of character codes."""
    width = sum(len(p) if isinstance(p, str) else p.shape[1] for p in parts)
    out = np.empty((n, width), np.uint8)
    at = 0
    for p in parts:
        if isinstance(p, str):
            p = np.frombuffer(p.encode(), np.uint8)
        out[:, at:at + p.shape[-1]] = p
        at += p.shape[-1]
    return pa.FixedSizeBinaryArray.from_buffers(
        pa.binary(width), n, [None, pa.py_buffer(out)]).cast(
            pa.binary()).cast(pa.string())


def _stamp(us: np.ndarray) -> np.ndarray:
    """`yyyy-MM-dd'T'HH:mm:ss.SSS'Z'` of microseconds since 1970, as a
    `[n, 24]` matrix of character codes: a Delta writer's form of a
    timestamp stat in the session zone UTC, cut to the millisecond."""
    days, ms = np.divmod(us // 1000, 86_400_000)
    distinct, which = np.unique(days, return_inverse=True)
    dates = np.array([(EPOCH + datetime.timedelta(days=int(d))).strftime(
        "%Y-%m-%dT").encode() for d in distinct], "S11")
    out = np.empty((len(us), 24), np.uint8)
    out[:, :11] = dates.view(np.uint8).reshape(-1, 11)[which]
    at = 11
    for value, digits, then in ((ms // 3_600_000, 2, ":"),
                                (ms // 60_000 % 60, 2, ":"),
                                (ms // 1000 % 60, 2, "."),
                                (ms % 1000, 3, "Z")):
        for k in range(digits):
            out[:, at + k] = 48 + value // 10 ** (digits - 1 - k) % 10
        out[:, at + digits] = ord(then)
        at += digits + 1
    return out


def _to_ms(us: np.ndarray) -> np.ndarray:
    """Microseconds truncated to the millisecond, as a writer's stats."""
    return us // 1000 * 1000


class FileStats:
    """The stats of every file id, from the seed: the numbers as arrays,
    the JSON strings built a block of ids at a time."""

    def __init__(self, n_files: int, adds_per_commit: int, seed: int):
        self.batch = batch = Batch(adds_per_commit)
        self.seed = seed
        v = np.arange(n_files, dtype=np.int64) // adds_per_commit
        start = batch.start_us(v)
        # the largest of the file's late events' delays: each of its
        # events is late with probability 0.1, by up to 3 s
        late = np.random.default_rng([seed, 1]).binomial(
            BIDS_PER_FILE, PROB_DELAYED_EVENT, n_files)
        reach = np.where(late > 0, _draws(seed, "reach", n_files)
                         ** (1.0 / np.maximum(late, 1)), 0.0)
        d_us = (reach * OCCASIONAL_DELAY_SEC * 1e6).astype(np.int64)
        # the file's last event: one of 1,000 spread over the batch
        eps_us = (_draws(seed, "last", n_files)
                  * (batch.width_us // BIDS_PER_FILE)).astype(np.int64)
        self.time_min = _to_ms(start - d_us)        # as stored, in us
        self.time_max = _to_ms(start + batch.width_us - 1 - eps_us)

        def ends(first, before, opened, tag):
            low = first - before + (_draws(seed, tag + "-lo", n_files)
                                    * ENDS).astype(np.int64)
            high = first + opened - 1 - (_draws(seed, tag + "-hi", n_files)
                                         * ENDS).astype(np.int64)
            return np.maximum(low, FIRST_AUCTION_ID), high

        self.auction_min, self.auction_max = ends(
            batch.first_auction(v), NUM_IN_FLIGHT_AUCTIONS, batch.auctions,
            "auction")
        self.bidder_min, self.bidder_max = ends(
            batch.first_person(v), NUM_ACTIVE_PEOPLE, batch.persons, "bidder")

    def strings(self, ids: np.ndarray) -> pa.Array:
        """The stats JSON of the file ids `ids`, in the form and key
        order a Delta writer gives them."""
        n = len(ids)
        words = _hash_by_id(self.seed, ids, 13)
        u = (words[:, :3] >> np.uint64(11)).astype(np.float64) / (1 << 53)
        letters = 97 + np.ascontiguousarray(words[:, 3:]).view(
            np.uint8).reshape(n, 80) % 26
        # price = round(10^(6u) * 100): the least and the most of 1,000
        least = 1.0 - u[:, 0] ** (1.0 / BIDS_PER_FILE)
        most = u[:, 1] ** (1.0 / BIDS_PER_FILE)
        price = [np.rint(10.0 ** (6.0 * x) * 100).astype(np.int64)
                 for x in (least, most)]
        # half the bids go to the four hot channels, half to channel-<n>
        top = (CHANNELS_NUMBER * u[:, 2] ** (2.0 / BIDS_PER_FILE)).astype(
            np.int64)

        # letters after the first of a url's and of a filler's 32
        w_url = STRING_PREFIX - len(BASE_URL) - 1
        w_side = w_url + STRING_PREFIX - 1

        def number(a: np.ndarray) -> pa.Array:
            return pc.cast(pa.array(a), pa.string())

        def texts(first: str, url, time_, extra):
            """The three fixed-width values of one side: a url and a
            filler of `STRING_PREFIX` characters that begin with
            `first`, and the time between them."""
            return ('","url":"' + BASE_URL + first, url,
                    '","dateTime":"', _stamp(time_),
                    '","extra":"' + first, extra)

        return pc.binary_join_element_wise(
            '{"numRecords":%d,"minValues":{"auction":' % BIDS_PER_FILE,
            number(self.auction_min[ids]),
            ',"bidder":', number(self.bidder_min[ids]),
            ',"price":', number(price[0]),
            _fixed_text(
                n, ',"channel":"' + HOT_CHANNELS[3],
                *texts("a", letters[:, :w_url], self.time_min[ids],
                       letters[:, w_url:w_side]),
                '"},"maxValues":{"auction":'),
            number(self.auction_max[ids]),
            ',"bidder":', number(self.bidder_max[ids]),
            ',"price":', number(price[1]),
            ',"channel":"channel-', number(top),
            _fixed_text(
                n, *texts("z", letters[:, w_side:w_side + w_url],
                          self.time_max[ids],
                          letters[:, w_side + w_url:2 * w_side]),
                '"},"nullCount":{"auction":0,"bidder":0,"price":0,'
                '"channel":0,"url":0,"dateTime":0,"extra":0}}'),
            "")


@dataclasses.dataclass
class Manifest(deltalog.Manifest):
    adds_per_commit: int = 0
    stats: FileStats = None

    def scan_expected(self, t0_us: int, t1_us: int,
                      auctions=()) -> np.ndarray:
        """Ids of the live files whose stats admit a bid with
        `t0 <= dateTime < t1` (microseconds since 1970) and, where
        `auctions` lists any, `auction` among them: the stored max
        standing for any instant within its millisecond, an id between
        the file's least and most. From the per-file arrays alone."""
        s, per = self.stats, self.adds_per_commit
        width, base = s.batch.width_us, s.batch.start_us(0)
        # no file reaches back past its batch's start by a batch's width
        first = max(0, (t0_us - base) // width - 1) * per
        last = min(len(self.alive), max(0, (t1_us - base) // width + 2) * per)
        if last <= first:
            return np.empty(0, np.int64)
        at = slice(first, last)
        keep = (self.alive[at] & (s.time_max[at] + MAX_SLACK_US >= t0_us)
                & (s.time_min[at] < t1_us))
        if len(auctions):
            listed = np.zeros(last - first, bool)
            for a in auctions:
                listed |= (s.auction_min[at] <= a) & (a <= s.auction_max[at])
            keep &= listed
        return first + np.flatnonzero(keep)


class _StatsByBlock:
    """`stats_of(fid)` for `deltalog`'s commit lines: the strings of a
    block of ids made at the first call for one of them."""

    BLOCK = 1 << 15

    def __init__(self, stats: FileStats, n_files: int):
        self.stats, self.n_files, self.blocks = stats, n_files, {}

    def __call__(self, fid: int) -> str:
        block = fid // self.BLOCK
        if block not in self.blocks:
            ids = np.arange(block * self.BLOCK,
                            min(self.n_files, (block + 1) * self.BLOCK))
            self.blocks[block] = self.stats.strings(ids).to_pylist()
        return self.blocks[block][fid % self.BLOCK]


def stats_column(stats: FileStats, ids: np.ndarray) -> pa.Array:
    """The stats strings of `ids` in their order, as one array."""
    step = 1 << 19      # bounds the pieces held beside the strings
    return pa.concat_arrays([stats.strings(ids[lo:lo + step])
                             for lo in range(0, len(ids), step)])


def generate(root: str, params: dict, seed: int) -> Manifest:
    """`deltalog.generate` with this module's schema and stats. `params`
    as there."""
    per_commit = int(params["actions_per_commit"])
    n_add = per_commit - int(per_commit * float(params["remove_fraction"]))
    n_files = n_add * (int(params["commits"])
                       + int(params.get("staged_commits", 0)))
    t0 = time.perf_counter()
    stats = FileStats(n_files, n_add, seed)
    spec = importlib.util.find_spec("chipbench.gen.deltalog")
    private = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(private)
    private.SCHEMA_STRING = SCHEMA_STRING
    private.METADATA = private.METADATA.replace(
        json.dumps(deltalog.SCHEMA_STRING), json.dumps(SCHEMA_STRING))
    private.stats_of = _StatsByBlock(stats, n_files)
    rows_of = private._checkpoint_table
    spent = {}

    def checkpoint_table(live: np.ndarray, adds_per_commit: int) -> pa.Table:
        """`deltalog._checkpoint_table` with the two string columns of
        its add rows made as arrays (its Python lists take 12 s at 2.4M
        files and a fifth of this width)."""
        t1 = time.perf_counter()
        head = rows_of(np.empty(0, np.int64), adds_per_commit)
        n = len(live)
        add_type = head.schema.field("add").type
        number = pc.utf8_lpad(pc.cast(pa.array(live), pa.string()), 10, "0")
        no_partition = pa.MapArray.from_arrays(
            pa.array(np.zeros(n + 1, np.int32)),
            pa.array([], pa.string()), pa.array([], pa.string()))
        add = pa.StructArray.from_arrays(
            [pc.binary_join_element_wise("part-", number, ".parquet", ""),
             no_partition,
             pa.array(np.full(n, deltalog.FILE_SIZE, np.int64)),
             pa.array(live // adds_per_commit), pa.array(np.ones(n, bool)),
             stats_column(stats, live)],
            fields=list(add_type))
        columns = {}
        for name in ("protocol", "metaData"):
            rows = head.column(name).combine_chunks()
            columns[name] = pa.concat_arrays([rows, pa.nulls(n, rows.type)])
        columns["add"] = pa.concat_arrays([pa.nulls(2, add_type), add])
        spent["checkpoint rows"] = time.perf_counter() - t1
        return pa.table(columns)

    private._checkpoint_table = checkpoint_table
    spent["per-file numbers"] = time.perf_counter() - t0
    made = private.generate(root, params, seed)
    print(f"nexmark bids: {n_files} file ids, a batch of {n_add} files is "
          f"{stats.batch.events} events and {stats.batch.width_us} us of "
          f"event time; set-up paid {time.perf_counter() - t0:.2f} s here, "
          + ", ".join(f"{k} {v:.2f} s" for k, v in spent.items()),
          flush=True)
    return Manifest(**vars(made), adds_per_commit=n_add, stats=stats)
