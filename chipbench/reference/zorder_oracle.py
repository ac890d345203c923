"""The plain reference of OPTIMIZE ... ZORDER BY as the configuration
`tpcds-store-sales-day-zorder` states it: numpy, pyarrow and the
standard library. Nothing of `delta_tpu` and no other reference is
imported.

What it does itself: a sequential replay of `_delta_log`'s JSON commits
to the live files at a version (`replay`), the reading of data files by
`pyarrow.parquet` in ascending order of `path` (`read_files`), the
curve (`curve_order`: ranks by `np.argsort(kind="stable")`, the
interleave as a loop over the key's bits on `uint32` arrays, the order
by `np.lexsort`), the expected output (`expected_files`: `table.take`,
cut every `ceil(rows / n_out)` rows), a file's statistics by
`pyarrow.compute` (`file_stats`) and two order-free digests of rows
(`key_digest` of three columns, `row_digest` of all).

The semantics are the program's, as `delta_tpu/ops/zorder.py` and
`commands/optimize.py::_rewrite_bin` state them. Each departure from
upstream's `MultiDimClustering` (recalled; the configuration's
`assumed` lists them):

- upstream ranks a column by `RangePartitionId` over sampled ranges, so
  equal values share an id, ids are approximate, and the rows inside an
  output file are in no stated order. Here a rank is a row's place in
  the stable ascending sort of the column: dense, unique, exact, ties
  by position in the input; the order along the curve is then total.
- the input's order is part of the result for that reason, and is
  stated: the bin's live files in ascending order of `path`, each
  file's rows in file order. Upstream states none.
- a null is filled with 0 (a string with "") before ranking, so nulls
  rank with the zeros and, in a column of positive keys, first.
  Upstream's range partitioner puts nulls first too.
- upstream interleaves the bits of `Int` ids, MSB first, round-robin
  over the columns in the order given; so does this, over 32-bit ranks.
  The program shifts every rank up by the same `32 - bit_length(m - 1)`
  for its padded row count `m`; a common shift changes no order, so
  nothing here knows `m`.
- upstream repartitions by range of the curve key into files of about
  `maxFileSize`; here the ordered rows are cut every `ceil(rows /
  n_out)` rows, `n_out` being `ceil(bytes of the bin's files /
  max_file_size)`.
"""

from __future__ import annotations

import decimal
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

KEY_BITS = 32
NULL_WORD = np.uint64(0x9E3779B97F4A7C15)   # what a null mixes in as
MIX_A = np.uint64(0xBF58476D1CE4E5B9)
MIX_B = np.uint64(0x94D049BB133111EB)


# ---- the log, replayed one commit after another --------------------------

def commit_path(log_dir: str, version: int) -> str:
    return os.path.join(log_dir, f"{version:020d}.json")


def read_commit(log_dir: str, version: int) -> list:
    """The actions of one commit, in the file's order."""
    with open(commit_path(log_dir, version)) as f:
        return [json.loads(line) for line in f if line.strip()]


class Replay:
    """The live files of a table whose log only grows: `at(version)`
    applies the commits it has not seen yet."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.version = -1
        self.live = {}

    def at(self, version: int) -> dict:
        if version < self.version:
            raise ValueError(f"asked for version {version} after "
                             f"{self.version}: this replay only advances")
        while self.version < version:
            self.version += 1
            for action in read_commit(self.log_dir, self.version):
                if "add" in action:
                    self.live[action["add"]["path"]] = action["add"]
                elif "remove" in action:
                    self.live.pop(action["remove"]["path"], None)
        return self.live


def replay(log_dir: str, version: int) -> dict:
    """path -> `add` of every file live at `version`, by applying the
    commits 0..version in turn. No checkpoint is read."""
    return Replay(log_dir).at(version)


def in_partition(live: dict, column: str, value) -> list:
    """The adds of the partition `column = value`, in ascending order of
    `path`: the order the curve's input is read in."""
    want = None if value is None else str(value)
    return [live[path] for path in sorted(live)
            if live[path]["partitionValues"].get(column) == want]


# ---- data files ----------------------------------------------------------

def read_files(table_path: str, paths, columns=None) -> pa.Table:
    """The files' rows, file after file in the order given, each file's
    rows in file order. The partition column is not in the files."""
    return pa.concat_tables(
        pq.read_table(os.path.join(table_path, path),
                      columns=None if columns is None else list(columns))
        for path in paths)


def footer_rows(table_path: str, paths) -> int:
    return sum(pq.read_metadata(os.path.join(table_path, path)).num_rows
               for path in paths)


# ---- the curve -----------------------------------------------------------

def key_values(column: pa.ChunkedArray) -> np.ndarray:
    """A clustering column as what is ranked: nulls filled with 0."""
    column = column.combine_chunks()
    if column.null_count:
        column = pc.fill_null(column, 0)
    return np.asarray(column)


def ranks_of(values: np.ndarray) -> np.ndarray:
    """A row's place in the stable ascending sort of its column."""
    order = np.argsort(values, kind="stable")
    ranks = np.empty(len(values), np.uint32)
    ranks[order] = np.arange(len(values), dtype=np.uint32)
    return ranks


def interleave(ranks: list) -> list:
    """The key words, most significant first: bit `g` of the key, from
    the top, is bit `31 - g // k` of column `g % k`'s rank."""
    k = len(ranks)
    words = [np.zeros(len(ranks[0]), np.uint32) for _ in range(k)]
    for g in range(KEY_BITS * k):
        source = KEY_BITS - 1 - g // k
        word, place = divmod(g, 32)
        bit = (ranks[g % k] >> np.uint32(source)) & np.uint32(1)
        words[word] |= bit << np.uint32(31 - place)
    return words


def curve_order(table: pa.Table, columns) -> np.ndarray:
    """The rows' order along the Z-order curve of `columns`: an int64
    permutation of the table's rows. The keys are unique, as the ranks
    are."""
    words = interleave([ranks_of(key_values(table.column(c)))
                        for c in columns])
    return np.lexsort(words[::-1]).astype(np.int64)     # last key first


def expected_files(table: pa.Table, columns, n_out: int) -> list:
    """The output files' tables, in the order the commit adds them."""
    ordered = table.take(pa.array(curve_order(table, columns)))
    if n_out <= 1 or table.num_rows == 0:
        return [ordered]
    rows = max(1, -(-table.num_rows // n_out))
    return [ordered.slice(start, rows)
            for start in range(0, table.num_rows, rows)]


# ---- statistics ----------------------------------------------------------

def as_integer(value, kind: pa.DataType):
    """A statistic as what it is compared as: cents for a decimal of
    scale 2, the value for an integer; any other kind's as it is."""
    if value is None:
        return None
    if pa.types.is_decimal(kind):
        return int(decimal.Decimal(str(value)).scaleb(kind.scale))
    if pa.types.is_integer(kind):
        return int(value)
    return value


def file_stats(table: pa.Table) -> dict:
    """numRecords, and per column least, most and nulls, as integers
    (`as_integer`); a column of nulls alone has no least and no most."""
    least, most, nulls = {}, {}, {}
    for field in table.schema:
        column = table.column(field.name)
        nulls[field.name] = column.null_count
        if column.null_count < len(column):
            found = pc.min_max(column)
            least[field.name] = as_integer(found["min"].as_py(), field.type)
            most[field.name] = as_integer(found["max"].as_py(), field.type)
    return {"numRecords": table.num_rows, "minValues": least,
            "maxValues": most, "nullCount": nulls}


def stated_rows(add: dict) -> int:
    return json.loads(add["stats"])["numRecords"]


def stated_stats(add: dict, schema: pa.Schema) -> dict:
    """The same of an add's `stats` string; its numbers are parsed as
    decimals, never as binary fractions."""
    said = json.loads(add["stats"], parse_float=decimal.Decimal)
    kinds = {f.name: f.type for f in schema}
    return {
        "numRecords": said["numRecords"],
        "minValues": {c: as_integer(v, kinds[c])
                      for c, v in said.get("minValues", {}).items()},
        "maxValues": {c: as_integer(v, kinds[c])
                      for c, v in said.get("maxValues", {}).items()},
        "nullCount": dict(said.get("nullCount", {}))}


# ---- digests that no order changes ---------------------------------------

def integers_of(column) -> tuple:
    """(values as uint64, validity or None) of an integer or decimal
    column; a decimal gives its unscaled value (cents)."""
    column = column.combine_chunks() if isinstance(
        column, pa.ChunkedArray) else column
    valid = (None if not column.null_count else
             np.asarray(pc.is_valid(column)))
    if pa.types.is_decimal(column.type):
        words = np.frombuffer(column.buffers()[1], np.int64)
        values = words[2 * column.offset:2 * (column.offset + len(column)):2]
    else:
        values = np.asarray(pc.fill_null(column, 0) if column.null_count
                            else column).astype(np.int64)
    return values.view(np.uint64), valid


def mixed(word: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer, on uint64 arrays."""
    word = (word ^ (word >> np.uint64(30))) * MIX_A
    word = (word ^ (word >> np.uint64(27))) * MIX_B
    return word ^ (word >> np.uint64(31))


def row_digest(table: pa.Table, columns=None) -> int:
    """The 64-bit wrapping sum, over the rows, of a mix of the row's
    values in `columns` (all of them by default), a null as a null:
    equal for equal multisets of rows, whatever their order."""
    acc = np.zeros(table.num_rows, np.uint64)
    with np.errstate(over="ignore"):
        for name in columns or table.column_names:
            values, valid = integers_of(table.column(name))
            if valid is not None:
                values = np.where(valid, values, NULL_WORD)
            acc = mixed(acc * np.uint64(31) + values)
        return int(acc.sum(dtype=np.uint64))


KEY_DIGEST_COLUMNS = ("ss_item_sk", "ss_ticket_number", "ss_net_paid")


def key_digest(table: pa.Table) -> int:
    """`row_digest` over an item, its ticket and what was paid: what the
    check of every operation compares, removed against added."""
    return row_digest(table, KEY_DIGEST_COLUMNS)


def wrapping_sum(*digests: int) -> int:
    return sum(digests) % (1 << 64)
