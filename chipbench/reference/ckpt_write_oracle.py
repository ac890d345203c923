"""The plain reference of a writer's table and of the checkpoint it
leaves: what `drivers/commit_and_checkpoint.py` holds the program to.

Two halves, neither of which runs a line of `delta_tpu` or of another
reference (numpy, hashlib, json and pyarrow.parquet alone):

- `Replay`: the sequential replay of the table as file ids. It starts
  from what is live at the fixture's version and applies one commit
  after another, its removes and then its adds, as PROTOCOL.md's action
  reconciliation has it for a commit whose adds and removes name
  different files. A remove of a file that is not live, or an add of
  one that is, is an error of the traffic and raises.
- `read_counts`, `read_adds`, `read_hint`: a classic checkpoint file
  and `_last_checkpoint` read by pyarrow alone. The counts come from
  the Parquet footer and one read of the `add.size` column, so that
  they can be taken at every operation;
  the adds in full (path, size, modificationTime, stats, in the order
  of the path) are for the check at the warm-up and at the window's
  close. Both take a file that `gen/deltalog.py` wrote (three columns)
  as they take one of the program's (six).
- `lane_aggregates`: what a writer's summary of the table it
  checkpoints has to read, lane by lane, computed from the replay's
  ids alone: the one thing the chip computes in an operation.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

# one leaf of each action that may not be null where the action is there
ACTION_LEAF = {"protocol": "minReaderVersion", "metaData": "id",
               "add": "size", "remove": "path", "txn": "appId",
               "domainMetadata": "domain"}


class Replay:
    """What is live, commit by commit. A commit is anything with
    `version`, `add_lo`, `add_hi` (its adds are the ids in between) and
    `removed` (ids)."""

    def __init__(self, version: int, alive: np.ndarray, file_size: int):
        self.version = version
        self.alive = np.array(alive, bool)
        self.file_size = file_size

    def apply(self, commit) -> None:
        if commit.version != self.version + 1:
            raise ValueError(f"commit {commit.version} after {self.version}")
        removed = np.asarray(commit.removed, np.int64)
        if len(np.unique(removed)) != len(removed) \
                or not self.alive[removed].all():
            raise ValueError(f"commit {commit.version} removes a file that "
                             "is not live")
        if self.alive[commit.add_lo:commit.add_hi].any():
            raise ValueError(f"commit {commit.version} adds a live file")
        self.alive[removed] = False
        self.alive[commit.add_lo:commit.add_hi] = True
        self.version = commit.version

    def live_ids(self) -> np.ndarray:
        return np.flatnonzero(self.alive)

    def num_files(self) -> int:
        return int(self.alive.sum())

    def size_in_bytes(self) -> int:
        return self.num_files() * self.file_size


INT64_MAX, INT64_MIN = np.iinfo(np.int64).max, np.iinfo(np.int64).min


def lane_aggregates(ids: np.ndarray, file_size: int,
                    adds_per_commit: int) -> dict:
    """Least, greatest, sum and nulls over the table of live `ids`, of
    each of a checkpoint's four numeric lanes in the order size,
    modification time (the version of the commit that added the file),
    deletion vector cardinality (no file has one: every row null, so
    the least and the greatest are what an empty set reads, the ends
    of int64) and partition code (one: the table has no partition)."""
    n = len(ids)
    stamps = np.asarray(ids, np.int64) // adds_per_commit
    empty = (INT64_MAX, INT64_MIN)

    def ends(least, greatest):
        return (int(least), int(greatest)) if n else empty

    least, greatest = zip(
        ends(file_size, file_size),
        ends(stamps.min(initial=INT64_MAX), stamps.max(initial=INT64_MIN)),
        empty, ends(0, 0))
    return {"lane_min": list(least), "lane_max": list(greatest),
            "lane_sum": [n * file_size, int(stamps.sum()), 0, 0],
            "lane_nulls": [0, 0, n, 0]}


def sha256_lines(strings) -> str:
    """sha256 of the strings joined by newlines, in the order given
    (`gen/deltalog.py::digest_of`'s digest). The join is Arrow's, into
    one buffer: 2.4M strings make no Python object."""
    if isinstance(strings, pa.ChunkedArray):
        strings = strings.combine_chunks()
    if len(strings) == 0:
        return hashlib.sha256(b"").hexdigest()
    lines = pc.binary_join_element_wise(strings, "", "\n")  # each + "\n"
    data = lines.buffers()[2]
    return hashlib.sha256(memoryview(data)[:data.size - 1]).hexdigest()


def sha256_int64(values) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(values, np.int64).tobytes()).hexdigest()


def read_counts(path: str) -> dict:
    """Rows of the file and of each kind of action by its footer (an
    action's rows are those at which its leaf is not null: the column
    chunks' `num_values` less their statistics' `null_count`; a chunk
    written without statistics is read instead), and the sum of
    `add.size` by one read of that column."""
    file = pq.ParquetFile(path)
    meta = file.metadata
    found = {"rows": meta.num_rows, "add_size": 0}
    found.update({action: 0 for action in ACTION_LEAF})
    leaves = {f"{action}.{leaf}": action
              for action, leaf in ACTION_LEAF.items()}
    for g in range(meta.num_row_groups):
        group = meta.row_group(g)
        for c in range(group.num_columns):
            chunk = group.column(c)
            action = leaves.get(chunk.path_in_schema)
            if action is None:
                continue
            stats = chunk.statistics
            if stats is not None and stats.has_null_count:
                found[action] += chunk.num_values - stats.null_count
            else:
                column = file.read_row_group(
                    g, columns=[chunk.path_in_schema]).column(action)
                found[action] += len(column) - column.null_count
    if found["add"]:
        sizes = file.read(columns=["add.size"]).column("add")
        found["add_size"] = pc.sum(pc.struct_field(sizes, "size")).as_py()
    return found


def read_adds(path: str) -> dict:
    """Every add of the file, in the order of its path: `path` and
    `stats` as string arrays, `size` and `modificationTime` as int64."""
    add = pq.ParquetFile(path).read(columns=[
        "add.path", "add.size", "add.modificationTime",
        "add.stats"]).column("add").combine_chunks()
    add = add.filter(add.is_valid())
    order = pc.sort_indices(add.field("path"))
    return {name: add.field(name).take(order)
            for name in ("path", "size", "modificationTime", "stats")}


def read_hint(log_dir: str) -> dict:
    with open(os.path.join(log_dir, "_last_checkpoint")) as f:
        return json.load(f)
