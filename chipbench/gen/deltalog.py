"""Seeded synthetic `_delta_log`, with the answers it implies.

A fast copy of `benchmarks/workloads.py::synth_delta_log` (same action
form, byte for byte: compact JSON as Delta writers emit it, 80 adds and
20 removes a commit, one long column `x` with stats). Two things are
added. With `checkpoint_interval` it writes the table as a deployment
with log clean-up keeps it: a classic single-file checkpoint, its
`_last_checkpoint` hint, and only the `retained_commits` newest commits
as JSON (older ones are simulated in memory and never written). And it
returns a `Manifest` of what is live at every version, which is the
expected answer of every run: it comes from the generator's own
bookkeeping and shares nothing with `delta_tpu`.

File `i` is `part-<i, 10 digits>.parquet`, created by commit `i // adds
per commit`, with `x` in `[(i + 1) * 1000, (i + 2) * 1000]`.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

FILE_SIZE = 1 << 20
X_STEP = 1000

PROTOCOL = '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}'
SCHEMA_STRING = ('{"type":"struct","fields":[{"name":"x","type":"long",'
                 '"nullable":true,"metadata":{}}]}')
METADATA = json.dumps({
    "metaData": {
        "id": "bench", "format": {"provider": "parquet", "options": {}},
        "schemaString": SCHEMA_STRING,
        "partitionColumns": [], "configuration": {},
    }
})


def path_of(fid: int) -> str:
    return f"part-{fid:010d}.parquet"


def stats_of(fid: int) -> str:
    return ('{"numRecords":1000,"minValues":{"x":%d},"maxValues":{"x":%d},'
            '"nullCount":{"x":0}}' % ((fid + 1) * X_STEP, (fid + 2) * X_STEP))


def add_line(fid: int, version: int) -> str:
    stats = stats_of(fid).replace('"', '\\"')
    return ('{"add":{"path":"%s","partitionValues":{},"size":%d,'
            '"modificationTime":%d,"dataChange":true,"stats":"%s"}}'
            % (path_of(fid), FILE_SIZE, version, stats))


def remove_line(fid: int, version: int) -> str:
    return ('{"remove":{"path":"%s","deletionTimestamp":%d,'
            '"dataChange":true}}' % (path_of(fid), version))


def commit_name(version: int) -> str:
    return f"{version:020d}.json"


def digest_of(ids: np.ndarray) -> str:
    """sha256 of the live paths, sorted and joined by newlines (ids in
    rising order give paths in sorted order: the number is zero-padded)."""
    return hashlib.sha256(
        "\n".join(path_of(int(i)) for i in ids).encode()).hexdigest()


@dataclasses.dataclass
class StagedCommit:
    version: int
    add_lo: int
    add_hi: int
    removed: np.ndarray


@dataclasses.dataclass
class Manifest:
    """What the table holds, as the generator knows it."""

    table_path: str
    staged_dir: str
    version: int              # newest version in `_delta_log`
    alive: np.ndarray         # bool by file id, at `version`
    staged: list              # StagedCommit, rising by version
    load_actions: int         # actions a cold load at `version` reads
    log_bytes: int            # bytes of the files such a load reads
    checkpoint_version: int | None

    def live_ids(self) -> np.ndarray:
        return np.flatnonzero(self.alive)

    def num_files(self) -> int:
        return int(self.alive.sum())

    def size_in_bytes(self) -> int:
        return self.num_files() * FILE_SIZE

    def digest(self) -> str:
        return digest_of(self.live_ids())

    def land(self, k: int) -> None:
        """Move the next `k` staged commits into `_delta_log` (rename,
        as a writer's put-if-absent ends) and advance the manifest."""
        batch, self.staged = self.staged[:k], self.staged[k:]
        log = os.path.join(self.table_path, "_delta_log")
        for c in batch:
            name = commit_name(c.version)
            os.replace(os.path.join(self.staged_dir, name),
                       os.path.join(log, name))
            self.alive[c.removed] = False
            self.alive[c.add_lo:c.add_hi] = True
            self.version = c.version

    def scan_expected(self, lo: int, hi: int) -> np.ndarray:
        """Ids of the live files whose `[min, max]` on `x` can hold a
        row with `lo <= x < hi`: max >= lo and min < hi."""
        first = max(0, -(-lo // X_STEP) - 2)       # (i + 2) * 1000 >= lo
        last = min(len(self.alive), -(-hi // X_STEP) - 1)  # (i + 1) * 1000 < hi
        if last <= first:
            return np.empty(0, np.int64)
        return first + np.flatnonzero(self.alive[first:last])


def _writer_order(ids: np.ndarray) -> np.ndarray:
    """The ids in the order a Spark writer emits a checkpoint's rows:
    the reconstructed state is repartitioned by a hash of the path
    (`spark.databricks.delta.snapshotPartitions`, 50) and each
    partition is replayed into a hash map, so the rows of the one
    checkpoint file follow a hash of the path, and neither the path
    nor the file's age. Here: a multiplicative hash of the file id."""
    hashed = ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    return ids[np.argsort(hashed, kind="stable")]


def _checkpoint_table(live: np.ndarray, adds_per_commit: int) -> pa.Table:
    """The PROTOCOL.md checkpoint schema as Spark writes a classic
    checkpoint of this table: one protocol row, one metaData row, one
    row per live add, in the order of `live`, with its stats as the
    JSON string. No remove row: a checkpoint keeps a tombstone for
    `delta.deletedFileRetentionDuration` (one week by default) after
    its `deletionTimestamp`, and the source's timestamps are the
    commit's version in milliseconds after 1970, so every one of them
    lies decades before any checkpoint's retention window."""
    n = len(live)
    string_map = pa.map_(pa.string(), pa.string())

    def empty_maps(rows: int):
        return pa.MapArray.from_arrays(
            pa.array(np.zeros(rows + 1, np.int32)),
            pa.array([], pa.string()), pa.array([], pa.string()))

    def rows_at(struct: pa.StructArray, at: int) -> pa.Array:
        """`struct` placed from row `at` of n + 2 rows, null elsewhere."""
        head = pa.nulls(at, struct.type)
        tail = pa.nulls(n + 2 - at - len(struct), struct.type)
        return pa.concat_arrays([head, struct, tail])

    ids = [int(i) for i in live]
    add = pa.StructArray.from_arrays(
        [pa.array([path_of(i) for i in ids], pa.string()),
         empty_maps(n),
         pa.array(np.full(n, FILE_SIZE, np.int64)),
         pa.array(live.astype(np.int64) // adds_per_commit),
         pa.array(np.ones(n, bool)),
         pa.array([stats_of(i) for i in ids], pa.string())],
        fields=[pa.field("path", pa.string()),
                pa.field("partitionValues", string_map),
                pa.field("size", pa.int64()),
                pa.field("modificationTime", pa.int64()),
                pa.field("dataChange", pa.bool_()),
                pa.field("stats", pa.string())])
    protocol = pa.StructArray.from_arrays(
        [pa.array([1], pa.int32()), pa.array([2], pa.int32())],
        names=["minReaderVersion", "minWriterVersion"])
    fmt = pa.StructArray.from_arrays(
        [pa.array(["parquet"]), empty_maps(1)],
        fields=[pa.field("provider", pa.string()),
                pa.field("options", string_map)])
    metadata = pa.StructArray.from_arrays(
        [pa.array(["bench"]), fmt, pa.array([SCHEMA_STRING]),
         pa.array([[]], pa.list_(pa.string())), empty_maps(1)],
        fields=[pa.field("id", pa.string()),
                pa.field("format", fmt.type),
                pa.field("schemaString", pa.string()),
                pa.field("partitionColumns", pa.list_(pa.string())),
                pa.field("configuration", string_map)])
    return pa.table({
        "protocol": rows_at(protocol, 0),
        "metaData": rows_at(metadata, 1),
        "add": rows_at(add, 2),
    })


def generate(root: str, params: dict, seed: int) -> Manifest:
    """Write the table under `root` and return its manifest.

    `params`: `commits`, `actions_per_commit`, `remove_fraction`, and
    optionally `checkpoint_interval` with `retained_commits`, and
    `staged_commits` (further commits written beside the table, for a
    mix that lets a writer land them)."""
    commits = int(params["commits"])
    per_commit = int(params["actions_per_commit"])
    n_rm = int(per_commit * float(params["remove_fraction"]))
    n_add = per_commit - n_rm
    interval = params.get("checkpoint_interval")
    staged_n = int(params.get("staged_commits", 0))
    total = commits + staged_n
    newest = commits - 1
    ckpt = newest - newest % interval if interval else None
    first_written = (max(0, ckpt - int(params["retained_commits"]))
                     if interval else 0)

    table = os.path.join(root, "table")
    log = os.path.join(table, "_delta_log")
    staged_dir = os.path.join(root, "staged")
    os.makedirs(log)
    os.makedirs(staged_dir)

    draws = np.random.default_rng(seed).random((total, n_rm))
    alive: list = []
    staged: list = []
    ckpt_live = None
    fid = 0
    log_bytes = 0
    load_actions = 0
    for v in range(total):
        removed = []
        for u in draws[v, :min(n_rm, len(alive))]:
            i = int(u * len(alive))
            removed.append(alive[i])
            alive[i] = alive[-1]
            alive.pop()
        alive.extend(range(fid, fid + n_add))
        if v >= first_written:
            lines = [PROTOCOL, METADATA] if v == 0 else []
            lines += [remove_line(r, v) for r in removed]
            lines += [add_line(a, v) for a in range(fid, fid + n_add)]
            data = ("\n".join(lines) + "\n").encode()
            where = log if v <= newest else staged_dir
            with open(os.path.join(where, commit_name(v)), "wb") as f:
                f.write(data)
            if v <= newest and (ckpt is None or v > ckpt):
                log_bytes += len(data)
                load_actions += len(lines)
        if v > newest:
            staged.append(StagedCommit(v, fid, fid + n_add,
                                       np.array(removed, np.int64)))
        fid += n_add
        if v == ckpt:
            ckpt_live = _writer_order(np.array(alive, np.int64))
        if v == newest:
            alive_now = np.zeros(total * n_add, bool)
            alive_now[alive] = True

    if ckpt is not None:
        name = os.path.join(log, f"{ckpt:020d}.checkpoint.parquet")
        # parquet-mr's defaults, as Spark writes a checkpoint
        pq.write_table(_checkpoint_table(ckpt_live, n_add), name,
                       compression="snappy", use_dictionary=True,
                       data_page_version="1.0")
        size = os.path.getsize(name)
        with open(os.path.join(log, "_last_checkpoint"), "w") as f:
            f.write(json.dumps(
                {"version": ckpt, "size": len(ckpt_live) + 2,
                 "sizeInBytes": size, "numOfAddFiles": len(ckpt_live)},
                separators=(",", ":")))
        log_bytes += size
        load_actions += len(ckpt_live) + 2
    return Manifest(table, staged_dir, newest, alive_now, staged,
                    load_actions, log_bytes, ckpt)
