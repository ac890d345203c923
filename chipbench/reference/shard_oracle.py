"""The reference replay, done the plain way a mesh would do it.

`oracle.py` replays one list of actions with one dict. Here every
action is first dealt to one of `shards` lists by a hash of its path,
each list is replayed in order by itself, and the table's state is the
union of what the lists hold. Because the path decides the list, every
action of one file meets its predecessors in one list and no list needs
to see another: that is the whole argument for sharding a replay, and
this file states it in plain Python so that the tests can hold the
program's sharded route to it (`tests/chipbench/test_chipbench_mesh.py`).

Shares nothing with `delta_tpu`, and nothing with `oracle.py` but the
form of a key (path and deletion vector id): stdlib `json`, `os`, `re`,
`zlib`, and `pyarrow.parquet` for the checkpoint's bytes. It reads what
this benchmark's generators write: a classic single-file checkpoint, if
there is one, and the commits after it. Sized for tests: a dict a
shard, a Python loop an action.
"""

from __future__ import annotations

import json
import os
import re
import zlib

import pyarrow.parquet as pq

_COMMIT = re.compile(r"^(\d{20})\.json$")
_CHECKPOINT = re.compile(r"^(\d{20})\.checkpoint\.parquet$")
_OTHER_CHECKPOINT = re.compile(r"^\d{20}\.checkpoint\..+\.(json|parquet)$")


def by_path_hash(row: int, path: str, shards: int) -> int:
    """The shard of an action: a hash of its path, and nothing else."""
    return zlib.crc32(path.encode()) % shards


def _dv_id(dv) -> str | None:
    if not dv:
        return None
    base = (dv.get("storageType") or "") + (dv.get("pathOrInlineDv") or "")
    if dv.get("offset") is not None:
        return f"{base}@{dv['offset']}"
    return base


def _file_rows(action: dict):
    """The add and the remove of one commit line or checkpoint row (a
    checkpoint row holds None under the kinds it is not)."""
    for kind in ("add", "remove"):
        a = action.get(kind)
        if a is not None:
            yield (kind, a["path"], _dv_id(a.get("deletionVector")),
                   int(a.get("size") or 0))


def file_actions(table_path: str):
    """Every add and remove a cold load of the newest version reads, in
    the log's order: `(kind, path, dv id, size)`, the checkpoint's rows
    first, then each later commit's lines."""
    log = os.path.join(table_path, "_delta_log")
    commits, checkpoints = {}, {}
    for name in sorted(os.listdir(log)):
        if _COMMIT.match(name):
            commits[int(name[:20])] = name
        elif _CHECKPOINT.match(name):
            checkpoints[int(name[:20])] = name
        elif _OTHER_CHECKPOINT.match(name):
            raise ValueError(f"{name}: only classic single-file "
                             "checkpoints are read here")
    start = 0
    if checkpoints:
        version = max(checkpoints)
        table = pq.read_table(os.path.join(log, checkpoints[version]))
        kept = [c for c in ("add", "remove") if c in table.column_names]
        for row in table.select(kept).to_pylist():
            yield from _file_rows(row)
        start = version + 1
    for version in range(start, max(commits, default=start - 1) + 1):
        with open(os.path.join(log, commits[version])) as f:
            for line in f:
                if line.strip():
                    yield from _file_rows(json.loads(line))


class Shard:
    """One list's replay: the last action of a key wins."""

    def __init__(self):
        self.rows = []      # the actions dealt here, in order
        self.files = {}     # (path, dv id) -> (kind, size)

    def replay(self) -> None:
        for kind, path, dv, size in self.rows:
            self.files[(path, dv)] = (kind, size)

    @property
    def live(self) -> dict:
        return {k: size for k, (kind, size) in self.files.items()
                if kind == "add"}

    @property
    def tombstones(self) -> set:
        return {k for k, (kind, _) in self.files.items() if kind == "remove"}


class ShardedState:
    def __init__(self, shards: list):
        self.shards = shards

    @property
    def live(self) -> dict:
        out = {}
        for s in self.shards:
            out.update(s.live)
        return out

    @property
    def tombstones(self) -> set:
        return set().union(*(s.tombstones for s in self.shards))

    def summary(self) -> dict:
        """The part of `oracle.OracleState.summary()` that a replay of
        file actions decides."""
        live = self.live
        return {
            "live_keys": sorted(f"{p}|{dv or ''}" for p, dv in live),
            "tombstone_keys": sorted(f"{p}|{dv or ''}"
                                     for p, dv in self.tombstones),
            "num_live": len(live),
            "live_bytes": sum(live.values()),
        }


def read_table_state(table_path: str, shards: int,
                     shard_of=by_path_hash) -> ShardedState:
    """Deal, replay each list, hand back the lists. `shard_of(row
    number, path, shards)` is the rule that deals; any other rule than
    one of the path alone parts a file's actions and is wrong, which is
    what the tests' controls show."""
    lists = [Shard() for _ in range(shards)]
    for row, action in enumerate(file_actions(table_path)):
        lists[shard_of(row, action[1], shards)].rows.append(action)
    for s in lists:
        s.replay()
    return ShardedState(lists)
