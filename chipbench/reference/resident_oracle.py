"""The reference of a refresh on state that is kept: an incremental
sharded replay, done the plain way.

`shard_oracle.py` deals every action of a cold load to one of `shards`
lists and replays each list by itself. Here the lists' dicts are *kept*
after the load, and a landed commit advances them by its own actions
alone: each action goes to the dict its path's hash names, in the
commit's order, and the last action of a key wins there. No earlier
commit is read again and no dict looks at another. That is the whole
argument the program's resident route rests on
(`parallel/resident.py`: a landed commit concerns only the shards its
paths fall in, and the state before it need not be re-read), stated in
plain Python so that the tests can hold `update()` to it after every
landed commit (`tests/chipbench/test_chipbench_resident.py`).

The plan of `lo <= x < hi` is `plan_oracle.py`'s rule over the union of
the dicts: of the live adds, those whose stats, parsed with
`json.loads`, have `maxValues.x >= lo` and `minValues.x < hi`; a file
without stats, or without either bound, cannot be ruled out and is kept.

Shares nothing with `delta_tpu`, and nothing with the other references
but the form of a key (path and deletion vector id): stdlib `hashlib`,
`json`, `os`, `re`, `zlib`, and `pyarrow.parquet` for the checkpoint's
bytes. It reads what this benchmark's generators write: a classic
single-file checkpoint, if there is one, and the commits after it.
Sized for tests: a dict a shard, a Python loop an action.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import zlib

import pyarrow.parquet as pq

_COMMIT = re.compile(r"^(\d{20})\.json$")
_CHECKPOINT = re.compile(r"^(\d{20})\.checkpoint\.parquet$")
_OTHER_CHECKPOINT = re.compile(r"^\d{20}\.checkpoint\..+\.(json|parquet)$")


def _dv_id(dv) -> str | None:
    if not dv:
        return None
    base = (dv.get("storageType") or "") + (dv.get("pathOrInlineDv") or "")
    if dv.get("offset") is not None:
        return f"{base}@{dv['offset']}"
    return base


def _file_rows(action: dict):
    """The add and the remove of one commit line or checkpoint row (a
    checkpoint row holds None under the kinds it is not): `(kind, path,
    dv id, size, stats)`."""
    for kind in ("add", "remove"):
        a = action.get(kind)
        if a is not None:
            yield (kind, a["path"], _dv_id(a.get("deletionVector")),
                   int(a.get("size") or 0), a.get("stats"))


def _listing(table_path: str):
    log = os.path.join(table_path, "_delta_log")
    commits, checkpoints = {}, {}
    for name in sorted(os.listdir(log)):
        if _COMMIT.match(name):
            commits[int(name[:20])] = os.path.join(log, name)
        elif _CHECKPOINT.match(name):
            checkpoints[int(name[:20])] = os.path.join(log, name)
        elif _OTHER_CHECKPOINT.match(name):
            raise ValueError(f"{name}: only classic single-file "
                             "checkpoints are read here")
    return commits, checkpoints


def _commit_rows(path: str):
    with open(path) as f:
        for line in f:
            if line.strip():
                yield from _file_rows(json.loads(line))


class KeptState:
    """`shards` dicts, `(path, dv id) -> (kind, size, stats)`, and the
    version they stand at."""

    def __init__(self, shards: int):
        self.shards = [{} for _ in range(shards)]
        self.version = -1
        self.commits_read = []      # every commit version, once

    def shard_of(self, path: str) -> int:
        """The dict of an action: a hash of its path, and nothing else."""
        return zlib.crc32(path.encode()) % len(self.shards)

    def _apply(self, rows) -> None:
        for kind, path, dv, size, stats in rows:
            self.shards[self.shard_of(path)][(path, dv)] = (kind, size, stats)

    def advance(self, table_path: str) -> int:
        """Every commit that landed since `version`, in order, each by
        its own actions alone. Returns how many there were."""
        commits, _ = _listing(table_path)
        newer = sorted(v for v in commits if v > self.version)
        for want, version in enumerate(newer, self.version + 1):
            if version != want:
                raise ValueError(f"commit {want} is missing")
            self._apply(_commit_rows(commits[version]))
            self.commits_read.append(version)
            self.version = version
        return len(newer)

    def live_of(self, shard: int) -> dict:
        return {key: (size, stats)
                for key, (kind, size, stats) in self.shards[shard].items()
                if kind == "add"}

    @property
    def live(self) -> dict:
        out = {}
        for shard in range(len(self.shards)):
            out.update(self.live_of(shard))
        return out

    def summary(self) -> tuple:
        """(number of live files, their total size, sha256 of the sorted
        live paths): what the cell's whole-state comparison compares."""
        live = self.live
        paths = sorted(path for path, _ in live)
        return (len(live), sum(size for size, _ in live.values()),
                hashlib.sha256("\n".join(paths).encode()).hexdigest())

    def plan(self, lo: int, hi: int) -> list:
        """Sorted paths of the files a scan of `lo <= x < hi` has to
        read: the min/max-intersection set over the union."""
        keep = []
        for (path, _), (_, stats) in self.live.items():
            parsed = json.loads(stats) if stats else {}
            low = parsed.get("minValues", {}).get("x")
            high = parsed.get("maxValues", {}).get("x")
            if (high is None or high >= lo) and (low is None or low < hi):
                keep.append(path)
        return sorted(keep)


def load(table_path: str, shards: int) -> KeptState:
    """The cold load: the newest checkpoint's rows dealt to the dicts,
    then every commit after it as `advance` takes it."""
    state = KeptState(shards)
    _, checkpoints = _listing(table_path)
    if checkpoints:
        version = max(checkpoints)
        table = pq.read_table(checkpoints[version])
        kept = [c for c in ("add", "remove") if c in table.column_names]
        for row in table.select(kept).to_pylist():
            state._apply(_file_rows(row))
        state.version = version
    state.advance(table_path)
    return state
