"""The plain reference of a scan plan: which live files a scan of
`lo <= x < hi` has to read.

Over `oracle.read_table_state` (sequential replay, per-line
`json.loads`): of the live adds, those whose stats, parsed with
`json.loads`, have `maxValues.x >= lo` and `minValues.x < hi`: the
min/max-intersection set. A file without stats, or without either
bound, cannot be ruled out and is kept. No code of `delta_tpu`.
"""

from __future__ import annotations

import json

from chipbench.reference import oracle


def plan(table_path: str, lo: int, hi: int) -> list:
    """Sorted paths of the files the scan has to read."""
    keep = []
    for (path, _), add in oracle.read_table_state(table_path).live.items():
        stats = json.loads(add["stats"]) if add.get("stats") else {}
        low = stats.get("minValues", {}).get("x")
        high = stats.get("maxValues", {}).get("x")
        if (high is None or high >= lo) and (low is None or low < hi):
            keep.append(path)
    return sorted(keep)
