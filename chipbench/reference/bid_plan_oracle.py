"""The plain reference of a plan over a table of bids: which live files
a scan of `t0 <= dateTime < t1`, with or without `auction IN (...)`,
has to read.

Over `oracle.read_table_state` (sequential replay, per-line
`json.loads`): of the live adds, those whose stats, parsed with
`json.loads`, admit such a row. `dateTime` is a Delta `timestamp`: its
stats are ISO-8601 instants (`datetime.fromisoformat`), and a writer
truncates them to the millisecond, so a stored max stands for any
instant within its millisecond (PROTOCOL.md, "Per-file Statistics"):
the file is kept when `max + 1 ms >= t0` and `min < t1`. A listed
auction can be in a file when `min <= id <= max`; one is enough. A
file without stats, or without a bound, cannot be ruled out by that
bound and is kept. No code of `delta_tpu`.
"""

from __future__ import annotations

import datetime
import json

from chipbench.reference import oracle

MILLISECOND = datetime.timedelta(milliseconds=1)


def _instant(text):
    return None if text is None else datetime.datetime.fromisoformat(text)


def plan(table_path: str, t0: datetime.datetime, t1: datetime.datetime,
         auctions=()) -> list:
    """Sorted paths of the files the scan has to read. `t0` and `t1`
    are zone-aware."""
    keep = []
    for (path, _), add in oracle.read_table_state(table_path).live.items():
        stats = json.loads(add["stats"]) if add.get("stats") else {}
        least, most = stats.get("minValues", {}), stats.get("maxValues", {})
        first = _instant(least.get("dateTime"))
        last = _instant(most.get("dateTime"))
        if last is not None and last + MILLISECOND < t0:
            continue
        if first is not None and first >= t1:
            continue
        low, high = least.get("auction"), most.get("auction")
        if auctions and not any((low is None or low <= a)
                                and (high is None or a <= high)
                                for a in auctions):
            continue
        keep.append(path)
    return sorted(keep)
