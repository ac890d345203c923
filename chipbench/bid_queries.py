"""The queries of the cells over a table of bids, as a user of the
library writes them: `chipbench/system.py::DeltaTpu.plan` is fixed to
the column `x`, so the predicate over a bid's columns is built here,
and a driver takes `plan_bids` of the system it was given where that
system has one of its own (the tests' broken systems) and this one
otherwise."""

from __future__ import annotations


def plan_bids(snapshot, t0, t1, auctions=()) -> list:
    """Paths of the files a scan of `t0 <= dateTime < t1` (zone-aware
    `datetime`s: `dateTime` is a Delta `timestamp`, an instant), and,
    where `auctions` lists any, NEXmark Query 2's selection `auction IN
    (...)`, has to read."""
    from delta_tpu.expressions import col, lit

    pred = (col("dateTime") >= lit(t0)) & (col("dateTime") < lit(t1))
    if len(auctions):
        pred = pred & col("auction").is_in(*auctions)
    return snapshot.scan(filter=pred).file_paths()
