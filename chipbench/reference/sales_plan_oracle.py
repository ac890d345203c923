"""The plain reference of a plan over a table of TPC-DS `store_sales`:
which live files a scan of the sold dates `day_lo..day_hi`, with or
without one of Query 28's buckets, has to read.

Over `oracle.read_table_state` (sequential replay, per-line
`json.loads`): of the live adds, those whose stats admit such a row.
The stats are parsed with `json.loads(..., parse_float=decimal.Decimal)`,
so a money column's `18.01` is the decimal its digits state and never a
double, and the predicate is evaluated as the tree it is: `and` / `or`
of `min <= hi and max >= lo`, in `Decimal` and `int`, nothing
distributed and no lanes. A column whose `nullCount` equals
`numRecords` holds no value, so no range over it admits a row
(PROTOCOL.md, "Per-file Statistics"). A file without stats, or without
a bound, cannot be ruled out by that bound and is kept. No code of
`delta_tpu`.
"""

from __future__ import annotations

import decimal
import json

from chipbench.reference import oracle


def _between(stats: dict, name: str, lo, hi) -> bool:
    """Can the file hold a row with `lo <= name <= hi`?"""
    nulls = stats.get("nullCount", {}).get(name)
    rows = stats.get("numRecords")
    if nulls is not None and rows is not None and nulls == rows:
        return False
    least = stats.get("minValues", {}).get(name)
    most = stats.get("maxValues", {}).get(name)
    return (least is None or least <= hi) and (most is None or most >= lo)


def admits(stats: dict, day_lo: int, day_hi: int, bucket=None) -> bool:
    if not _between(stats, "ss_sold_date_sk", day_lo, day_hi):
        return False
    if bucket is None:
        return True
    return any(_between(stats, "ss_quantity", q_lo, q_hi) and (
        _between(stats, "ss_list_price", p, p + 10)
        or _between(stats, "ss_coupon_amt", c, c + 1000)
        or _between(stats, "ss_wholesale_cost", w, w + 20))
        for q_lo, q_hi, p, c, w in ([bucket] if isinstance(bucket, tuple)
                                    else bucket))


def plan(table_path: str, day_lo: int, day_hi: int, bucket=None) -> list:
    """Sorted paths of the files the scan has to read. `bucket` is
    `(q_lo, q_hi, p, c, w)` (`ss_quantity BETWEEN q_lo AND q_hi`, then
    the three amounts), whole numbers or `decimal.Decimal`s, or a list
    of such, any of which."""
    keep = []
    for (path, _), add in oracle.read_table_state(table_path).live.items():
        stats = json.loads(add["stats"], parse_float=decimal.Decimal) \
            if add.get("stats") else {}
        if admits(stats, day_lo, day_hi, bucket):
            keep.append(path)
    return sorted(keep)
