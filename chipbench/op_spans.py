"""The program's spans by the operation they ran in: what the readers
of a cell whose operations are of several kinds share."""

from __future__ import annotations

import statistics

from chipbench import spans


def median_ms(run, kind: str, *names):
    """Over the window's operations of `kind`, the median of the time
    in the spans called one of `names` that started inside the
    operation; None where there is no such operation or no such span."""
    totals = []
    for op in run.ops:
        if op["kind"] != kind:
            continue
        mine = spans.named(
            spans.inside(run.spans, op["start_unix_ns"], op["end_unix_ns"]),
            *names)
        if mine:
            totals.append(sum(s["duration_ns"] for s in mine) / 1e6)
    return statistics.median(totals) if totals else None
