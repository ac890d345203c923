"""Entry points: over the operations that follow a writer's batch, the
median of `snapshot.update` plus the `scan.plan` after it."""

import statistics

from chipbench import spans


def read(run):
    totals = []
    for op in run.ops:
        if op["kind"] != "refresh":
            continue
        mine = spans.named(
            spans.inside(run.spans, op["start_unix_ns"], op["end_unix_ns"]),
            "snapshot.update", "scan.plan")
        if mine:
            totals.append(sum(s["duration_ns"] for s in mine) / 1e6)
    return statistics.median(totals) if totals else None
