"""Entry points: median duration of the program's `scan.plan` span over
the operations that only plan (a refresh's plan rebuilds the stats
index and is read by `refresh_ms`)."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "plan", "scan.plan")
