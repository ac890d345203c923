"""Entry points: median duration of the program's `scan.plan` span over
the operations that only plan, the window alone and the window with one
of Query 28's buckets together (three of four carry a bucket)."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "plan", "scan.plan")
