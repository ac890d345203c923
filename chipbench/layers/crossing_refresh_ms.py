"""Entry points: over the operations whose landing brought a
checkpoint (`crossing`), the median of the program's `table.update`
plus the `scan.plan` after it: the refresh across a checkpoint, a full
load from it and a full index build today."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "crossing", "table.update", "scan.plan")
