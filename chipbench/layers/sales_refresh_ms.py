"""Entry points: over the operations that follow a landed commit, the
median of the program's `table.update` plus the `scan.plan` after it."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "table.update", "scan.plan")
