"""Entry points: over the operations that follow a landed commit, the
median of the program's `update.advance` span: the landed commit's
actions replayed on top of the retained state."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "update.advance")
