"""Entry points: per operation, the time in `optimize.commit`: the
removes and adds handed to the transaction, its commit under snapshot
isolation and the post-commit hooks; the median over the window's
operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.commit")
