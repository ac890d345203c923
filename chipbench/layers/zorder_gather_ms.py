"""Host pipeline: per operation, the time in `optimize.gather`: the
bin's rows, all columns, taken in the curve's order (`table.take`);
the median over the window's operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.gather")
