"""Host pipeline: per operation, the time in `optimize.read`
(`commands/optimize.py::_rewrite_bin`): the bin's files read one after
another in ascending order of path and concatenated; the median over
the window's operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.read")
