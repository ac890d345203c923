"""Device dispatch funnel: per operation, the time in `optimize.curve`:
the `zorder.curve_perm` dispatch (the key matrix up, ranks, interleave
and sort on the chip) and the blocking read of its permutation; the
median over the window's operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.curve")
