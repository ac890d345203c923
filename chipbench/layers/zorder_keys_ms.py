"""Host pipeline: per operation, the time in `optimize.keys`: the
clustering columns' nulls filled and their values made the padded
uint32 key matrix (`ops/zorder.py::curve_keys`); the median over the
window's operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.keys")
