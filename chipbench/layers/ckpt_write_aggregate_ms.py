"""Host pipeline: over the window's operations, the median of the
program's `checkpoint.aggregate` span: the stats lanes taken from the
live table, their upload, the `stats.ckpt_block` launch and the wait
for its block (or the host twin, where the span says
`stats_mode=host`)."""

from chipbench import op_spans
from chipbench.layers.ckpt_write_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "checkpoint.aggregate")
