"""Host pipeline: over the window's operations, the median of the time
in `checkpoint.upload` (the encoded file put if absent) and
`checkpoint.hint` (its size read back, `_last_checkpoint` written).
On a program without the second span, the first alone."""

from chipbench import op_spans
from chipbench.layers.ckpt_write_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "checkpoint.upload",
                              "checkpoint.hint")
