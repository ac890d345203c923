"""Host pipeline: over the window's operations, the median of the time
in `checkpoint.assemble` (the live table, the tombstones inside
retention, the add and remove struct arrays, the small actions) and
`checkpoint.table` (the SingleAction table over them). None on a
program that does not name them."""

from chipbench import op_spans
from chipbench.layers.ckpt_write_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "checkpoint.assemble",
                              "checkpoint.table")
