"""Host pipeline: over the window's operations, the median of the time
in `checkpoint.serialize` (`write/ckpt_pipeline.py::_build`): the
SingleAction table encoded to Parquet, snappy, by one thread."""

from chipbench import op_spans
from chipbench.layers.ckpt_write_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "checkpoint.serialize")
