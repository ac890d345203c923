"""Host pipeline: per operation, the time in `optimize.write`
(`write/writer.py::write_data_files`): the ordered rows cut, encoded
to Parquet and their statistics collected; the median over the window's
operations."""

from chipbench import op_spans
from chipbench.layers.zorder_optimize_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "optimize.write")
