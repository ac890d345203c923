"""Entry points: over the window's operations, the median of the time
in `hook.snapshot` (`hooks.py::_snapshot_for_hook`): the checkpoint
hook's read of the state it is about to write, served by `update()`
from the commit's own bytes or, where another writer got past the
version, by `snapshot_at`. None on a program whose hooks do not name
it."""

from chipbench import op_spans
from chipbench.layers.ckpt_write_ms import OP


def read(run):
    return op_spans.median_ms(run, OP, "hook.snapshot")
