"""Host pipeline: over the `crossing` operations, the median of the
program's `snapshot.load` (list, checkpoint read and decode, replay):
the state rebuilt from the checkpoint that landed."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "crossing", "snapshot.load")
