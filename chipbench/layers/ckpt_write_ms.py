"""Entry points: over the window's operations, the median of the
program's `checkpoint.write` span (`log/checkpointer.py`): the
checkpoint from the state's live table to `_last_checkpoint`, inside
the tenth commit's post-commit hook."""

from chipbench import op_spans

OP = "commit+checkpoint"


def read(run):
    return op_spans.median_ms(run, OP, "checkpoint.write")
