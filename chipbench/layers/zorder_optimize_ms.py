"""Entry points: over the window's operations, the median of the
program's `command.optimize` span (`commands/optimize.py`): OPTIMIZE
... ZORDER BY from the transaction's build to its commit and hooks."""

from chipbench import op_spans

OP = "optimize-zorder"


def read(run):
    return op_spans.median_ms(run, OP, "command.optimize")
