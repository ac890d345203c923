"""Entry points: median duration of the program's `scan.plan` span."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.named(run.spans, "scan.plan"))
