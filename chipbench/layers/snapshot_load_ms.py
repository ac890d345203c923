"""Entry points: median duration of the program's `snapshot.load` span
(state reconstruction: list, read, parse or decode, replay)."""

from chipbench import spans


def read(run):
    return spans.median_ms(spans.named(run.spans, "snapshot.load"))
