"""Host pipeline: per operation, the self time of the program's
`log.columnarize` and `pipeline.parse_window` spans, which is their
duration less what their child spans cover."""

from chipbench import spans


def read(run):
    mine = spans.named(run.spans, "log.columnarize", "pipeline.parse_window")
    if not mine:
        return None
    return sum(spans.self_time_ns(s, run.spans) for s in mine) / 1e6 / len(
        run.ops)
