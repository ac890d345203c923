"""Host pipeline: per operation, the time in the program's
`checkpoint.read_part` spans: one checkpoint part fetched and decoded
by Arrow up to its table, before anything is made canonical."""

from chipbench import spans


def read(run):
    mine = spans.named(run.spans, "checkpoint.read_part")
    if not mine:
        return None
    return sum(s["duration_ns"] for s in mine) / 1e6 / len(run.ops)
