"""Host pipeline: per operation, the host's side of replay: the
program's `snapshot.replay` spans less what the `replay.wait` spans
below them cover (the blocking read of the chip's answer). On the host
route there is no wait and the whole span counts."""

from chipbench import spans


def read(run):
    mine = spans.named(run.spans, "snapshot.replay")
    if not mine:
        return None
    by_id = {s["span_id"]: s for s in run.spans}

    def below(span, root):
        while span is not None and span is not root:
            span = by_id.get(span["parent_id"])
        return span is root

    waits = spans.named(run.spans, "replay.wait")
    total = 0
    for replay in mine:
        total += replay["duration_ns"] - spans.union_ns(
            (w["start_unix_ns"], spans.end_ns(w))
            for w in waits if below(w, replay))
    return total / 1e6 / len(run.ops)
