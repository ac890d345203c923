"""Device dispatch funnel: per operation, the time the driving thread
is blocked on the chip's answer: the program's `replay.wait` and
`parse.wait` spans of this thread, which drives the operations (a wait
on a worker thread overlaps them and is not on an operation's path)."""

import threading

from chipbench import spans


def read(run):
    mine = [s for s in spans.named(run.spans, "replay.wait", "parse.wait")
            if s["thread_id"] == threading.get_ident()]
    if not mine:
        return None
    return sum(s["duration_ns"] for s in mine) / 1e6 / len(run.ops)
