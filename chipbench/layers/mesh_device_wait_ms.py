"""Device dispatch funnel: per operation, the time the driving thread
is blocked on the four chips' answer: the program's `replay.wait` spans
of this thread (under `replay.shard_reconcile`, the read of the winner
words). None on a program whose sharded route has no such span."""

import threading

from chipbench import spans


def read(run):
    mine = [s for s in spans.named(run.spans, "replay.wait")
            if s["thread_id"] == threading.get_ident()]
    if not mine:
        return None
    return sum(s["duration_ns"] for s in mine) / 1e6 / len(run.ops)
