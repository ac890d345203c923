"""Host pipeline: per operation, the host's side of a sharded replay:
`replay_host_ms`' reading (the program's `snapshot.replay` spans less
what the `replay.wait` spans below them cover). None on a program whose
sharded route has no `replay.wait`: there the blocking read of the four
chips would be counted as host work."""

from chipbench import spans
from chipbench.layers import replay_host_ms


def read(run):
    if not spans.named(run.spans, "replay.wait"):
        return None
    return replay_host_ms.read(run)
