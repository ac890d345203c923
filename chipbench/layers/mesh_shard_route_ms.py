"""Host pipeline: per operation, what the mesh costs the host on either
side of the launch: the program's `replay.shard_route` (rows binned by
path key modulo the shards, first-appearance coding a shard, the bit
planes) and `replay.shard_gather` (winner words unpacked and scattered
back to row order). A replay on one chip has neither. None on a program
without both spans: the two together are the number."""

from chipbench import spans


def read(run):
    route = spans.named(run.spans, "replay.shard_route")
    gather = spans.named(run.spans, "replay.shard_gather")
    if not route or not gather:
        return None
    return (sum(s["duration_ns"] for s in route + gather) / 1e6
            / len(run.ops))
