"""The least bytes one shard of the mesh-sharded replay
(`parallel/sharded_replay.py`) has to move in one launch, from the
launch's dispatch record: its `attrs` (`shards`, `m` padded rows a
shard, `want_key`) and the operand bytes it recorded. Beside
`mesh_replay_roofline.py`, which reads it; whatever implements the
kernel, these are moved."""

KEY_BYTES = 4       # the rebuilt key lane is uint32
ROWS_A_BYTE = 8     # winner words carry one bit a padded row


def mesh_replay_bytes(record: dict) -> int:
    """One launch is bound by bytes, and every chip moves its own
    shard's at the same time, so the least time of a launch is one
    shard's bytes over one chip's bandwidth. First-appearance route: the
    shard's share of the operands (flag words, add words, the byte
    planes of the explicit refs: the record's H2D bytes over the
    shards) read once; the `uint32` key lane of `m` rows written once
    and read once by the sort, and written once more where `want_key`
    keeps it resident; the winner words written once. Raw route: the
    key, add and size lanes arrive whole among the operands and are
    read once, and the two masks are written as bytes. A sort is no
    single pass over its keys, so the share this gives reads low: it is
    a lower bound on the bytes, under 100% by construction."""
    attrs = record["attrs"]
    shards, m = attrs["shards"], attrs["m"]
    operands = record["h2d_bytes"] // shards
    if record["kernel"] == "replay.sharded_raw":
        return operands + 2 * m
    key_passes = 2 + (1 if attrs["want_key"] else 0)
    return operands + key_passes * m * KEY_BYTES + m // ROWS_A_BYTE
