"""The least bytes one shard of the resident append
(`parallel/resident.py`) has to move in one launch, from the launch's
dispatch record: its `attrs` (`shards`, `m` slots a shard, `d_pad` delta
slots a shard) and the operand bytes it recorded. Beside
`resident_append_roofline.py`, which reads it; whatever implements the
kernel, these are moved."""

KEY_BYTES = 4       # the resident key lane is uint32
ROWS_A_BYTE = 8     # winner words carry one bit a slot


def resident_append_bytes(record: dict) -> int:
    """One launch is bound by bytes, and every chip moves its own
    shard's at the same time, so the least time of a launch is one
    shard's bytes over one chip's bandwidth: the shard's share of the
    operands (slot indexes, keys, fill level: the record's H2D bytes
    over the shards) read once; the scatter in place on the donated
    lane, which touches `d_pad` keys; the `uint32` key lane of `m` slots
    read once and written once by the sort; the winner words written
    once. A sort is no single pass over its keys, so the share this
    gives reads low: it is a lower bound on the bytes, under 100% by
    construction."""
    attrs = record["attrs"]
    shards, m, d_pad = attrs["shards"], attrs["m"], attrs["d_pad"]
    operands = record["h2d_bytes"] // shards
    return (operands + d_pad * KEY_BYTES + 2 * m * KEY_BYTES
            + m // ROWS_A_BYTE)
