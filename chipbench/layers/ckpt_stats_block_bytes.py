"""The least bytes the checkpoint's stats block (`ops/stats.py`,
`stats.ckpt_block`) has to move in one launch, from its shapes. Beside
`ckpt_write_stats_roofline.py`, which reads it; the other kernels'
counts are in `chipbench/roofline.py` and beside their readers."""

LANE_BYTES = 8      # the lanes are int64
ROWS_A_BYTE = 8     # their validity is one bit a row
PART_BYTES = 4      # the part ids are int32
BLOCK_BYTES = 8     # the block is int64


def ckpt_stats_block_bytes(lanes: int, n_pad: int, p_pad: int) -> int:
    """One launch is bound by bytes: it has to read each of the `lanes`
    x `n_pad` values once, their validity words once and the `n_pad`
    part ids once, and to write the block of `4 * lanes + 1` rows of
    `p_pad` parts. Its comparisons and sums are a few integer
    operations a value. Whatever implements the block moves these: a
    sort and sixteen scatters move several times as much, so the share
    this gives reads low, and a later kernel is read by the same
    yardstick."""
    return (lanes * n_pad * LANE_BYTES + lanes * n_pad // ROWS_A_BYTE
            + n_pad * PART_BYTES + (4 * lanes + 1) * p_pad * BLOCK_BYTES)
