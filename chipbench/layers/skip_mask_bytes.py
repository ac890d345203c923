"""The least bytes the data-skipping kernel (`ops/skipping.py`) has to
move in one launch, from its shapes. Beside `skip_roofline.py`, which
reads it; the other kernels' counts are in `chipbench/roofline.py`."""

LANE_BYTES = 8      # the resident index's lanes are int64
VALID_BYTES = 1     # their validity plane is bool
MASK_BYTES = 1      # the keep mask is bool


def skip_mask_bytes(lanes: int, n_pad: int) -> int:
    """One launch is bound by bytes: it reads each of the index's
    `lanes` x `n_pad` values and validity flags once (a range predicate
    on one column needs all four lanes of this cell's index: the min,
    the max, and nullCount against numRecords) and writes one flag a
    padded file. Its comparisons are a few integer operations a value,
    far under what the chip computes in the time it moves that value;
    the atoms' few dozen bytes are left out."""
    return lanes * n_pad * (LANE_BYTES + VALID_BYTES) + n_pad * MASK_BYTES
