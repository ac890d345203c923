"""The least bytes the data-skipping kernel (`ops/skipping.py`) has to
move in one launch over an index wider than the plan: a launch reads
only the lane rows its atoms name. Beside `bids_skip_roofline.py`,
which reads it. `skip_mask_bytes.py` charges every lane of the index,
which is the least only where a plan names them all (the one-column
index of the sibling cells); over 13 lanes it would charge 13 to a
launch that reads 4 or 7, and the share could pass 100%."""

from chipbench.layers.skip_mask_bytes import (LANE_BYTES, MASK_BYTES,
                                              VALID_BYTES)


def bid_skip_mask_bytes(rows_read: int, n_pad: int) -> int:
    """One launch is bound by bytes: it reads each of the `rows_read`
    distinct lane rows its atoms name (min, max and nullCount of every
    column compared, and numRecords), `n_pad` values and validity flags
    each, once, and writes one flag a padded file. Atoms on one column
    share its rows; the comparisons are a few integer operations a
    value."""
    return rows_read * n_pad * (LANE_BYTES + VALID_BYTES) + n_pad * MASK_BYTES
