"""The least bytes the data-skipping kernel (`ops/skipping.py`) has to
move in one launch over the 70-lane index of a fact table: a launch
reads only the lane rows its atoms name (4 of 70 for a window alone:
the sold date's min, max and nullCount, and numRecords; 16 for a window
with a bucket: five columns and numRecords), `n_pad` values and validity
flags each, once, and writes one flag a padded file; atoms on one
column share its rows, however many OR-groups the distribution put them
in. The count is `bid_skip_mask_bytes.py`'s (`rows_read` x `n_pad` x 9 +
`n_pad`), under this cell's name for `sales_skip_roofline.py`."""

from chipbench.layers.bid_skip_mask_bytes import (  # noqa: F401
    bid_skip_mask_bytes as sales_skip_mask_bytes)
