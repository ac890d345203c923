"""Kernels: the data-skipping kernel's share of its roofline on the
70-lane index. The device time is what the operations of the program
`jit_skipping_mask_block` cover; the least time is the bytes its
launches have to move (`sales_skip_mask_bytes`: the lane rows each
launch's atoms name, from `rows_read` on its `skip.wait` span, by
`n_pad` from its dispatch record) over the chip's memory bandwidth.
Bound by bytes. None where no plan reached the chip, or on a program
whose `skip.wait` does not say which rows it read.
`bids_skip_roofline`'s reading, over this cell's launches."""

from chipbench.layers.bids_skip_roofline import read  # noqa: F401
