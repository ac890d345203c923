"""Kernels: the data-skipping kernel's share of its roofline over this
cell's launches, by `skip_roofline.py`'s reckoning (bytes by
`skip_mask_bytes.py`, over the chip's memory bandwidth): no new kernel,
so no new count."""

from chipbench.layers.skip_roofline import read  # noqa: F401
