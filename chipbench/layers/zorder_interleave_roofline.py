"""Kernels: the Pallas bit-interleave's share of its roofline
(`ops/pallas_kernels.py::interleave_bits_tiled`, inside the curve's
program): the least bytes of the window's launches
(`zorder_curve_bytes.interleave_bytes`) over the chip's memory
bandwidth, against the time of the kernel's own operations, found by
the name the trace prints for the call. None where the curve ran
without the kernel (off the TPU it is the `jnp` body, fused away)."""

from chipbench.layers.zorder_curve_bytes import interleave_bytes
from chipbench.layers.zorder_curve_roofline import PROGRAM, share

KERNEL = "interleave_bits_tiled"


def read(run):
    return share(run, interleave_bytes,
                 lambda name: name.startswith(PROGRAM) and KERNEL in name)
