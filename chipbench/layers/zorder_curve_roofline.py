"""Kernels: the Z-order curve program's share of its roofline. The
device time is what the operations of the program that
`obs.program("zorder.curve_perm")` names cover on the chip; the least
time is the bytes its launches have to move
(`zorder_curve_bytes.curve_bytes`) over the chip's memory bandwidth.
Bound by bytes. None where no launch reached the chip, or where the
program's records do not carry the shape."""

from chipbench import roofline, spans
from chipbench.layers.zorder_curve_bytes import curve_bytes, launches

PROGRAM = "jit_zorder_curve_perm/"


def share(run, nbytes, mine):
    """100 x the least seconds of the window's launches over what the
    chip's operations that `mine(name)` picks cover."""
    shapes = launches(run)
    # the union: a program's `while` holds the operations of its body
    took = spans.union_ns(
        (start, end) for name, start, end in
        (run.trace.events[0] if run.trace.events else ())
        if mine(name)) / 1e9
    if not shapes or not took:
        return None
    least = sum(roofline.least_seconds(nbytes(s), run.device_kind)
                for s in shapes)
    return 100.0 * least / took


def read(run):
    return share(run, curve_bytes, lambda name: name.startswith(PROGRAM))
