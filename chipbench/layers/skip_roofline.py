"""Kernels: the data-skipping kernel's share of its roofline. The
device time is what the operations of the program that
`obs.program("skipping.mask_block")` names cover; the least time is the bytes
its launches have to move (from the lanes' shape on each dispatch
record, `skip_mask_bytes`) over the chip's memory bandwidth. Bound by
bytes. None where no plan reached the chip, or where the program's
records do not carry the lanes' shape."""

from chipbench import roofline, spans
from chipbench.layers.skip_mask_bytes import skip_mask_bytes

PROGRAM = "jit_skipping_mask_block/"


def read(run):
    shapes = [r.get("attrs", {}) for r in run.dispatches
              if r["kernel"] == "skipping.mask_block"]
    if not shapes or not all("n_pad" in s for s in shapes):
        return None
    least = sum(roofline.least_seconds(
        skip_mask_bytes(s["lanes"], s["n_pad"]), run.device_kind)
        for s in shapes)
    # the union: the program's `while` holds the operations of its body
    took = spans.union_ns(
        (start, end) for name, start, end in
        (run.trace.events[0] if run.trace.events else ())
        if name.startswith(PROGRAM)) / 1e9
    return 100.0 * least / took if took else None
