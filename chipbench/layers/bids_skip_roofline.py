"""Kernels: the data-skipping kernel's share of its roofline on an
index wider than its plans. The device time is what the operations of
the program `jit_skipping_mask_block` cover; the least time is the
bytes its launches have to move (`bid_skip_mask_bytes`: the lane rows
each launch's atoms name, from `rows_read` on its `skip.wait` span, by
`n_pad` from its dispatch record) over the chip's memory bandwidth.
Bound by bytes. None where no plan reached the chip, or on a program
whose `skip.wait` does not say which rows it read."""

from chipbench import roofline, spans
from chipbench.layers.bid_skip_mask_bytes import bid_skip_mask_bytes
from chipbench.layers.skip_roofline import PROGRAM


def read(run):
    shapes = [r.get("attrs", {}) for r in run.dispatches
              if r["kernel"] == "skipping.mask_block"]
    rows = [s.get("attrs", {}).get("rows_read")
            for s in spans.named(run.spans, "skip.wait")]
    if (not shapes or not rows or None in rows
            or not all("n_pad" in s for s in shapes)):
        return None
    took = spans.union_ns(
        (start, end) for name, start, end in
        (run.trace.events[0] if run.trace.events else ())
        if name.startswith(PROGRAM)) / 1e9
    if not took:
        return None
    # one `skip.wait` a launch, in the launches' order; where the window's
    # edge parted a pair, every launch is over the index as it stood last
    n_pads = [s["n_pad"] for s in shapes]
    if len(n_pads) != len(rows):
        n_pads = [n_pads[-1]] * len(rows)
    least = sum(roofline.least_seconds(bid_skip_mask_bytes(r, n_pad),
                                       run.device_kind)
                for r, n_pad in zip(rows, n_pads))
    return 100.0 * least / took
