"""Kernels: the checkpoint stats block's share of its roofline. The
device time is what the operations of the program that
`obs.program("stats.ckpt_block")` names cover on the chip; the least
time is the bytes its launches have to move (from the shape on each
dispatch record, `ckpt_stats_block_bytes`) over the chip's memory
bandwidth. Bound by bytes. None where no block reached the chip, or
where the program's records do not carry the shape."""

from chipbench import roofline, spans
from chipbench.layers.ckpt_stats_block_bytes import ckpt_stats_block_bytes
from chipbench.layers.ckpt_write_h2d_mb_per_op import blocks

PROGRAM = "jit_stats_ckpt_block/"


def read(run):
    shapes = [r.get("attrs", {}) for r in blocks(run)]
    if not shapes or not all("n_pad" in s for s in shapes):
        return None
    least = sum(roofline.least_seconds(
        ckpt_stats_block_bytes(s["lanes"], s["n_pad"], s["p_pad"]),
        run.device_kind) for s in shapes)
    # the union: the program's `while` holds the operations of its body
    took = spans.union_ns(
        (start, end) for name, start, end in
        (run.trace.events[0] if run.trace.events else ())
        if name.startswith(PROGRAM)) / 1e9
    return 100.0 * least / took if took else None
