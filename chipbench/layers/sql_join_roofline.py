"""Kernels: the value-lane join's share of its roofline. The device time
is what the operations of the program `jit_sqlops_join_lanes` cover in
the window; the least time is the bytes its launches have to move
(`sql_join_bytes.join_lanes_bytes`, from the padded shapes on each
launch's dispatch record) over the chip's memory bandwidth. Bound by
bytes; the sort is several passes, so the share reads low. None where no
such join reached the chip, or on a program whose records carry no
shapes."""

from chipbench import roofline, spans
from chipbench.layers.sql_join_bytes import join_lanes_bytes


def share(run, kernel, needs, least_bytes):
    shapes = [r.get("attrs", {}) for r in run.dispatches
              if r["kernel"] == kernel]
    if not shapes or not all(k in s for s in shapes for k in needs):
        return None
    program = "jit_" + kernel.replace(".", "_") + "/"
    took = spans.union_ns(
        (start, end) for name, start, end in
        (run.trace.events[0] if run.trace.events else ())
        if name.startswith(program)) / 1e9
    if not took:
        return None
    least = sum(roofline.least_seconds(least_bytes(s), run.device_kind)
                for s in shapes)
    return 100.0 * least / took


def read(run):
    return share(run, "sqlops.join_lanes", ("nl_pad", "nr_pad"),
                 join_lanes_bytes)
