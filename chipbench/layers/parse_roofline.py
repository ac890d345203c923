"""Kernels: the JSON field extraction's share of its roofline. The
device time is that of the operations the trace shows starting inside a
`json_parse.window` dispatch; the least time is the bytes the kernel
has to move (from its shapes, `roofline.parse_window_bytes`) over the
chip's memory bandwidth. Bound by bytes."""

import ast

from chipbench import roofline


def read(run):
    mine = [r for r in run.dispatches if r["kernel"] == "json_parse.window"]
    if not mine:
        return None
    least = 0.0
    for r in mine:
        n_pad, l_pad, _ = ast.literal_eval(r["key"])
        least += roofline.least_seconds(
            roofline.parse_window_bytes(n_pad, l_pad), run.device_kind)
    took = run.trace.device_seconds(
        (run.to_trace_ns(r["ts_unix_ns"] - r["wall_ns"]),
         run.to_trace_ns(r["ts_unix_ns"])) for r in mine)
    return 100.0 * least / took if took else None
