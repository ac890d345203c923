"""Host pipeline: of the time the scan pool's tasks took, the share
their threads were on a CPU: over the window's `scan.read_run` spans,
the sum of `cpu_ms` over the sum of the durations, so a long task
weighs as it lasts. The rest a task's thread was not running: on a warm
local store, waiting its turn on the interpreter's lock. Near 100 the
decode is the floor of a scan, near 100 / `threads` the lock is. None
on a program whose tasks open no span."""

from chipbench import spans


def read(run):
    tasks = [s for s in spans.named(run.spans, "scan.read_run")
             if "cpu_ms" in s.get("attrs", {})]
    took_ms = sum(s["duration_ns"] for s in tasks) / 1e6
    if not took_ms:
        return None
    return 100.0 * sum(s["attrs"]["cpu_ms"] for s in tasks) / took_ms
