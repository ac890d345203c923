"""Host pipeline: `resident_append_ms` less the time the driving thread
is blocked on the chips (`resident.wait`, the read of the winner words):
the host's own share of an append, a refresh: the path dictionary, the
operands, the masks rebuilt over every slot. None on a program without
the `resident.wait` span: there the blocking read would be counted as
host work."""

import statistics

from chipbench import spans


def per_refresh(run, name):
    """Per `refresh` operation, the milliseconds in the spans called
    `name` that started inside it (0 where there is none)."""
    return [sum(s["duration_ns"] for s in spans.named(
        spans.inside(run.spans, op["start_unix_ns"], op["end_unix_ns"]),
        name)) / 1e6 for op in run.ops if op["kind"] == "refresh"]


def read(run):
    if not spans.named(run.spans, "resident.wait"):
        return None
    host = [whole - wait for whole, wait in zip(
        per_refresh(run, "advance.resident_append"),
        per_refresh(run, "resident.wait")) if whole]
    return statistics.median(host) if host else None
