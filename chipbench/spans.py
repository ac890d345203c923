"""Arithmetic on the program's host spans (`delta_tpu.obs` span dicts:
`name`, `span_id`, `parent_id`, `start_unix_ns`, `duration_ns`)."""

from __future__ import annotations

import statistics


def end_ns(span: dict) -> int:
    return span["start_unix_ns"] + span["duration_ns"]


def named(spans, *names):
    return [s for s in spans if s["name"] in names]


def median_ms(spans):
    """Median duration in milliseconds; None of no spans."""
    if not spans:
        return None
    return statistics.median(s["duration_ns"] for s in spans) / 1e6


def merge(intervals) -> list:
    """Sorted, disjoint union of `(start, end)` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def union_ns(intervals) -> int:
    """Length covered by `(start, end)` intervals."""
    return sum(end - start for start, end in merge(intervals))


def self_time_ns(span: dict, spans) -> int:
    """The span's duration less the part of it its child spans cover."""
    lo, hi = span["start_unix_ns"], end_ns(span)
    covered = union_ns(
        (max(lo, c["start_unix_ns"]), min(hi, end_ns(c)))
        for c in spans
        if c["parent_id"] == span["span_id"]
        and c["start_unix_ns"] < hi and end_ns(c) > lo)
    return span["duration_ns"] - covered


def inside(spans, start_ns: int, stop_ns: int):
    """Spans that start in `[start_ns, stop_ns)`."""
    return [s for s in spans if start_ns <= s["start_unix_ns"] < stop_ns]
