"""End-to-end arithmetic: from the operations of one window to the
numbers a user of the library would feel."""

from __future__ import annotations

import statistics


def end_to_end(latencies_ms, n_correct: int, window_s: float,
               setup_s: float) -> dict:
    """`latencies_ms` of every operation of the window; `ops_per_s` is
    the correct ones over the whole window, checks and landings
    included. No tail: the highest percentile with ten samples beyond
    it needs 200 operations for the 95th, and no cell has them."""
    return {
        "op_p50_ms": (statistics.median(latencies_ms), "ms"),
        "ops_per_s": (n_correct / window_s, "ops/s"),
        "setup_s": (setup_s, "s"),
    }

