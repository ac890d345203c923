"""Entry points: median of the program's `scan.plan` over the
operations that only plan: the sibling cell's `scan_plan_ms`, by its
reader, here on a state that a crossing rebuilds every 200 operations."""

from chipbench.layers.scan_plan_ms import read  # noqa: F401
