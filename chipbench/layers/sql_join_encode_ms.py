"""Host pipeline: per pass, the host's work on a device-route join
before its launch: `join.nulls` (the null check over both key columns
and the exclusion) and `join.encode` (the probe side to the resident
lane's domain, or both sides to joint codes) (median over the window's
passes of the sum inside a pass). None on a program without the
spans."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "join.nulls", "join.encode")
