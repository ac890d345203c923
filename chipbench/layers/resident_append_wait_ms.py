"""Device dispatch funnel: over the operations that follow a landed
commit, the median of the program's `resident.wait` span: the time the
driving thread is blocked on the four chips' winner words after the
append kernel's launch (the scatter into the donated lanes and the
per-shard sort). None on a program without that span, or where no
refresh took the resident route."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "resident.wait")
