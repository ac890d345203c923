"""Host pipeline: per pass, the time in `join.expand`: a join kernel's
sorted answer made into row pairs on the host (median over the window's
passes of the sum inside a pass). None on a program without the
span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "join.expand")
