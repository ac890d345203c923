"""Host pipeline: per pass, the time in `join.gather`: the joined frame
put together from the matched row pairs, a `take` of every column of
both sides and their concatenation (median over the window's passes of
the sum inside a pass). None on a program without the span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "join.gather")
