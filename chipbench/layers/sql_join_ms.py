"""Entry points: per pass, the time in the SQL executor's `sql.join`
spans, on whichever route the gate sent each (median over the window's
passes of the sum inside a pass). None on a program without the span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "sql.join")
