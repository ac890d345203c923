"""Entry points: per pass, the time in the SQL executor's `sql.groupby`
and `sql.sort` spans (median over the window's passes of the sum inside
a pass). None on a program without the spans."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "sql.groupby", "sql.sort")
