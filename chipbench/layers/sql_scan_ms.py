"""Entry points: per pass, the time in the SQL executor's `sql.scan`
spans, a source's plan, its data files read and its frame made (median
over the window's passes of the sum inside a pass). None on a program
without the span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "sql.scan")
