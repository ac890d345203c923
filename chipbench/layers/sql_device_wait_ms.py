"""Device dispatch funnel: per pass, the time the driving thread is
blocked reading a device result of a SQL operator: the program's
`sql.wait` spans (median over the window's passes of the sum inside a
pass). None where nothing was read from the chip, or on a program
without the span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "sql.wait")
