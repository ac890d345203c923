"""Host pipeline: per pass, what a source does with its batches once
they are read: `scan.assemble` (one table, the partition columns, the
residual filter, the projection) and `sql.frame` (decimals to float64,
Arrow to pandas) (median over the window's passes of the sum inside a
pass). None on a program without the spans."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "scan.assemble", "sql.frame")
