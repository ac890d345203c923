"""Host pipeline: per pass, the time in the `scan.read` spans, a scan's
data files read as one batch: on the driving thread the wait for the
scan pool's tasks where the files were dealt out, the files themselves
where the batch was read inline (median over the window's passes of the
sum inside a pass). None on a program without the span."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "pass", "scan.read")
