"""Entry points: over the operations that follow a landed commit, the
median of the program's `state.filter_live`: one `Table.filter` over
every column of the live files, their stats strings (~0.5 KB a file
here, a real row's width) among them."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "state.filter_live")
