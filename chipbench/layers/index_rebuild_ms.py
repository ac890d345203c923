"""Stats index: over the operations that follow a landed commit, the
median of the time spent making the resident stats index anew: the
program's `stats.index_build` (every live file's stats parsed into
lanes) plus `stats.index_upload` (the lanes sent to the chip)."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "stats.index_build",
                              "stats.index_upload")
