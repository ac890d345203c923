"""Stats index: over the operations that follow a landed commit, the
median of the time spent bringing the resident stats index to the new
version: the program's `stats.index_build` (the survivors of 70 lanes
compacted, the landed files' stats parsed under the typed schema, the
twelve money columns from their digits) plus `stats.index_upload`
(every lane sent to the chip)."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "stats.index_build",
                              "stats.index_upload")
