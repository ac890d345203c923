"""Stats index: over the operations that follow a landed commit, the
median of the time spent bringing the resident stats index to the new
version: the program's `stats.index_build` (the survivors of 13 lanes
and of a parsed table with three string columns compacted, the landed
files' stats parsed under the typed schema) plus `stats.index_upload`
(every lane sent to the chip)."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "refresh", "stats.index_build",
                              "stats.index_upload")
