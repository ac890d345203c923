"""Stats index: over the `crossing` operations, the median of the
program's `stats.index_build` plus `stats.index_upload`: the resident
index made from every live file's stats string, since nothing of the
one before crosses the checkpoint."""

from chipbench import op_spans


def read(run):
    return op_spans.median_ms(run, "crossing", "stats.index_build",
                              "stats.index_upload")
