"""Stats index: over the operations that follow a landed commit, the
median of `stats.index_build` plus `stats.index_upload`
(`index_rebuild_ms`' reading, under the resident cell's name): the
index of 6.0M files brought to the new version and sent whole to
chip 0."""

from chipbench.layers import index_rebuild_ms


def read(run):
    return index_rebuild_ms.read(run)
