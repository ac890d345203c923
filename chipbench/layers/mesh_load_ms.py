"""Entry points: median duration of the program's `snapshot.load` span
on the four-chip host (`snapshot_load_ms`' reading, under the mesh
cell's name): list, read, decode, the sharded replay."""

from chipbench.layers import snapshot_load_ms


def read(run):
    return snapshot_load_ms.read(run)
