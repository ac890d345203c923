"""Entry points: over the operations that follow a landed commit, the
median of `snapshot.update` plus the `scan.plan` after it
(`refresh_ms`' reading, under the resident cell's name)."""

from chipbench.layers import refresh_ms


def read(run):
    return refresh_ms.read(run)
