"""Device: the share of the window in which no operation ran on a
chip, busy time averaged over the four planes of the trace
(`device_idle_pct`'s reading, under the resident cell's name)."""

from chipbench.layers import device_idle_pct


def read(run):
    return device_idle_pct.read(run)
