"""Device: the share of the window in which no operation ran on the
chip (`device_idle_pct`'s reading, under the SQL cell's name)."""

from chipbench.layers import device_idle_pct


def read(run):
    return device_idle_pct.read(run)
