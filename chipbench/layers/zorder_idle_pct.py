"""Device: the share of the window in which no operation ran on the
chip (`device_idle_pct`'s reading, under the Z-order cell's name). The
curve is the one thing an operation asks of the chip, so this reads
near 100: the host's read, gather and write hold the command back."""

from chipbench.layers import device_idle_pct


def read(run):
    return device_idle_pct.read(run)
