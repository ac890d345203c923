"""Device: the share of the window in which no operation ran on the
chip (`device_idle_pct`'s reading, under the writer cell's name). The
stats block is the one thing an operation asks of the chip, so this
reads near 100: the host holds the writer back."""

from chipbench.layers import device_idle_pct


def read(run):
    return device_idle_pct.read(run)
