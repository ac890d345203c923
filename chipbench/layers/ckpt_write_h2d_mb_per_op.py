"""Device dispatch funnel: host-to-device bytes of the window's
`stats.ckpt_block` dispatch records, per operation: the int64 lanes,
their validity words and the part ids of one checkpoint's stats block.
None where none was dispatched."""

KERNEL = "stats.ckpt_block"


def blocks(run):
    return [r for r in run.dispatches if r["kernel"] == KERNEL]


def read(run):
    mine = blocks(run)
    if not mine:
        return None
    return sum(r["h2d_bytes"] for r in mine) / 1e6 / len(run.ops)
