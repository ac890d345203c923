"""Device dispatch funnel: host-to-device bytes of the window's
`zorder.curve_perm` dispatch records, per operation: the uint32 key
matrix, columns x padded rows. None where none was dispatched."""


def read(run):
    mine = [r for r in run.dispatches if r["kernel"] == "zorder.curve_perm"]
    if not mine:
        return None
    return sum(r["h2d_bytes"] for r in mine) / 1e6 / len(run.ops)
