"""Device dispatch funnel: host-to-device bytes of the window's
dispatch records, per operation (0 where nothing was dispatched)."""


def read(run):
    return sum(r["h2d_bytes"] for r in run.dispatches) / 1e6 / len(run.ops)
