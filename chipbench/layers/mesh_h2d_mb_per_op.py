"""Device dispatch funnel: host-to-device bytes of the window's
`replay.sharded_*` dispatch records, per operation: what the sharded
replay's operands weigh on the link (the module's "1-2 bits a row",
read). None where no sharded replay was launched."""

KERNELS = ("replay.sharded_fa", "replay.sharded_raw")


def sharded(run):
    return [r for r in run.dispatches if r["kernel"] in KERNELS]


def read(run):
    mine = sharded(run)
    if not mine:
        return None
    return sum(r["h2d_bytes"] for r in mine) / 1e6 / len(run.ops)
