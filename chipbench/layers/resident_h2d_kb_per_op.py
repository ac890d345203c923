"""Device dispatch funnel: host-to-device bytes of the window's
`replay.resident_append` dispatch records, per refresh: what a landed
commit weighs on the link on the resident route (slot index and key of
each delta row, padded to a power of two a shard, and the shards' fill
levels). None where no append was launched."""

KERNEL = "replay.resident_append"


def appends(run):
    return [r for r in run.dispatches if r["kernel"] == KERNEL]


def read(run):
    mine = appends(run)
    refreshes = sum(op["kind"] == "refresh" for op in run.ops)
    if not mine or not refreshes:
        return None
    return sum(r["h2d_bytes"] for r in mine) / 1e3 / refreshes
