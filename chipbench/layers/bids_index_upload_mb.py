"""Device dispatch funnel: host-to-device megabytes of the window's
`stats.index_upload` dispatch records, per refresh: what of the index
crosses to the chip each time a commit lands. None where the window
uploaded no index."""


def read(run):
    uploads = [r for r in run.dispatches
               if r["kernel"] == "stats.index_upload"]
    refreshes = sum(op["kind"] == "refresh" for op in run.ops)
    if not uploads or not refreshes:
        return None
    return sum(r["h2d_bytes"] for r in uploads) / 1e6 / refreshes
