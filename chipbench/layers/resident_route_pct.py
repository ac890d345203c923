"""Routing gate: of the window's `update.advance` spans, the share that
say `route=resident`: the landed commit's rows went to the key lanes
the load left on the chips, and no path held was probed on the host.
100 where every refresh stayed there; the refresh that ends residency
(`route=host`: a batch the lanes cannot take) and every one after it
lower it. None where nothing was advanced, or on a program whose span
does not say which route it took."""

from chipbench import spans


def read(run):
    routes = [s.get("attrs", {}).get("route")
              for s in spans.named(run.spans, "update.advance")]
    if not routes or None in routes:
        return None
    return 100.0 * routes.count("resident") / len(routes)
