"""Routing gate: of the window's `checkpoint.aggregate` spans, the
share that say `stats_mode=device`: the stats block was computed on the
chip. Under 100 where a dispatch failed and the host twin stood in (the
span then names the error, `device_error`), 0 where the engine keeps
the stage on the host."""

from chipbench import spans


def read(run):
    modes = [s.get("attrs", {}).get("stats_mode")
             for s in spans.named(run.spans, "checkpoint.aggregate")]
    if not modes or None in modes:
        return None
    return 100.0 * modes.count("device") / len(modes)
