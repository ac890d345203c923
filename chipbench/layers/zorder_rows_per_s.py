"""Entry points: rows clustered a second of OPTIMIZE: the rows the
window's `zorder.curve_perm` launches put in order (`rows` on each
dispatch record; the counter `optimize.rows_clustered` counts the same
rows, but since the process began, and the registry knows no window)
over the time in the window's `command.optimize` spans. The dispatch
funnel's records are the program's counts a launch: `program_counter`,
as `h2d_mb_per_op`'s source is. BASELINE.json's "files/sec" for a
maintenance command is its rows. None on a program whose records carry
no `rows`."""

from chipbench import spans


def read(run):
    rows = [r["attrs"]["rows"] for r in run.dispatches
            if r["kernel"] == "zorder.curve_perm"
            and "rows" in r.get("attrs", {})]
    took = sum(s["duration_ns"]
               for s in spans.named(run.spans, "command.optimize"))
    if not rows or not took:
        return None
    return sum(rows) / (took / 1e9)
