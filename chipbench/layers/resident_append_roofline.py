"""Kernels: the resident append's share of its roofline. The device
time of a launch is what the operations of the program that
`obs.program("replay.resident_append")` names cover on its slowest plane
(the host waits for that one); the least time is the bytes one shard has
to move (`resident_append_bytes`, from the launch's dispatch record)
over one chip's memory bandwidth. Bound by bytes; the sort is several
passes over the lane, so the share reads low. None where no launch is
in the trace, or where the program's records do not carry the shapes."""

import bisect

from chipbench import roofline, spans
from chipbench.layers.resident_append_bytes import resident_append_bytes
from chipbench.layers.resident_h2d_kb_per_op import appends

PROGRAM = "jit_replay_resident_append/"
# the device's clock and the host's lie within a millisecond of each
# other and launches a second apart: an operation belongs to the last
# launch that began before it, give or take this
SLACK_NS = 50_000_000


def covered_by_launch(run):
    """`(record, [ns covered on plane 0, 1, ...])` of every launch of
    the append program whose operations the trace holds."""
    records = sorted(appends(run),
                     key=lambda r: r["ts_unix_ns"] - r["wall_ns"])
    planes = run.trace.events
    if not records or not planes:
        return []
    begins = [run.to_trace_ns(r["ts_unix_ns"] - r["wall_ns"])
              for r in records]
    found = [[[] for _ in planes] for _ in records]
    for p, events in enumerate(planes):
        for name, start, end in events:
            if name.startswith(PROGRAM):
                i = bisect.bisect_right(begins, start + SLACK_NS) - 1
                if i >= 0:
                    found[i][p].append((start, end))
    covered = [(r, [spans.union_ns(iv) for iv in launch])
               for r, launch in zip(records, found)]
    return [(r, c) for r, c in covered if any(c)]


def read(run):
    launches = covered_by_launch(run)
    if not launches or not all("m" in r.get("attrs", {})
                               for r, _ in launches):
        return None
    least = sum(roofline.least_seconds(resident_append_bytes(r),
                                       run.device_kind)
                for r, _ in launches)
    took = sum(max(c) for _, c in launches) / 1e9
    return 100.0 * least / took
