"""Device: how unevenly the sharded replay's work falls on the chips.
Over the window's launches, the time the program's own operations cover
on the busiest plane of a launch over the mean of the planes, less one:
0 where every chip works as long as the others (the path key modulo the
shards fills them evenly), 300 where one of four does it all. The
collectives are left out: a `psum` ends on every chip when the slowest
has arrived, so counting it would even out exactly what this reads (it
is `mesh_collective_pct`'s). None where no launch of the program is in
the trace."""

import bisect
import re

from chipbench import spans
from chipbench.layers.mesh_h2d_mb_per_op import sharded

PROGRAMS = ("jit_replay_sharded_fa/", "jit_replay_sharded_raw/")
# the device's clock and the host's lie within a millisecond of each
# other and launches seconds apart: an operation belongs to the last
# launch that began before it, give or take this
SLACK_NS = 50_000_000
# `trace_reduce.Reduced` keeps an operation's instruction name and not
# its scope (`replay.psum`); XLA names the instruction after the
# primitive under the scope (`%psum_invariant.7`) or after the
# collective it becomes
COLLECTIVE = re.compile(
    r"^%?(psum|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)")


def is_collective(name: str) -> bool:
    """`name`: `<program>/<instruction>`, as `Reduced.events` has it."""
    return bool(COLLECTIVE.match(name.split("/", 1)[1]))


def covered_by_launch(run, collectives=True):
    """`(record, [ns covered on plane 0, 1, ...])` of every launch of
    the sharded program whose operations the trace holds; without the
    collectives' time where `collectives` is false."""
    records = sorted(sharded(run),
                     key=lambda r: r["ts_unix_ns"] - r["wall_ns"])
    planes = run.trace.events
    if not records or not planes:
        return []
    begins = [run.to_trace_ns(r["ts_unix_ns"] - r["wall_ns"])
              for r in records]
    found = [[[] for _ in planes] for _ in records]
    for p, events in enumerate(planes):
        for name, start, end in events:
            if name.startswith(PROGRAMS) and (
                    collectives or not is_collective(name)):
                i = bisect.bisect_right(begins, start + SLACK_NS) - 1
                if i >= 0:
                    found[i][p].append((start, end))
    covered = [(r, [spans.union_ns(iv) for iv in launch])
               for r, launch in zip(records, found)]
    return [(r, c) for r, c in covered if any(c)]


def read(run):
    launches = covered_by_launch(run, collectives=False)
    if not launches:
        return None
    busiest = sum(max(c) for _, c in launches)
    mean = sum(sum(c) / len(c) for _, c in launches)
    return 100.0 * (busiest / mean - 1.0)
