"""Kernels: of chip 0's device time inside the sharded replay program,
the share its collectives take: the `psum` of the live-file count (and
of the bytes on the raw route), which the program holds under the
`jax.named_scope` `replay.psum`. A collective ends when the slowest
chip has arrived, so its time on chip 0 is the exchange and the wait
for the others. Found by the instruction's name (`is_collective`): the
reduction keeps no scope. 0 where the program ran and none is found;
None where the program is not in the trace."""

from chipbench.layers.mesh_shard_skew_pct import PROGRAMS, is_collective


def read(run):
    mine = [(name, end - start) for name, start, end in
            (run.trace.events[0] if run.trace.events else ())
            if name.startswith(PROGRAMS)]
    total = sum(took for _, took in mine)
    if not total:
        return None
    return 100.0 * sum(took for name, took in mine
                       if is_collective(name)) / total
