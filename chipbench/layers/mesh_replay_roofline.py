"""Kernels: the mesh-sharded replay's share of its roofline. The device
time of a launch is what the program's operations cover on its slowest
plane (the host waits for that one); the least time is the bytes one
shard has to move (`mesh_replay_bytes`, from the launch's dispatch
record) over one chip's memory bandwidth. Bound by bytes; the sort is
several passes, so the share reads low. None where no launch is in the
trace, or where the program's records do not carry the shapes."""

from chipbench import roofline
from chipbench.layers.mesh_replay_bytes import mesh_replay_bytes
from chipbench.layers.mesh_shard_skew_pct import covered_by_launch


def read(run):
    launches = covered_by_launch(run)
    if not launches or not all("m" in r.get("attrs", {})
                               for r, _ in launches):
        return None
    least = sum(roofline.least_seconds(mesh_replay_bytes(r),
                                       run.device_kind)
                for r, _ in launches)
    took = sum(max(c) for _, c in launches) / 1e9
    return 100.0 * least / took
