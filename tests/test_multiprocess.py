"""Two-process distributed replay over a global mesh (jax.distributed).

The multi-host story from the module docstring of
`parallel/sharded_replay.py`, actually executed: two OS processes, each
with 4 virtual CPU devices, form one 8-device global mesh via
`jax.distributed.initialize`. Each process routes ONLY the rows it
"parsed" (keys are pre-partitioned by `key % 2 == process_id`, the way a
multi-host columnarizer would split commit files), provides its local
[4, M] shard blocks with `jax.make_array_from_process_local_data`, and
runs the same shard_map replay kernel. The `psum` aggregate crosses the
process boundary (Gloo collectives on CPU; ICI/DCN on real TPU pods) and
must equal the global sequential reference on BOTH processes; each
process additionally verifies the winner masks of its own rows.

The subprocesses drop the parent's platform env so the CPU platform
initializes fresh — mirroring how a real multi-host job launches one
process per host before any jax import.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tier-2: real jax.distributed two-process jobs (Gloo rendezvous + full
# XLA re-init per process) take minutes on constrained hosts; the tier-1
# sharded coverage lives in test_sharded_replay.py on the in-process
# 8-emulated-device mesh (the `sharded8` lane)
pytestmark = pytest.mark.slow

WORKER = r"""
import os, sys
pid = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid)
import numpy as np
sys.path.insert(0, {repo!r})
from jax.sharding import NamedSharding, PartitionSpec as P
from delta_tpu.ops.replay import python_replay_reference
from delta_tpu.parallel.mesh import REPLAY_AXIS, make_mesh
from delta_tpu.parallel.sharded_replay import build_sharded_replay_fn

assert len(jax.devices()) == 8, len(jax.devices())
assert len(jax.local_devices()) == 4

# deterministic GLOBAL history, identical in both processes
rng = np.random.default_rng(0)
n = 20_000
key = rng.integers(0, 3000, n).astype(np.uint32)
ver = np.sort(rng.integers(0, 64, n)).astype(np.int32)
add = rng.random(n) < 0.6
size = rng.integers(100, 1000, n).astype(np.int64)

# this process's rows (the files its host "parsed"); shard assignment is
# process = key % 2, local shard = (key // 2) % 4 — injective per key, so
# per-shard dedup is globally correct with no cross-device key exchange
mine = key % 2 == pid
lk, la, ls = key[mine], add[mine], size[mine]
n_local = int(mine.sum())
local_shard = ((lk // 2) % 4).astype(np.int64)
sort_idx = np.argsort(local_shard, kind="stable")
counts = np.bincount(local_shard, minlength=4)
M = 4096
assert counts.max() <= M
k = np.full((4, M), 0xFFFFFFFF, np.uint32)
a = np.zeros((4, M), np.bool_)
s2 = np.zeros((4, M), np.float32)
scatter = np.full((4, M), -1, np.int64)
starts = np.zeros(5, np.int64)
np.cumsum(counts, out=starts[1:])
rows = local_shard[sort_idx]
cols = np.arange(n_local) - starts[rows]
k[rows, cols] = lk[sort_idx]
a[rows, cols] = la[sort_idx]
s2[rows, cols] = ls[sort_idx]
scatter[rows, cols] = sort_idx

mesh = make_mesh()  # global: 8 devices across both processes
spec = NamedSharding(mesh, P(REPLAY_AXIS, None))
gk = jax.make_array_from_process_local_data(spec, k)
ga = jax.make_array_from_process_local_data(spec, a)
gs = jax.make_array_from_process_local_data(spec, s2)
fn = build_sharded_replay_fn(mesh)
live, tomb, num_live, live_bytes = fn(gk, ga, gs)

# global reference (identical in both processes)
live_h, tomb_h = python_replay_reference(
    [(int(x), 0) for x in key], ver, np.zeros(n, np.int32), add)
# the psum crossed the process boundary: both processes see the GLOBAL count
assert int(num_live) == int(live_h.sum()), (int(num_live), int(live_h.sum()))

# my rows' masks from my addressable shards
shards = sorted(live.addressable_shards, key=lambda s: s.index[0].start)
live_local = np.concatenate([np.asarray(s.data) for s in shards])
my_live = np.zeros(n_local, bool)
sel = scatter.ravel() >= 0
my_live[scatter.ravel()[sel]] = live_local.ravel()[sel]
expected = live_h[mine]
assert np.array_equal(my_live, expected), "local winner masks disagree"
print(f"MP_OK pid={pid} num_live={int(num_live)} rows={n_local}", flush=True)
"""


def test_two_process_distributed_replay(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    # the workers set their own platform env before importing jax
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)

    script = tmp_path / "worker.py"
    script.write_text(WORKER.replace("{repo!r}", repr(REPO)))
    procs = [
        subprocess.Popen([sys.executable, str(script), str(pid), str(port)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"MP_OK pid={pid}" in out, out[-3000:]


WORKER_BLOCKWISE = r"""
import os, sys, time
pid = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid)
import numpy as np
sys.path.insert(0, {repo!r})
from jax.sharding import NamedSharding, PartitionSpec as P
from delta_tpu.ops.replay import _unpack_bits, pad_bucket
from delta_tpu.parallel.mesh import REPLAY_AXIS, make_mesh
from delta_tpu.parallel.sharded_blockwise import _PAD_KEY, _step_fn

t0 = time.time()
assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

# deterministic GLOBAL history, identical in both processes: >=2M rows
# per process (VERDICT r4 ask #6 — the DCN-analogue path at scale)
rng = np.random.default_rng(7)
n = 4_000_000
K = 1_000_000
key = rng.integers(0, K, n).astype(np.uint32)
# rows are already in chronological (array) order; the winner per key
# is last-wins over that order

# routing: process = key % 2, local shard = (key // 2) % 4 (injective
# per key -> per-shard dedup is globally correct)
mine = key % 2 == pid
lk = key[mine]
n_local = int(mine.sum())
assert n_local >= 1_900_000, n_local
local_shard = ((lk // 2) % 4).astype(np.int64)

# GLOBAL block geometry (both processes must agree): max rows on any
# of the 8 global shards
g_shard = (key % 2) * 4 + ((key // 2) % 4)
g_counts = np.bincount(g_shard, minlength=8)
m = 1 << 17
n_blocks = -(-int(g_counts.max()) // m)
assert n_blocks > 1, n_blocks  # every shard streams multiple blocks
L = n_blocks * m

# local slab [4, L] in chronological order per shard
sort_idx = np.argsort(local_shard, kind="stable")
counts = np.bincount(local_shard, minlength=4)
starts = np.zeros(5, np.int64)
np.cumsum(counts, out=starts[1:])
rows = local_shard[sort_idx]
cols = np.arange(n_local) - starts[rows]
local_key = (lk // 8).astype(np.uint32)  # dense per shard, < K/8
keys_slab = np.full((4, L), _PAD_KEY, np.uint32)
keys_slab[rows, cols] = local_key[sort_idx]
scatter = np.full((4, L), -1, np.int64)
scatter[rows, cols] = sort_idx

mesh = make_mesh()  # 8 devices across both processes
spec = NamedSharding(mesh, P(REPLAY_AXIS, None))
vec_spec = NamedSharding(mesh, P(REPLAY_AXIS))
n_words = pad_bucket(-(-(K // 8 + 1) // 32), min_bucket=256)
seen = jax.make_array_from_process_local_data(
    spec, np.zeros((4, n_words), np.uint32))
step = _step_fn(mesh, m)

winner = np.zeros(n_local, bool)
for b in reversed(range(n_blocks)):
    blk = np.ascontiguousarray(keys_slab[:, b * m:(b + 1) * m])
    n_real = np.clip(counts - b * m, 0, m).astype(np.int32)
    gblk = jax.make_array_from_process_local_data(spec, blk)
    greal = jax.make_array_from_process_local_data(vec_spec, n_real)
    seen, packed = step(seen, gblk, greal)
    shards = sorted(packed.addressable_shards,
                    key=lambda s: s.index[0].start)
    words = np.stack([np.asarray(s.data).reshape(-1) for s in shards])
    tgt = scatter[:, b * m:(b + 1) * m]
    for s in range(4):
        w = _unpack_bits(words[s], m)
        sel = tgt[s] >= 0
        winner[tgt[s][sel]] = w[sel]

# vectorized global oracle (lexsort last-wins), then my rows
shift = np.uint64(max(1, int(n - 1).bit_length()))
k64 = (key.astype(np.uint64) << shift) | np.arange(n, dtype=np.uint64)
srt = np.sort(k64)
kk = srt >> shift
boundary = np.empty(n, bool)
boundary[:-1] = kk[:-1] != kk[1:]
boundary[-1] = True
idx = (srt & np.uint64((1 << int(shift)) - 1))[boundary].astype(np.int64)
winner_h = np.zeros(n, bool)
winner_h[idx] = True
expected = winner_h[mine]
assert np.array_equal(winner, expected), "blockwise winner masks disagree"
blocks_per_shard = np.maximum(-(-counts // m), 0)
assert (blocks_per_shard > 1).all(), blocks_per_shard
print(f"MPBW_OK pid={{pid}} rows={{n_local}} blocks={{blocks_per_shard.tolist()}} "
      f"wall={{time.time() - t0:.1f}}s", flush=True)
"""


def test_two_process_blockwise_replay_4m(tmp_path):
    """Sharded x blockwise at scale across a REAL process boundary:
    >=2M rows per process on one 8-device global mesh, every shard
    streaming >1 bounded block with a persistent device bitset, winner
    masks parity vs the global vectorized oracle (VERDICT r4 ask #6)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)

    script = tmp_path / "worker_bw.py"
    script.write_text(
        WORKER_BLOCKWISE.replace("{repo!r}", repr(REPO))
        .replace("{{", "\x00").replace("}}", "\x01")
        .replace("\x00", "{").replace("\x01", "}"))
    procs = [
        subprocess.Popen([sys.executable, str(script), str(pid), str(port)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"MPBW_OK pid={pid}" in out, out[-3000:]
