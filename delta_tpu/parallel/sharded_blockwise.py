"""Sharded × blockwise replay: the multi-host >HBM configuration.

The reference's production shape for 10M+-file tables is BOTH
distributed and bounded-memory at once: state reconstruction shuffles
by path hash across executors AND each partition streams through a
sequential reconciler without materializing the whole partition
(`Snapshot.scala:481-511` — `repartition(hash(path))` then
`mapPartitions { InMemoryLogReplay }` over an iterator).

This module composes the repo's two halves the same way:

- `parallel/sharded_replay.py`'s host shuffle: rows bin to shard
  `key % S`, so per-shard reconciliation is globally correct with no
  cross-device key exchange;
- `ops/replay_blockwise.py`'s reverse-chronological streaming: each
  shard walks its substream newest→oldest in fixed-size blocks with a
  persistent *seen* bitset (first occurrence wins — the
  kernel-descending formulation of `ActiveAddFilesIterator.java:146`),
  reusing the exact single-device block kernel under `shard_map`.

All S shards advance one block per step — operands are [S, m] slabs,
the seen bitsets an [S, W] donated array XLA updates in place. Device
residency per step is one block per shard plus the bitsets,
independent of total rows. Shard skew (a hot path-hash shard) costs
padded lanes on the cold shards, never correctness: each shard's
bitset only ever sees its own key space.

Local key space: shard s holds exactly the keys ≡ s (mod S), so
`key // S` is a dense code over the shard's keys and the bitset is
`ceil(n_uniq / S / 32)` u32 words per shard — 10M files over 8 shards
≈ 4.9KB per shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from delta_tpu import obs
from delta_tpu.ops.replay import (
    _PAD_KEY,
    _unpack_bits,
    chrono_ok,
    combine_key_lanes,
    pad_bucket,
)
from delta_tpu.ops.replay_blockwise import _block_kernel_impl
from delta_tpu.parallel.sharded_replay import REPLAY_AXIS

DEFAULT_BLOCK_ROWS = 1 << 20  # 1M rows/shard/block


def _shard_block_step(seen, keys, n_real, m: int):
    """[1, ...]-sliced wrapper running the single-device block kernel
    on this shard's slab."""
    winner_words, seen_out = _block_kernel_impl(
        seen[0], keys[0], n_real[0], m)
    return seen_out[None], winner_words[None]


@functools.lru_cache(maxsize=8)
def _step_fn(mesh: Mesh, m: int):
    spec = P(REPLAY_AXIS, None)
    fn = shard_map(
        functools.partial(_shard_block_step, m=m),
        mesh=mesh,
        in_specs=(spec, spec, P(REPLAY_AXIS)),
        out_specs=(spec, spec),
    )
    return jax.jit(obs.program("replay.sharded_blockwise")(fn),
                   donate_argnums=(0,))


def replay_select_sharded_blockwise(
    key_lanes,
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    mesh: Mesh,
    block_rows: int = DEFAULT_BLOCK_ROWS,
):
    """Mesh-sharded, bounded-memory replay. Returns
    (live_mask, tombstone_mask, per_shard_block_counts); the masks are
    identical to `replay_select` / `replay_select_blockwise` on the
    same stream (original row order)."""
    version = np.asarray(version)
    n = int(version.shape[0])
    S = int(mesh.devices.size)
    if n == 0:
        z = np.zeros((0,), dtype=bool)
        return z, z, np.zeros(S, np.int64)

    is_add_orig = np.asarray(is_add, bool)
    perm = None
    if not chrono_ok(version, np.asarray(order)):
        perm = np.lexsort((order, version))
        key_lanes = [np.asarray(k)[perm] for k in key_lanes]

    # shard by the PATH lane (lane 0), exactly like
    # parallel/sharded_replay: all DV variants of a path land on one
    # shard, and — crucially — a sparse secondary lane (dv mostly 0)
    # can't bias the shard distribution the way `combined % S` would
    lanes = [np.asarray(k) for k in key_lanes]
    pk = lanes[0]
    shard_of = (pk % np.uint32(S)).astype(np.int64)
    local_key = combine_key_lanes(
        [(pk // np.uint32(S)).astype(np.uint32)] + lanes[1:])
    if local_key is None:
        # radix overflow: densify over ALL lanes (shard-local codes
        # stay dense because every (path, dv, ...) tuple maps to a
        # unique structured row)
        cols_ = [(pk // np.uint32(S)).astype(np.uint32)]
        cols_ += [l.astype(np.uint32) for l in lanes[1:]]
        stacked = np.ascontiguousarray(np.stack(cols_, axis=1))
        view = stacked.view(
            [("", np.uint32)] * stacked.shape[1]).reshape(-1)
        _, local_key = np.unique(view, return_inverse=True)
        local_key = local_key.astype(np.uint32)
    n_uniq_local = int(local_key.max()) + 1

    # stable per-shard chronological substreams (the "shuffle")
    sort_idx = np.argsort(shard_of, kind="stable")
    counts = np.bincount(shard_of, minlength=S)
    max_count = int(counts.max())
    m = pad_bucket(min(block_rows, max(max_count, 1)))
    n_blocks = -(-max_count // m)
    L = n_blocks * m

    rows = shard_of[sort_idx]
    cols = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
    keys_slab = np.full((S, L), _PAD_KEY, dtype=np.uint32)
    keys_slab[rows, cols] = local_key[sort_idx]
    # slab position -> ORIGINAL row id (pre-perm)
    scatter = np.full((S, L), -1, dtype=np.int64)
    scatter[rows, cols] = sort_idx if perm is None else perm[sort_idx]

    n_words = pad_bucket(-(-max(n_uniq_local, 1) // 32),
                         min_bucket=256)
    # one-time seed upload of the per-shard bitsets (donated and updated
    # in place by every block step after)
    with obs.device_dispatch("replay.sharded_seed",
                             key=(S, n_words)) as dd:
        seen = dd.h2d("seen", jax.device_put(
            jnp.zeros((S, n_words), jnp.uint32),
            NamedSharding(mesh, P(REPLAY_AXIS, None))))
    step = _step_fn(mesh, m)

    winner = np.zeros(n, dtype=bool)  # original row space
    for b in reversed(range(n_blocks)):
        blk = keys_slab[:, b * m:(b + 1) * m]
        n_real = np.clip(counts - b * m, 0, m).astype(np.int32)
        # block operands ride as jit arguments (no device_put lane); the
        # per-block costs accumulate onto the same pending replay
        # decision, so calibration prices the whole block loop
        with obs.device_dispatch("replay.sharded_blockwise",
                                 key=(S, m, n_words), gate="replay",
                                 route="sharded") as dd:
            dd.h2d("block", int(blk.nbytes) + int(n_real.nbytes))
            seen, packed = step(seen, jnp.asarray(blk),
                                jnp.asarray(n_real))
            words = dd.d2h("packed", np.asarray(packed))
        tgt = scatter[:, b * m:(b + 1) * m]
        for s in range(S):
            w = _unpack_bits(words[s], m)
            sel = tgt[s] >= 0
            winner[tgt[s][sel]] = w[sel]

    live = winner & is_add_orig
    tomb = winner & ~is_add_orig
    blocks_used = np.maximum(-(-counts // m), 0).astype(np.int64)
    return live, tomb, blocks_used
