"""Device kernels for the checkpoint WRITE path: per-column min/max/
null-count/sum segment aggregation, partition-code distinct counts, and
deletion-vector bitmap-container packing.

The checkpoint writer's aggregation stage summarizes the snapshot's
live-file columnar state per checkpoint part (rows, logical bytes,
modification-time bounds, null counts, distinct partition values) for
the part manifest and the `checkpoint.write` span tree. On an
accelerator the whole stage is ONE batched dispatch over the numeric
lanes — the state is already columnar, and the per-part segment
reductions are exactly the shape the replay kernels use — with the
results shipped back as one dense D2H block. Both stat modes (host
numpy / device) produce bit-identical aggregates: every lane is int64
and every reduction (min/max/sum/count) is order-independent over
integers, so checkpoints are byte-identical regardless of where the
aggregation ran (asserted by the write->read parity matrix in
tests/test_checkpoint_write.py).

H2D lanes are pinned by `resources/transfer_budget.json`
(`ckpt-stats-block`, `ckpt-dv-pack`): lane matrix int64, validity as a
packed bitplane, part ids int32; DV packing ships one int64 flat bit
index per set bit.

Env:
  DELTA_TPU_DEVICE_CKPT_STATS=1|0  force the aggregation stage on/off
                                   (unset: the engine flag decides —
                                   TpuEngine autodetects a non-CPU
                                   backend, HostEngine stays host)
  DELTA_TPU_DEVICE_DV_PACK=1      route multi-container roaring bitmap
                                   packing through the device kernel
  DELTA_TPU_DEVICE_DV_DECODE=1    route DV blob -> row-mask expansion
                                   through the decode kernel (the
                                   pack kernel's inverse)
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Sequence

import numpy as np

from delta_tpu import obs

# identity elements for empty segments — shared by both modes so the
# host fallback is bit-identical to jax.ops.segment_min/max
IDENT_MIN = np.iinfo(np.int64).max
IDENT_MAX = np.iinfo(np.int64).min

_BITMAP_WORDS = 2048  # 8192-byte roaring bitmap container, as uint32

def _x64():
    """Scoped 64-bit context for the dispatch: exact (order-independent)
    int64 device math without flipping the process-global
    `jax_enable_x64`, which would silently change default dtypes for
    every other kernel sharing the process."""
    import jax

    return jax.enable_x64(True)


def device_stats_enabled(engine=None) -> bool:
    """Should the checkpoint aggregation stage run on device? Env
    override first (tests force either mode on any engine), then the
    engine's construction-time flag."""
    env = os.environ.get("DELTA_TPU_DEVICE_CKPT_STATS")
    if env is not None:
        return env not in ("0", "off", "false", "no")
    return bool(getattr(engine, "use_device_ckpt_stats", False))


def device_dv_pack_enabled() -> bool:
    return os.environ.get("DELTA_TPU_DEVICE_DV_PACK") == "1"


def device_dv_decode_enabled() -> bool:
    return os.environ.get("DELTA_TPU_DEVICE_DV_DECODE") == "1"


def accel_backend_default() -> bool:
    """Construction-time autodetect for TpuEngine: aggregate on device
    when a real accelerator backend is present."""
    import jax

    return jax.default_backend() != "cpu"


# ---------------------------------------------------------- aggregation


@functools.lru_cache(maxsize=16)
def _agg_fn_cached(n_lanes: int, n_pad: int, p_pad: int):
    """jit'd segmented min/max/sum/null-count over an int64 lane matrix
    plus a distinct-count of the (part, code) pairs in the LAST lane.
    Padded rows carry part id `p_pad` and are dropped by the segment
    ops. One dense output block -> one D2H transfer."""
    import jax
    import jax.numpy as jnp

    @obs.program("stats.ckpt_block")
    def kernel(vals, valid_words, parts, code_mult):
        valid = jnp.unpackbits(valid_words, axis=1, count=n_pad,
                               bitorder="little").astype(bool)
        seg = parts
        vmin = jnp.where(valid, vals, jnp.int64(IDENT_MIN))
        vmax = jnp.where(valid, vals, jnp.int64(IDENT_MAX))
        vsum = jnp.where(valid, vals, jnp.int64(0))
        nulls = (~valid).astype(jnp.int64)
        mins = jax.vmap(
            lambda v: jax.ops.segment_min(v, seg, num_segments=p_pad))(vmin)
        maxs = jax.vmap(
            lambda v: jax.ops.segment_max(v, seg, num_segments=p_pad))(vmax)
        sums = jax.vmap(
            lambda v: jax.ops.segment_sum(v, seg, num_segments=p_pad))(vsum)
        nullc = jax.vmap(
            lambda v: jax.ops.segment_sum(v, seg, num_segments=p_pad))(nulls)
        # distinct (part, partition-code) pairs via one sorted pass over
        # the last lane: sort the combined key, count fresh values per
        # part segment (sentinel = padded/invalid rows, sorts last)
        codes = vals[-1]
        okrow = valid[-1] & (seg < p_pad)
        sentinel = jnp.int64(IDENT_MIN)
        key = jnp.where(okrow, seg.astype(jnp.int64) * code_mult + codes,
                        sentinel)
        skey = jnp.sort(key)
        fresh = jnp.concatenate(
            [skey[:1] != sentinel,
             (skey[1:] != skey[:-1]) & (skey[1:] != sentinel)])
        part_of = jnp.where(skey == sentinel, jnp.int64(p_pad),
                            skey // code_mult).astype(jnp.int32)
        distinct = jax.ops.segment_sum(fresh.astype(jnp.int64), part_of,
                                       num_segments=p_pad)
        return jnp.concatenate(
            [mins, maxs, sums, nullc, distinct[None, :]], axis=0)

    return jax.jit(kernel)


def checkpoint_stats_block(
    lanes: Sequence[np.ndarray],
    valids: Sequence[np.ndarray],
    part_of_row: np.ndarray,
    n_parts: int,
    n_codes: int,
    device=None,
) -> np.ndarray:
    """Per-part aggregates of `lanes` on device, one dispatch, one dense
    D2H block of shape [4*L + 1, n_parts]: rows 0..L-1 min, L..2L-1 max,
    2L..3L-1 sum, 3L..4L-1 null count, last row = distinct partition
    codes (the last lane holds the partition-value dictionary codes).

    `device` colocates the lane upload with e.g. the resident replay
    state's device. All lanes int64, validity a packed bitplane, part
    ids int32 — the transfer plane committed in transfer_budget.json.
    """
    import jax

    from delta_tpu.ops.replay import pad_bucket

    n_l = len(lanes)
    n = int(lanes[0].shape[0]) if n_l else 0
    n_pad = pad_bucket(max(n, 1))
    p_pad = pad_bucket(max(n_parts, 1), min_bucket=8)
    lane_vals = np.zeros((n_l, n_pad), np.int64)
    vb = np.zeros((n_l, n_pad), bool)
    for i, (lane, valid) in enumerate(zip(lanes, valids)):
        lane_vals[i, :n] = np.asarray(lane, np.int64)
        vb[i, :n] = np.asarray(valid, bool)
    valid_words = np.packbits(vb, axis=1, bitorder="little")
    part_ids = np.full(n_pad, p_pad, np.int32)
    part_ids[:n] = np.asarray(part_of_row, np.int32)
    # a code multiplier > any code keeps (part, code) pairs distinct
    code_mult = np.int64(max(int(n_codes), 1) + 1)
    fn = _agg_fn_cached(n_l, n_pad, p_pad)
    # lane matrices are [n_l, n_pad]: each lane prices at its own unit
    # count (the manifest unit is one padded file row per stat lane)
    with obs.device_dispatch("stats.ckpt_block", key=(n_l, n_pad, p_pad),
                             budget="ckpt-stats-block",
                             units=n_pad) as dd, _x64():
        dd.h2d("lane_vals", lane_vals, units=n_l * n_pad)
        dd.h2d("valid_words", valid_words, units=n_l * n_pad)
        dd.h2d("part_ids", part_ids)
        block = fn(jax.device_put(lane_vals, device),
                   jax.device_put(valid_words, device),
                   jax.device_put(part_ids, device),
                   code_mult)
        return dd.d2h("block", np.asarray(block))[:, :n_parts]


def host_stats_block(
    lanes: Sequence[np.ndarray],
    valids: Sequence[np.ndarray],
    part_of_row: np.ndarray,
    n_parts: int,
    n_codes: int,
) -> np.ndarray:
    """Host-mode twin of `checkpoint_stats_block` — bit-identical
    output (same identities, same int64 arithmetic)."""
    n_l = len(lanes)
    out = np.zeros((4 * n_l + 1, n_parts), np.int64)
    out[0:n_l, :] = IDENT_MIN
    out[n_l:2 * n_l, :] = IDENT_MAX
    pid = np.asarray(part_of_row, np.int64)
    for p in range(n_parts):
        m = pid == p
        for i in range(n_l):
            v = np.asarray(lanes[i], np.int64)[m]
            ok = np.asarray(valids[i], bool)[m]
            if ok.any():
                out[i, p] = v[ok].min()
                out[n_l + i, p] = v[ok].max()
            out[2 * n_l + i, p] = int(v[ok].sum()) if ok.any() else 0
            out[3 * n_l + i, p] = int((~ok).sum())
        if n_l:
            codes = np.asarray(lanes[-1], np.int64)[m]
            okc = np.asarray(valids[-1], bool)[m]
            out[4 * n_l, p] = len(np.unique(codes[okc]))
    return out


# ------------------------------------------------------- DV bit packing


@functools.lru_cache(maxsize=16)
def _pack_fn_cached(n_pad: int, n_words: int):
    """jit'd scatter of flat bit indexes into a stack of roaring bitmap
    containers. Each set bit appears exactly once, so the per-word
    contributions are distinct powers of two and `add` == bitwise-or.
    The sentinel index (word == n_words) drops."""
    import jax
    import jax.numpy as jnp

    @obs.program("stats.dv_pack")
    def kernel(idx):
        word = (idx >> 5).astype(jnp.int32)
        bit = jnp.left_shift(jnp.uint32(1), (idx & 31).astype(jnp.uint32))
        return jnp.zeros(n_words, jnp.uint32).at[word].add(bit, mode="drop")

    return jax.jit(kernel)


def pack_bitmap_words(flat_bits: np.ndarray, n_containers: int,
                      device=None) -> np.ndarray:
    """Pack flat container-relative bit indexes (container * 65536 +
    low16) into `n_containers` 8192-byte roaring bitmap containers in
    one batched dispatch; returns a [n_containers, 8192] uint8 block
    (one dense D2H) laid out exactly like the host packer
    (little-endian bit order)."""
    import jax

    from delta_tpu.ops.replay import pad_bucket

    n = int(len(flat_bits))
    n_pad = pad_bucket(max(n, 1))
    n_words = int(n_containers) * _BITMAP_WORDS
    flat_idx = np.full(n_pad, n_words * 32, np.int64)
    flat_idx[:n] = np.asarray(flat_bits, np.int64)
    with obs.device_dispatch("stats.dv_pack", key=(n_pad, n_words),
                             budget="ckpt-dv-pack", units=n_pad) as dd, \
            _x64():
        dd.h2d("flat_idx", flat_idx)
        words = _pack_fn_cached(n_pad, n_words)(
            jax.device_put(flat_idx, device))
        out = dd.d2h("words", np.ascontiguousarray(np.asarray(words)))
    if out.dtype.byteorder == ">":  # pragma: no cover - LE hosts only
        out = out.astype("<u4")
    return out.view(np.uint8).reshape(n_containers, 8192)


# ------------------------------------------------------- DV bit decode


@functools.lru_cache(maxsize=16)
def _decode_fn_cached(i_pad: int, w_pad: int, n_words: int):
    """jit'd inverse of `_pack_fn_cached`: scatter array-container bit
    indexes AND whole bitmap-container words into one flat uint32 word
    stream. The two lane families are disjoint by construction — a
    roaring container is either array-coded (contributes single bits)
    or bitmap-coded (contributes whole words) — and set bits are
    unique, so `add` == bitwise-or throughout. Sentinels (bit index ==
    n_words*32, word position == n_words) drop."""
    import jax
    import jax.numpy as jnp

    @obs.program("stats.dv_decode")
    def kernel(bit_idx, bm_words, bm_pos):
        word = (bit_idx >> 5).astype(jnp.int32)
        bit = jnp.left_shift(jnp.uint32(1),
                             (bit_idx & 31).astype(jnp.uint32))
        out = jnp.zeros(n_words, jnp.uint32).at[word].add(bit, mode="drop")
        return out.at[bm_pos].add(bm_words, mode="drop")

    return jax.jit(kernel)


def decode_mask_words(bit_idx: np.ndarray, bm_words: np.ndarray,
                      bm_pos: np.ndarray, n_words: int,
                      device=None) -> np.ndarray:
    """Expand a deletion vector's containers to a flat little-endian
    uint32 word stream on device, one batched dispatch: `bit_idx` are
    absolute row indexes from array/run containers (int64), `bm_words`
    are raw bitmap-container words placed at word positions `bm_pos`.
    Returns [n_words] uint32 (one dense D2H) — the exact inverse of
    `pack_bitmap_words`."""
    import jax

    from delta_tpu.ops.replay import pad_bucket

    ni = int(len(bit_idx))
    nw = int(len(bm_words))
    i_pad = pad_bucket(max(ni, 1))
    w_pad = pad_bucket(max(nw, 1))
    lane_bit_idx = np.full(i_pad, int(n_words) * 32, np.int64)
    lane_bit_idx[:ni] = np.asarray(bit_idx, np.int64)
    lane_bm_words = np.zeros(w_pad, np.uint32)
    lane_bm_words[:nw] = np.asarray(bm_words, np.uint32)
    lane_bm_pos = np.full(w_pad, int(n_words), np.int32)
    lane_bm_pos[:nw] = np.asarray(bm_pos, np.int32)
    with obs.device_dispatch("stats.dv_decode",
                             key=(i_pad, w_pad, int(n_words)),
                             budget="dv-decode-lanes") as dd, _x64():
        dd.h2d("lane_bit_idx", lane_bit_idx, units=i_pad)
        dd.h2d("lane_bm_words", lane_bm_words, units=w_pad)
        dd.h2d("lane_bm_pos", lane_bm_pos, units=w_pad)
        words = _decode_fn_cached(i_pad, w_pad, int(n_words))(
            jax.device_put(lane_bit_idx, device),
            jax.device_put(lane_bm_words, device),
            jax.device_put(lane_bm_pos, device))
        out = dd.d2h("words", np.ascontiguousarray(np.asarray(words)))
    if out.dtype.byteorder == ">":  # pragma: no cover - LE hosts only
        out = out.astype("<u4")
    return out
