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
(`ckpt-stats-block`, `ckpt-dv-pack`): lane matrix int64 `[L, n_pad]`,
validity as a packed bitplane in uint32 words `[L, n_pad / 32]` (bit k
of word j is row 32 j + k), part ids int32 `[n_pad]`, and three scalars
(the parts, the code multiplier, the bits of the largest pair key); DV packing
ships one int64 flat bit index per set bit. The dispatch record of
`stats.ckpt_block` carries its shape as `attrs` (`lanes`, `n_pad`,
`p_pad`, which is what a roofline reader prices it from, and `win`,
the rows a part's pass reads).

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
def _agg_fn_cached(n_lanes: int, n_pad: int, p_pad: int, win: int,
                   one_code: bool):
    """jit'd segmented min/max/sum/null-count over an int64 lane matrix
    plus a distinct-count of the (part, code) pairs in the LAST lane.
    Padded rows carry part id `p_pad`, which no pass asks for. One
    dense output block -> one D2H transfer.

    `win` is the rows a part's pass reads. The writer's parts are runs
    of rows in order (`checkpointer._chunk_plan`), so a part's rows lie
    in one window of the largest part's bucket from its first row, and
    a block costs about its rows whatever the parts; part ids in no
    order take `win == n_pad`, every pass over every row. `one_code`
    says every valid code is 0 (an unpartitioned table): a part then
    has one pair where it has a row, and nothing is sorted.

    Three forms are what the v5e compiler and the chip are quick over at
    a 2.6M-row bucket (PERF.md has the seconds): the validity words are
    uint32 and unpacked by the tree's own shift-and-mask (a
    `jnp.unpackbits` shifts 8-bit lanes); the pairs are put in order by
    `sqlops._radix_perm`, passes of one unstable uint32 sort each (a
    one-key int64 `jnp.sort` alone compiles for 83 s); and a part's
    reductions are one masked pass over the lanes, a part after another
    (sixteen `segment_min/max/sum` scatters of 2.6M rows into 8 segments
    run for 0.9 s vmapped, with 2.5 GB of temporaries, and for 2.5 s a
    lane at a time: a scatter's cost is its rows', whatever the
    segments)."""
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops.replay import _unpack_bits_device
    from delta_tpu.ops.sqlops import _digit_bits, _radix_perm

    width = _digit_bits(n_pad)
    part_range = np.arange(p_pad + 1, dtype=np.int32)

    def pairs_by_part(vals, valid, parts, code_mult, key_bits):
        # distinct (part, partition-code) pairs via one ordered pass
        # over the last lane: order the combined key and mark the fresh
        # values; the order is the parts' too, so a part's pairs are the
        # fresh ones between its bounds. Padded and invalid rows share
        # the one key past every real pair and the part `p_pad`, so
        # they count for nothing.
        okrow = valid[-1] & (parts < p_pad)
        pair_part = jnp.where(okrow, parts, jnp.int32(p_pad))
        key = (pair_part.astype(jnp.int64) * code_mult
               + jnp.where(okrow, vals[-1], jnp.int64(0))).astype(jnp.uint64)
        steps = jnp.arange(-(-64 // width), dtype=jnp.int32)
        perm = _radix_perm(key[None, :], jnp.zeros_like(steps),
                           steps * width, (key_bits + width - 1) // width)
        skey = key[perm]
        fresh = jnp.concatenate([jnp.ones((1,), bool),
                                 skey[1:] != skey[:-1]])
        before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(fresh.astype(jnp.int32))])
        bounds = jnp.searchsorted(pair_part[perm], part_range)
        return (before[bounds[1:]] - before[bounds[:-1]]).astype(jnp.int64)

    @obs.program("stats.ckpt_block")
    def kernel(vals, valid_words, parts, n_parts, code_mult, key_bits):
        valid = (_unpack_bits_device(valid_words.reshape(-1))
                 != 0).reshape(n_lanes, n_pad)
        pairs = (None if one_code else
                 pairs_by_part(vals, valid, parts, code_mult, key_bits))
        # where the parts are in order, the first row of each
        first = (jnp.searchsorted(parts, part_range[:-1]).astype(jnp.int32)
                 if win < n_pad else None)

        def with_part(p, block):
            v, ok, ids = vals, valid, parts
            if first is not None:
                at = jnp.minimum(first[p], n_pad - win)
                v = jax.lax.dynamic_slice_in_dim(v, at, win, axis=1)
                ok = jax.lax.dynamic_slice_in_dim(ok, at, win, axis=1)
                ids = jax.lax.dynamic_slice_in_dim(ids, at, win)
            mine = (ids == p)[None, :]
            nulls = jnp.sum((mine & ~ok).astype(jnp.int64), axis=1)
            ok = mine & ok
            column = jnp.concatenate([
                jnp.min(jnp.where(ok, v, jnp.int64(IDENT_MIN)), axis=1),
                jnp.max(jnp.where(ok, v, jnp.int64(IDENT_MAX)), axis=1),
                jnp.sum(jnp.where(ok, v, jnp.int64(0)), axis=1),
                nulls,
                (jnp.any(ok[-1]).astype(jnp.int64)[None] if one_code
                 else pairs[p][None])])
            return jax.lax.dynamic_update_slice_in_dim(
                block, column[:, None], p, axis=1)

        # a pass a real part: the padding's columns are cut off unread
        return jax.lax.fori_loop(
            0, n_parts, with_part,
            jnp.zeros((4 * n_lanes + 1, p_pad), jnp.int64))

    return jax.jit(kernel)


def _pass_rows(part_ids: np.ndarray, n: int, n_parts: int, n_pad: int) -> int:
    """The rows a part's pass has to read (`win` above): the bucket of
    the largest part where the rows' part ids never step down, else
    every row."""
    ids = part_ids[:n]
    if n_parts <= 1 or n == 0 or (ids[1:] < ids[:-1]).any():
        return n_pad
    from delta_tpu.ops.replay import pad_bucket

    return min(pad_bucket(int(np.bincount(ids).max())), n_pad)


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
    codes (the last lane holds the partition-value dictionary codes,
    each under `n_codes`).

    `device` colocates the lane upload with e.g. the resident replay
    state's device. All lanes int64, validity a bitplane packed into
    uint32 words, part ids int32 — the transfer plane committed in
    transfer_budget.json.
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
    # bit k of word j is row 32 j + k (n_pad is a multiple of 1024)
    valid_words = np.packbits(vb, axis=1, bitorder="little").view("<u4")
    part_ids = np.full(n_pad, p_pad, np.int32)
    part_ids[:n] = np.asarray(part_of_row, np.int32)
    # a code multiplier > any code keeps (part, code) pairs distinct
    code_mult = np.int64(max(int(n_codes), 1) + 1)
    # the pairs' keys run to p_pad * code_mult, the padding's included
    key_bits = np.int32((int(p_pad) * int(code_mult)).bit_length())
    win = _pass_rows(part_ids, n, n_parts, n_pad)
    one_code = int(n_codes) <= 1
    fn = _agg_fn_cached(n_l, n_pad, p_pad, win, one_code)
    # lane matrices are [n_l, n_pad]: each lane prices at its own unit
    # count (the manifest unit is one padded file row per stat lane)
    with obs.device_dispatch("stats.ckpt_block",
                             key=(n_l, n_pad, p_pad, win, one_code),
                             budget="ckpt-stats-block",
                             units=n_pad) as dd, _x64():
        dd.h2d("lane_vals", lane_vals, units=n_l * n_pad)
        dd.h2d("valid_words", valid_words, units=n_l * n_pad)
        dd.h2d("part_ids", part_ids)
        dd.set(lanes=n_l, n_pad=n_pad, p_pad=p_pad, win=win)
        block = fn(jax.device_put(lane_vals, device),
                   jax.device_put(valid_words, device),
                   jax.device_put(part_ids, device),
                   np.int32(n_parts), code_mult, key_bits)
        # the launch is asynchronous: this read is the wait for the chip
        with obs.span("stats.wait", kernel="stats.ckpt_block",
                      rows=n, parts=int(n_parts)):
            block = np.asarray(block)
        return dd.d2h("block", block)[:, :n_parts]


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
