"""Device equi-join for MERGE matching.

The reference's MERGE finds matches with a Spark shuffle join
(`commands/merge/ClassicMergeExecutor.scala`). The TPU-native
formulation reuses the replay kernel's shape: dictionary-encode the join
keys host-side, then ONE fixed-shape device pass — sort (code, side) and
segment-reduce — produces everything MERGE's planner needs:

- per-target-row: the matching source row (or -1);
- per-source-row: whether any target row matched it (insert detection),
  shipped home as packed bits;
- one scalar: how many target rows have MULTIPLE matching source rows
  (the cardinality rule needs only the count — shipping a full per-row
  count lane home would triple the D2H bytes).

MERGE's cardinality rule makes the fixed shapes possible: a target row
matched by more than one source row is an ERROR when update/delete
clauses exist, so the successful output is exactly one source index per
target row — no variable-length pair materialization.

Operands are laid out as [target block | source block] with separately
bucket-padded static sizes, so outputs slice exactly on device and jit
programs are reused across growing tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from delta_tpu import obs
from delta_tpu.ops.replay import _unpack_bits, pad_bucket

_PAD_CODE = np.uint32(0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("nt_pad", "ns_pad"))
@obs.program("join.merge_match")
def _join_kernel(codes, nt_pad: int, ns_pad: int):
    """codes u32[nt_pad + ns_pad]: target codes then source codes, pads =
    all-ones sentinel. Returns (match_src i32[nt_pad] source-local row or
    -1, src_matched_words u32[ns_pad/32], n_multi i32[] count of target
    rows whose key has > 1 source row)."""
    n = nt_pad + ns_pad
    iota = jnp.arange(n, dtype=jnp.uint32)
    side = (iota >= nt_pad).astype(jnp.uint32)  # 0 target, 1 source
    # pads carry the sentinel code; their side bit doesn't matter — the
    # sentinel run never matches a real run's code
    s_code, s_side, s_pos = jax.lax.sort((codes, side, iota), num_keys=2)

    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), s_code[1:] != s_code[:-1]])
    run_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1

    pad_run = s_code == jnp.uint32(0xFFFFFFFF)
    is_src = (s_side == 1) & ~pad_run
    is_tgt = (s_side == 0) & ~pad_run
    n_src_per_run = jax.ops.segment_sum(
        is_src.astype(jnp.int32), run_id, num_segments=n)
    n_tgt_per_run = jax.ops.segment_sum(
        is_tgt.astype(jnp.int32), run_id, num_segments=n)
    src_pos_or_inf = jnp.where(is_src, s_pos, jnp.uint32(n))
    first_src_sorted = jax.ops.segment_min(
        src_pos_or_inf, run_id, num_segments=n)

    # scatter run aggregates back to input positions
    n_src_in = jnp.zeros((n,), jnp.int32).at[s_pos].set(n_src_per_run[run_id])
    n_tgt_in = jnp.zeros((n,), jnp.int32).at[s_pos].set(n_tgt_per_run[run_id])
    first_src_in = jnp.full((n,), jnp.uint32(n)).at[s_pos].set(
        first_src_sorted[run_id])

    match_src = jnp.where(
        n_src_in[:nt_pad] > 0,
        first_src_in[:nt_pad].astype(jnp.int32) - jnp.int32(nt_pad),
        jnp.int32(-1))
    n_multi = jnp.sum((n_src_in[:nt_pad] > 1).astype(jnp.int32))

    src_matched = (n_tgt_in[nt_pad:] > 0)
    bit_pos = jnp.arange(32, dtype=jnp.uint32)
    weights = jnp.uint32(1) << bit_pos
    src_words = (src_matched.reshape(-1, 32).astype(jnp.uint32)
                 * weights).sum(axis=1, dtype=jnp.uint32)
    return match_src, src_words, n_multi


def equi_join_codes(
    t_codes: np.ndarray, s_codes: np.ndarray, device=None,
) -> tuple[np.ndarray, int, np.ndarray]:
    """Join by pre-encoded key codes (< 0xFFFFFFFF). Returns
    (match_src int32[nt] source row index or -1, n_multi int,
    source_matched bool[ns])."""
    nt, ns = len(t_codes), len(s_codes)
    nt_pad = pad_bucket(max(nt, 1))
    ns_pad = pad_bucket(max(ns, 1))
    codes = np.full(nt_pad + ns_pad, _PAD_CODE, np.uint32)
    codes[:nt] = t_codes
    codes[nt_pad:nt_pad + ns] = s_codes
    with obs.device_dispatch("join.merge_match",
                             key=(nt_pad, ns_pad),
                             budget="merge-join-codes",
                             units=nt_pad + ns_pad) as dd:
        dd.h2d("codes", codes)
        codes_dev = jax.device_put(codes, device) \
            if device is not None else codes
        match_src, src_words, n_multi = _join_kernel(
            codes_dev, nt_pad=nt_pad, ns_pad=ns_pad)
    match_src = np.asarray(match_src)[:nt]
    src_matched = _unpack_bits(np.asarray(src_words), ns_pad)[:ns]
    return match_src, int(n_multi), src_matched


def equi_join_device(
    target_keys, source_keys, device=None,
) -> tuple[np.ndarray, int, np.ndarray]:
    """Join on one or more key columns (numpy arrays, null-free —
    callers drop SQL-null keys first). Dictionary-encodes
    (target ++ source) jointly with pandas factorize, then runs the
    device kernel. Returns (match_src, n_multi, source_matched) as in
    `equi_join_codes`."""
    import pandas as pd

    t_cols = [np.asarray(c) for c in target_keys]
    s_cols = [np.asarray(c) for c in source_keys]
    nt = len(t_cols[0]) if t_cols else 0
    codes = None
    for tc, sc in zip(t_cols, s_cols):
        both = np.concatenate([tc, sc])
        # use_na_sentinel=False: float NaN gets a REAL code (all NaNs the
        # same one), so NaN = NaN matches — Spark's equi-join semantics.
        # (Genuinely-NULL keys were dropped by the caller; the sentinel
        # -1 would wrap to 2**64-1 under uint64 and poison the radix.)
        c, _ = pd.factorize(both, sort=False, use_na_sentinel=False)
        c = c.astype(np.uint64)
        if codes is None:
            codes = c
        else:
            codes = codes * np.uint64(int(c.max(initial=0)) + 1) + c
        if int(codes.max(initial=0)) >= 1 << 32:
            # keep the running radix far from uint64 wrap (3+ wide keys)
            _, codes = np.unique(codes, return_inverse=True)
            codes = codes.astype(np.uint64)
    if codes is None:
        raise ValueError("equi_join_device requires at least one key")
    if int(codes.max(initial=0)) >= 0xFFFFFFFF - 1:
        # joint radix overflows u32: re-densify
        _, codes = np.unique(codes, return_inverse=True)
    codes = codes.astype(np.uint32)
    return equi_join_codes(codes[:nt], codes[nt:], device=device)
