"""Sharded snapshot state reconstruction over a device mesh.

This is the TPU-native counterpart of the reference's distributed replay
(`Snapshot.scala:481-511`): shuffle by path hash, per-partition
reconcile. Here:

1. HOST ROUTE — rows are binned by `path_key % n_shards` (the
   "shuffle"; a stable numpy argsort by shard id, so each shard's rows
   stay in chronological order and the in-shard row index is the
   chronological rank). The key fully determines its shard, so
   per-shard reconciliation is globally correct with zero cross-device
   key exchange. Rows sharing a path (any DV id) land together.
2. TRANSFER — the same first-appearance delta coding as the
   single-chip kernel (`ops/replay.py`), per shard. The trick that
   makes it free: global path codes are dense first-appearance codes,
   so shard s's local code for path c ≡ s (mod S) is exactly c // S —
   itself a dense first-appearance coding of the shard's stream. The
   global `is_new` flags route through unchanged; explicit refs ship as
   byte planes; the DV lane ships sparse; is_add ships bit-packed.
   ~1-2 bits/row crosses the link instead of 9 bytes/row.
3. DEVICE — under `shard_map` each device rebuilds its local code
   lane with a cumsum + gather, runs the same (key, chrono) sort +
   run-boundary last-wins reduce as the single-chip kernel, and
   contributes to global aggregates (live-file count, live bytes) with
   `psum` over the ICI. Winner masks come home bit-packed (32x smaller
   D2H).
4. HOST GATHER — per-shard winner words are unpacked, split into
   live/tombstone with the host-resident add bits, and scattered back
   to the original row order.

Streams that aren't first-appearance-coded (host-hashed keys, permuted
histories) fall back to shipping raw u32 key lanes — same kernel tail,
fatter transfer.

Multi-host scale-out: the mesh spans hosts; each host routes only the
rows it parsed (`jax.make_array_from_process_local_data`), the psum
rides ICI within a pod and DCN across pods — no NCCL/MPI analogue
needed, XLA owns the collectives. See tests/test_multiprocess.py for
the 2-process jax.distributed harness.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from delta_tpu import obs
from delta_tpu.ops.replay import (
    _PAD_KEY,
    _decode_planes,
    _sort_winner_pack,
    _unpack_bits,
    _unpack_bits_device,
    chrono_ok,
    derive_fa_flags,
    key_byte_width,
    pad_bucket,
)
from delta_tpu.parallel.mesh import REPLAY_AXIS, make_mesh

# Same counter as the single-chip launch path (ops/replay.py): total
# replay operand bytes shipped host->device, read by the residency
# tests and the bench transfer accounting.
_H2D_BYTES = obs.counter("replay.h2d_bytes")
_LAUNCHES = obs.counter("replay.sharded_launches")
# the one collective of the replay, by name in a profile
PSUM_SCOPE = "replay.psum"


# --------------------------------------------------------------- raw path


def _shard_kernel(key, is_add, size):
    """Per-device replay over its local [1, M] shard block. Rows arrive
    in chronological order (stable routing), so the local iota is the
    chronological tiebreaker."""
    key, is_add, size = key[0], is_add[0], size[0]
    m = key.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    s_key, s_idx, s_add, s_size = lax.sort(
        (key, idx, is_add, size), num_keys=2, is_stable=False
    )
    is_last = jnp.concatenate([s_key[:-1] != s_key[1:], jnp.ones((1,), bool)])
    live_s = is_last & s_add
    tomb_s = is_last & ~s_add
    live = jnp.zeros((m,), bool).at[s_idx].set(live_s)
    tomb = jnp.zeros((m,), bool).at[s_idx].set(tomb_s)
    # global aggregates over the ICI (padding rows: add=False, size=0)
    local_live = jnp.sum(live_s.astype(jnp.int32))
    local_bytes = jnp.sum(jnp.where(live_s, s_size, 0.0))
    with jax.named_scope(PSUM_SCOPE):
        num_live = lax.psum(local_live, REPLAY_AXIS)
        live_bytes = lax.psum(local_bytes, REPLAY_AXIS)
    return live[None], tomb[None], num_live, live_bytes


def build_sharded_replay_fn(mesh: Mesh):
    """jit'd [S, M]-batch replay over `mesh` (S = mesh size) — raw-key
    operands (uint32 key, bool add, f32 size)."""
    spec = P(REPLAY_AXIS, None)
    fn = shard_map(
        _shard_kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, P(), P()),
    )
    return jax.jit(obs.program("replay.sharded_raw")(fn))


def route_to_shards(
    path_key: np.ndarray,
    dv_key: np.ndarray,
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    size: Optional[np.ndarray],
    n_shards: int,
):
    """Host-side shuffle for the raw path: returns ([S, M] operand
    arrays (key, is_add, size), scatter indexes) where
    scatter_index[s, j] = original row (or -1 for padding)."""
    n = len(path_key)
    # perm=None in the common chronological case avoids three O(n) copies
    perm = None
    if not chrono_ok(np.asarray(version), np.asarray(order)):
        perm = np.lexsort((order, version)).astype(np.int64)
    key = _combined_u32(path_key, dv_key)
    is_add = np.asarray(is_add, bool)
    size_p = None if size is None else np.asarray(size)
    if perm is not None:
        key = key[perm]
        is_add = is_add[perm]
        size_p = None if size_p is None else size_p[perm]

    shard_of = (key % np.uint32(n_shards)).astype(np.int64)
    sort_idx, rows, cols, counts, m = _shard_coords(shard_of, n_shards)

    k = np.full((n_shards, m), _PAD_KEY, dtype=np.uint32)
    add = np.zeros((n_shards, m), dtype=np.bool_)
    sz = np.zeros((n_shards, m), dtype=np.float32)
    scatter = np.full((n_shards, m), -1, dtype=np.int32)

    k[rows, cols] = key[sort_idx]
    add[rows, cols] = is_add[sort_idx]
    if size_p is not None:
        sz[rows, cols] = size_p[sort_idx].astype(np.float32)
    orig = sort_idx if perm is None else perm[sort_idx]
    scatter[rows, cols] = orig.astype(np.int32)
    return (k, add, sz), scatter


def _combined_u32(path_key: np.ndarray, dv_key: np.ndarray) -> np.ndarray:
    """Combined (path, dv) -> one dense uint32 lane below the pad
    sentinel (re-encoding through np.unique when the radix product
    overflows)."""
    from delta_tpu.ops.replay import combine_key_lanes

    key = combine_key_lanes([path_key, dv_key])
    if key is None:
        wide = path_key.astype(np.uint64) << np.uint64(32) | dv_key.astype(
            np.uint64)
        _, key = np.unique(wide, return_inverse=True)
        key = key.astype(np.uint32)
    return key


def _shard_coords(shard_of: np.ndarray, n_shards: int):
    """(sort_idx, rows, cols, counts, padded bucket M) of the stable
    shard sort."""
    n = len(shard_of)
    sort_idx = np.argsort(shard_of, kind="stable")
    counts = np.bincount(shard_of, minlength=n_shards)
    m = pad_bucket(int(counts.max(initial=1)))
    starts = np.zeros(n_shards + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    rows = shard_of[sort_idx]
    cols = np.arange(n) - starts[rows]
    return sort_idx, rows, cols, counts, m


# ---------------------------------------------------------------- FA path


class ShardedFAOperands(NamedTuple):
    """Routed, delta-coded device operands + host bookkeeping."""
    flag_words: np.ndarray        # [S, M/32] u32 is_new bits
    ref_planes: tuple             # each [S, R] u8 (little-endian planes)
    sub_radix: int                # DV lane radix (1 = no DV anywhere)
    sub_idx: np.ndarray           # [S, D] u32 in-shard rows (pad 0xFFFFFFFF)
    sub_val: np.ndarray           # [S, D] u32
    n_real: np.ndarray            # [S, 1] i32 rows per shard
    add_words: np.ndarray         # [S, M/32] u32 is_add bits
    scatter: np.ndarray           # [S, M] i32 original row (-1 = pad)
    m: int
    nbytes: int                   # H2D payload bytes (transfer accounting)


def route_to_shards_fa(
    path_key: np.ndarray,
    dv_key: np.ndarray,
    is_new: np.ndarray,
    is_add: np.ndarray,
    n_shards: int,
) -> Optional[ShardedFAOperands]:
    """FA-coded routing (chronological input required — caller permutes
    first). Returns None when ranges don't fit (caller falls back to the
    raw route)."""
    n = len(path_key)
    path_key = np.asarray(path_key, np.uint32)
    dv_key = np.asarray(dv_key, np.uint32)
    n_uniq = (int(path_key.max()) + 1) if n else 0
    local_max = (n_uniq - 1) // n_shards if n_uniq else 0
    sub_radix = int(dv_key.max(initial=0)) + 1
    # the device key is local_code * sub_radix + dv; keep the pad
    # sentinel exclusive
    if (local_max + 1) * sub_radix >= 0xFFFFFFFF:
        return None

    shard_of = (path_key % np.uint32(n_shards)).astype(np.int64)
    sort_idx, rows, cols, counts, m = _shard_coords(shard_of, n_shards)

    # is_new flags route through unchanged (a globally-new path is new
    # in its shard; refs always target a path first seen in the SAME
    # shard because routing is by path)
    sorted_new = np.asarray(is_new, bool)[sort_idx]
    flags = np.zeros((n_shards, m), dtype=np.bool_)
    flags[rows, cols] = sorted_new
    flag_words = np.packbits(flags, axis=1, bitorder="little").view(np.uint32)

    add = np.zeros((n_shards, m), dtype=np.bool_)
    add[rows, cols] = np.asarray(is_add, bool)[sort_idx]
    add_words = np.packbits(add, axis=1, bitorder="little").view(np.uint32)

    # explicit refs: non-new rows, local code = global code // S, in
    # shard-stream order (the stable sort preserves it)
    ref_rows = rows[~sorted_new]
    ref_vals = (path_key[sort_idx][~sorted_new] //
                np.uint32(n_shards)).astype(np.uint32)
    ref_counts = np.bincount(ref_rows, minlength=n_shards)
    r_pad = pad_bucket(int(ref_counts.max(initial=1)), min_bucket=128)
    ref_width = key_byte_width(local_max)
    refs2d = np.zeros((n_shards, r_pad), dtype=np.uint32)
    ref_starts = np.zeros(n_shards + 1, dtype=np.int64)
    np.cumsum(ref_counts, out=ref_starts[1:])
    ref_cols = np.arange(len(ref_vals)) - ref_starts[ref_rows]
    refs2d[ref_rows, ref_cols] = ref_vals
    rbytes = refs2d.view(np.uint8).reshape(n_shards, r_pad, 4)
    ref_planes = tuple(
        np.ascontiguousarray(rbytes[:, :, j]) for j in range(ref_width))

    # DV lane: sparse (in-shard row, value); pad rows scatter-drop
    if sub_radix > 1:
        dv_sorted = dv_key[sort_idx]
        nz = dv_sorted != 0
        nz_rows = rows[nz]
        nz_counts = np.bincount(nz_rows, minlength=n_shards)
        d_pad = pad_bucket(int(nz_counts.max(initial=1)), min_bucket=128)
        sub_idx = np.full((n_shards, d_pad), 0xFFFFFFFF, dtype=np.uint32)
        sub_val = np.zeros((n_shards, d_pad), dtype=np.uint32)
        nz_starts = np.zeros(n_shards + 1, dtype=np.int64)
        np.cumsum(nz_counts, out=nz_starts[1:])
        nz_cols = np.arange(int(nz.sum())) - nz_starts[nz_rows]
        sub_idx[nz_rows, nz_cols] = cols[nz].astype(np.uint32)
        sub_val[nz_rows, nz_cols] = dv_sorted[nz]
    else:
        sub_idx = np.empty((n_shards, 0), dtype=np.uint32)
        sub_val = np.empty((n_shards, 0), dtype=np.uint32)

    scatter = np.full((n_shards, m), -1, dtype=np.int32)
    scatter[rows, cols] = sort_idx.astype(np.int32)

    n_real = counts.astype(np.int32).reshape(n_shards, 1)
    nbytes = (flag_words.nbytes + sum(p.nbytes for p in ref_planes)
              + sub_idx.nbytes + sub_val.nbytes + n_real.nbytes
              + add_words.nbytes)
    return ShardedFAOperands(flag_words, ref_planes, sub_radix, sub_idx,
                             sub_val, n_real, add_words, scatter,
                             m, nbytes)


def _shard_kernel_fa(ref_width: int, has_sub: bool, want_key: bool = False):
    """Kernel body factory for the FA-coded sharded replay. With
    `want_key` the rebuilt per-shard key lane is returned as a third
    output so the caller can keep it device-resident across
    `Snapshot.update()` calls (parallel/resident.py) — the lane already
    exists on device, so residency costs zero extra transfer."""

    def kernel(*ops):
        flag_words = ops[0][0]
        ref_planes = tuple(o[0] for o in ops[1:1 + ref_width])
        rest = ops[1 + ref_width:]
        if has_sub:
            sub_radix, sub_idx, sub_val = (rest[0], rest[1][0], rest[2][0])
            rest = rest[3:]
        n_real = rest[0][0][0]
        add_words = rest[1][0]

        m = flag_words.shape[0] * 32
        is_new = _unpack_bits_device(flag_words)
        new_rank = jnp.cumsum(is_new.astype(jnp.int32))
        ref_rank = jnp.arange(1, m + 1, dtype=jnp.int32) - new_rank
        refs = _decode_planes(ref_planes)
        ref_gather = refs[jnp.clip(ref_rank - 1, 0, refs.shape[0] - 1)]
        key = jnp.where(is_new == 1, (new_rank - 1).astype(jnp.uint32),
                        ref_gather)
        if has_sub:
            sub = jnp.zeros((m,), jnp.uint32).at[sub_idx].set(
                sub_val, mode="drop")
            key = key * sub_radix + sub
        iota = jnp.arange(m, dtype=jnp.int32)
        key = jnp.where(iota < n_real, key, jnp.uint32(0xFFFFFFFF))

        winner_words = _sort_winner_pack((key,), n_real)
        live_words = winner_words & add_words
        live_bits = _unpack_bits_device(live_words)
        local_live = jnp.sum(live_bits.astype(jnp.int32))
        # the only cross-device exchange in the whole replay: one scalar
        # psum over the ICI (int32 — exact)
        with jax.named_scope(PSUM_SCOPE):
            num_live = lax.psum(local_live, REPLAY_AXIS)
        if want_key:
            return winner_words[None], num_live, key[None]
        return winner_words[None], num_live

    return kernel


@functools.lru_cache(maxsize=32)
def _fa_fn_cached(mesh: Mesh, ref_width: int, has_sub: bool,
                  want_key: bool = False):
    spec = P(REPLAY_AXIS, None)
    in_specs = [spec]                       # flag_words
    in_specs += [spec] * ref_width          # ref planes
    if has_sub:
        in_specs += [P(), spec, spec]       # sub_radix (replicated), idx, val
    in_specs += [spec, spec]                # n_real, add_words
    out_specs = (spec, P(), spec) if want_key else (spec, P())
    fn = shard_map(
        _shard_kernel_fa(ref_width, has_sub, want_key),
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
    )
    return jax.jit(obs.program("replay.sharded_fa")(fn))


def build_sharded_replay_fa_fn(mesh: Mesh, ref_width: int, has_sub: bool,
                               want_key: bool = False):
    return _fa_fn_cached(mesh, ref_width, has_sub, want_key)


# ------------------------------------------------------------ public API


class ResidentPayload(NamedTuple):
    """Everything `parallel/resident.py` needs to keep a sharded replay
    device-resident after `sharded_replay_select` returns: the rebuilt
    per-shard key lane (already on device — zero extra transfer) plus
    the host-side routing bookkeeping."""
    key_sh: object                # jax [S, M] u32, NamedSharding over mesh
    mesh: Mesh
    m: int
    n_real: np.ndarray            # [S] i32 rows per shard
    add_words: np.ndarray         # [S, M/32] u32
    scatter: np.ndarray           # [S, M] i32 original row (-1 = pad)
    n: int                        # total real rows
    n_uniq: int                   # dense path-code count (sub_radix == 1)


def sharded_replay_select(
    path_key: np.ndarray,
    dv_key: np.ndarray,
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    size: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
    fa_hint: Optional[tuple] = None,
    resident_sink: Optional[list] = None,
) -> tuple[np.ndarray, np.ndarray, int, int]:
    """Full pipeline; returns (live_mask, tomb_mask, num_live, live_bytes)
    in original row order. `fa_hint` = (is_new flags, refs, n_uniq) from
    the native scanner's in-scan dictionary (refs unused here — the
    sharded route re-derives per-shard refs from the codes).

    `resident_sink`: when the FA route runs with no DV lane, a
    `ResidentPayload` is appended so the caller can keep the per-shard
    state device-resident (see parallel/resident.py); otherwise the list
    is left untouched. Rows that arrive out of chronological order (the
    generic parser hands a tail's adds before its removes) are routed in
    that order and the payload's scatter names the caller's rows, so
    residency does not hang on which parser read the commits."""
    if mesh is None:
        mesh = make_mesh()
    n = len(path_key)
    if n == 0:
        z = np.zeros(0, bool)
        return z, z, 0, 0
    n_shards = mesh.devices.size

    size_orig = size  # original row order, for the exact host aggregate
    with obs.span("replay.shard_route", rows=n, shards=n_shards) as sp:
        perm = None
        if not chrono_ok(np.asarray(version), np.asarray(order)):
            perm = np.lexsort((order, version)).astype(np.int64)
            path_key = np.asarray(path_key)[perm]
            dv_key = np.asarray(dv_key)[perm]
            is_add = np.asarray(is_add)[perm]
            size = None if size is None else np.asarray(size)[perm]
            fa_hint = None  # hint flags were in original row order

        is_new = fa_hint[0] if fa_hint is not None else None
        if is_new is None or len(is_new) != n:
            is_new = derive_fa_flags(np.asarray(path_key))

        fa = None
        if is_new is not None:
            fa = route_to_shards_fa(path_key, dv_key, is_new, is_add,
                                    n_shards)
        if fa is None:
            operands, scatter = route_to_shards(
                path_key, dv_key,
                np.arange(n, dtype=np.int64), np.zeros(n, np.int64),
                is_add, size, n_shards)
        if sp.recording:
            # how evenly the path key fills the shards, and what a
            # shard is padded to
            per_shard = (fa.n_real if fa is not None
                         else (scatter >= 0).sum(axis=1))
            sp.set_attrs(rows_min=int(per_shard.min()),
                         rows_max=int(per_shard.max()),
                         m=fa.m if fa is not None else scatter.shape[1])
    spec = NamedSharding(mesh, P(REPLAY_AXIS, None))
    live_bytes = None
    if fa is not None:
        has_sub = fa.sub_radix > 1
        want_key = resident_sink is not None and not has_sub
        ops = [fa.flag_words, *fa.ref_planes]
        if has_sub:
            ops += [np.uint32(fa.sub_radix), fa.sub_idx, fa.sub_val]
        ops += [fa.n_real, fa.add_words]
        # the budget entry is non-exhaustive: ref planes and the DV lane
        # are data-dependent and accounted through replay.h2d_bytes; the
        # two committed bitplanes are priced per padded shard row
        fa_rows = n_shards * fa.m
        with obs.device_dispatch("replay.sharded_fa",
                                 key=(n_shards, fa.m, len(fa.ref_planes),
                                      has_sub, want_key),
                                 budget="sharded-replay-fa-plane",
                                 units=fa_rows, gate="replay",
                                 route="sharded") as dd:
            dd.set(shards=n_shards, m=fa.m, ref_planes=len(fa.ref_planes),
                   want_key=want_key)
            dd.h2d("flag_words", fa.flag_words)
            dd.h2d("add_words", fa.add_words)
            for i, rp in enumerate(fa.ref_planes):
                dd.h2d(f"ref_plane_{i}", rp)
            with obs.span("replay.shard_transfer", nbytes=fa.nbytes,
                          route="fa"):
                _H2D_BYTES.inc(fa.nbytes)
                device_ops = tuple(
                    o if np.isscalar(o) or o.ndim == 0
                    else jax.device_put(o, spec)
                    for o in ops)
            # scalar sub_radix is replicated, not sharded
            fn = build_sharded_replay_fa_fn(mesh, len(fa.ref_planes),
                                            has_sub, want_key)
            with obs.span("replay.shard_reconcile", shards=n_shards,
                          route="fa"):
                _LAUNCHES.inc()
                if want_key:
                    winner_sh, num_live, key_sh = fn(*device_ops)
                else:
                    winner_sh, num_live = fn(*device_ops)
                # the launch returns at once; this read is where the
                # host waits for the chips
                with obs.span("replay.wait", rows=n) as sp:
                    winner_words = dd.d2h("winner_words",
                                          np.asarray(winner_sh))
                    sp.set_attr("bytes", winner_words.nbytes)
        if want_key:
            # slots hold the rows in chronological order; the caller's
            # masks are in its own
            rows = fa.scatter if perm is None else np.where(
                fa.scatter >= 0, perm[fa.scatter], -1).astype(np.int32)
            resident_sink.append(ResidentPayload(
                key_sh=key_sh, mesh=mesh, m=fa.m,
                n_real=fa.n_real.reshape(-1).astype(np.int64),
                add_words=fa.add_words, scatter=rows, n=n,
                n_uniq=(int(np.asarray(path_key).max()) + 1) if n else 0))
        scatter = fa.scatter
    else:
        nbytes = sum(int(o.nbytes) for o in operands)
        with obs.device_dispatch("replay.sharded_raw",
                                 key=(n_shards, operands[0].shape[1]),
                                 gate="replay", route="sharded") as dd:
            dd.set(shards=n_shards, m=operands[0].shape[1], ref_planes=0,
                   want_key=False)
            dd.h2d("operands", nbytes)
            with obs.span("replay.shard_transfer", nbytes=nbytes,
                          route="raw"):
                _H2D_BYTES.inc(nbytes)
                device_ops = tuple(jax.device_put(o, spec)
                                   for o in operands)
            fn = _cached_fn(mesh)
            with obs.span("replay.shard_reconcile", shards=n_shards,
                          route="raw"):
                _LAUNCHES.inc()
                live_sh, tomb_sh, num_live, live_bytes = fn(*device_ops)
                with obs.span("replay.wait", rows=n) as sp:
                    flat_live = np.asarray(live_sh).ravel()
                    flat_tomb = np.asarray(tomb_sh).ravel()
                    sp.set_attr("bytes", flat_live.nbytes + flat_tomb.nbytes)

    with obs.span("replay.shard_gather", rows=n, shards=n_shards,
                  _verbose=n < obs.PHASE_SPAN_ROWS):
        if fa is not None:
            live_words = winner_words & fa.add_words
            tomb_words = winner_words & ~fa.add_words
            flat_live = _unpack_bits(live_words.ravel(), n_shards * fa.m)
            flat_tomb = _unpack_bits(tomb_words.ravel(), n_shards * fa.m)
        live = np.zeros(n, dtype=bool)
        tomb = np.zeros(n, dtype=bool)
        flat_scatter = scatter.ravel()
        sel = flat_scatter >= 0
        live[flat_scatter[sel]] = flat_live[sel]
        tomb[flat_scatter[sel]] = flat_tomb[sel]
        if perm is not None:
            inv_live = np.zeros(n, dtype=bool)
            inv_tomb = np.zeros(n, dtype=bool)
            inv_live[perm] = live
            inv_tomb[perm] = tomb
            live, tomb = inv_live, inv_tomb

    n_live = int(num_live)
    if size_orig is not None:
        if live_bytes is None:
            # FA route ships no size lane: exact int64 host aggregate
            # (`live` is already back in original row order here)
            bytes_out = int(np.asarray(size_orig)[live].sum())
        else:
            bytes_out = int(live_bytes)  # raw route's f32 device psum
    else:
        bytes_out = 0
    return live, tomb, n_live, bytes_out


@functools.lru_cache(maxsize=8)
def _sharded_fn_for(mesh_key):
    return build_sharded_replay_fn(mesh_key[0])


def _cached_fn(mesh: Mesh):
    return _sharded_fn_for((mesh,))


def sharded_replay_step(mesh: Mesh):
    """The framework's "training step" equivalent for dry-run compilation:
    one jitted function that takes the routed [S, M] batch and returns
    masks + global aggregates, sharded over `mesh`."""
    return build_sharded_replay_fn(mesh)
