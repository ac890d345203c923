"""Log replay as a device sort + segmented last-wins reduce.

The reconciliation contract (PROTOCOL.md:823-843): for each logical file
key `(path, dv_unique_id)`, the newest action wins — a surviving `add` is
a live file, a surviving `remove` is a tombstone (kept for VACUUM), and
the live/tombstone key sets are disjoint.

The reference implements this as sequential hash-map upserts per action
(ascending, spark `InMemoryLogReplay.scala:52`) or hash-set probes
(descending, kernel `ActiveAddFilesIterator.java:146`). Neither
vectorizes. The TPU-native formulation used here:

1. The columnarizer emits actions in chronological order (checkpoint
   rows, then commits ascending, line order within a commit), so the row
   index *is* the chronological rank — no (version, order) columns need
   to ship to the device; a device-side iota is the sort tiebreaker.
   (If a caller passes rows out of order, the host permutes them into
   chronological order first and un-permutes the masks after — the
   kernel itself never sees a rank lane.)
2. Key lanes are dense dictionary codes assigned by the columnarizer in
   FIRST-APPEARANCE order (`pd.factorize`, replay/state.py). In a real
   Delta log every `add` carries a fresh UUID file name, so most rows
   introduce a brand-new code — which, under first-appearance coding, is
   always `prev_max + 1`. The transfer exploits that: one `is_new` flag
   bit per row, explicit byte-packed codes only for the minority of rows
   that reference an existing file (removes, DV re-adds), and a sparse
   (row, value) list for the rare non-zero DV lane. The device rebuilds
   the exact code array with a cumsum + gather. Typical cost: ~1–2
   bits/row over the host↔device link instead of 4 bytes. Streams that
   aren't first-appearance-coded (verified host-side with two cheap
   vector passes) fall back to shipping the combined code lane as the
   minimum number of little-endian byte planes that hold its range.
3. One `lax.sort` by (key, chrono_rank) — two operands total, both
   sort keys, and the rank is a device-side iota. After the sort every
   logical file's history is a contiguous run in chronological order;
   the run-boundary mask `key[i] != key[i+1]` marks the newest action
   per key. No loops, no hash table. The add/remove bit never ships:
   the iota is already unique, so the bit cannot change any winner, and
   the host keeps its own packed copy for the live/tombstone split.
4. One scatter puts the per-run winner mask back in input order; the
   winner bits ship home packed (32× smaller D2H) and the host — which
   already holds `is_add` — splits winners into live (`winner & add`)
   and tombstone (`winner & ~add`) with two packed-word ops. The device
   never materializes the live/tomb masks separately.

Padding rows (key = all-ones sentinel) sort to the end; a run that mixes
real and padding rows is won by its last *valid* row via the
`is_last | ~next_valid` mask, so no `valid` lane ships.

Complexity O(n log n) versus the hash maps' O(n) — but as one fused XLA
sort at HBM bandwidth versus pointer-chasing JVM maps, and it shards
cleanly: route rows by key to devices, sort/reduce locally, no
cross-device dedup needed (delta_tpu.parallel).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from delta_tpu import obs

_PAD_KEY = np.uint32(0xFFFFFFFF)
_MIN_BUCKET = 1024

# Bytes of replay operands shipped host->device. The residency tests
# and the bench artifact read this to prove incremental updates ship
# only delta rows (never the 10M-row base state).
_H2D_BYTES = obs.counter("replay.h2d_bytes")


_FINE_PAD_START = 1 << 20  # above this, pad linearly instead of to pow2
_FINE_PAD_STEP = 1 << 19


def pad_bucket(n: int, min_bucket: int = _MIN_BUCKET) -> int:
    """Round up to a shape bucket so jit caches a bounded number of
    shapes across snapshot sizes: next power of two up to 1M rows, then
    the next multiple of 512k. Pure pow2 padding wastes up to ~2× in
    transfer bytes and sort rows exactly at the multi-million-row scale
    where each step costs hundreds of ms; the linear tail keeps waste
    under 5% there while still bounding distinct compiled shapes."""
    if n <= min_bucket:
        return min_bucket
    if n <= _FINE_PAD_START:
        return 1 << (int(n - 1).bit_length())
    return -(-n // _FINE_PAD_STEP) * _FINE_PAD_STEP


def chrono_ok(version: np.ndarray, order: np.ndarray) -> bool:
    """True if rows are already in chronological (version, order) order,
    in which case the row index is the chronological rank.

    Uses elementwise comparisons rather than diffs so any integer dtype
    (signed or unsigned, any width) is handled without overflow-prone
    casts or copies."""
    if version.shape[0] <= 1:
        return True
    v0, v1 = version[:-1], version[1:]
    if (v1 < v0).any():
        return False
    same = v1 == v0
    if not same.any():
        return True
    return not bool((same & (order[1:] < order[:-1])).any())


def combine_key_lanes(key_lanes: Sequence[np.ndarray]) -> Optional[np.ndarray]:
    """Mixed-radix combine of dense key-code lanes into one uint32 lane
    (reserving 0xFFFFFFFF for padding). None if the ranges don't fit.

    All arithmetic stays in uint32: every mixed-radix partial value is
    bounded by the final radix product, which is checked (in Python ints)
    to fit below the sentinel before any array math runs."""
    lanes = [np.asarray(k) for k in key_lanes]
    maxes = [int(lane.max(initial=0)) for lane in lanes]
    radix = 1
    for mx in maxes:
        radix *= mx + 1
        if radix > 0xFFFFFFFF:  # need the sentinel free: values < 0xFFFFFFFF
            return None
    if len(lanes) == 1:
        return lanes[0].astype(np.uint32, copy=False)
    combined = lanes[0].astype(np.uint32, copy=True)
    for lane, mx in zip(lanes[1:], maxes[1:]):
        combined *= np.uint32(mx + 1)
        combined += lane.astype(np.uint32, copy=False)
    return combined


def key_byte_width(max_key: int) -> int:
    """Bytes/row needed to ship keys so that the all-ones sentinel of that
    width stays reserved for padding."""
    for width in (1, 2, 3):
        if max_key < (1 << (8 * width)) - 1:
            return width
    return 4


def _pack_key_planes(key: np.ndarray, width: int, pad: int,
                     pad_byte: int = 0xFF) -> tuple[np.ndarray, ...]:
    """uint32[n] -> `width` separate contiguous uint8 planes (little-endian
    byte j of each value), padded. Planar layout: interleaved (n, width)
    u8 would force stride-`width` byte access on device, which TPUs hate."""
    b = np.ascontiguousarray(key).view(np.uint8).reshape(-1, 4)
    planes = []
    for j in range(width):
        plane = np.ascontiguousarray(b[:, j])
        if pad:
            plane = np.concatenate([plane, np.full(pad, pad_byte, np.uint8)])
        planes.append(plane)
    return tuple(planes)


def _pack_bits(mask: np.ndarray) -> np.ndarray:
    """bool[n] -> uint32[n/32] little-endian bit words (n % 32 == 0)."""
    return np.packbits(mask, bitorder="little").view(np.uint32)


def _unpack_bits(words: np.ndarray, n: int) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), bitorder="little")[:n].astype(bool)


def _unpack_bits_device(words: jax.Array) -> jax.Array:
    """uint32[m/32] -> uint32[m] of 0/1 bits (little-endian bit order)."""
    bit_pos = jnp.arange(32, dtype=jnp.uint32)
    return ((words[:, None] >> bit_pos[None, :]) & jnp.uint32(1)).reshape(-1)


def _decode_planes(planes) -> jax.Array:
    """Little-endian uint8 planes -> uint32 values."""
    key = planes[0].astype(jnp.uint32)
    for j in range(1, len(planes)):
        key = key | (planes[j].astype(jnp.uint32) << jnp.uint32(8 * j))
    return key


def _sort_winner_pack(lanes, n_real) -> jax.Array:
    """Shared tail of both kernels: sort by (key..., iota) where the
    iota is the chronological rank (callers permute first if their rows
    aren't already chronological). Marks per-run winners in sorted
    order, scatters the single winner mask back to input order, and
    bit-packs it. The iota is unique, so no extra tiebreaker lane can
    ever change a winner — in particular the add/remove bit stays home
    (the r05 regression shipped it per-row and widened the payload for
    nothing). Padding rows (idx >= n_real) sort after the real rows of
    any run they share a key with (their iota is larger), so the winner
    of a run is its last *valid* row — a real row whose key happens to
    equal the all-ones pad sentinel is never swallowed by padding."""
    m = lanes[0].shape[0]
    with jax.named_scope("replay.sort"):
        payload = jnp.arange(m, dtype=jnp.uint32)
        sorted_ = lax.sort((*lanes, payload), num_keys=len(lanes) + 1,
                           is_stable=False)
    s_lanes, s_payload = sorted_[:-1], sorted_[-1]
    with jax.named_scope("replay.winner"):
        s_idx = s_payload.astype(jnp.int32)
        s_valid = s_idx < n_real

        same_as_next = jnp.ones((m - 1,), dtype=bool)
        for k in s_lanes:
            same_as_next = same_as_next & (k[:-1] == k[1:])
        next_valid = jnp.concatenate(
            [s_valid[1:], jnp.zeros((1,), dtype=bool)])
        is_last = jnp.concatenate(
            [~same_as_next, jnp.ones((1,), dtype=bool)])
        winner = s_valid & (is_last | ~next_valid)

    with jax.named_scope("replay.pack"):
        winner_orig = jnp.zeros((m,), dtype=bool).at[s_idx].set(winner)
        bit_pos = jnp.arange(32, dtype=jnp.uint32)
        weights = jnp.uint32(1) << bit_pos
        return (winner_orig.reshape(-1, 32).astype(jnp.uint32)
                * weights).sum(axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("width",))
@obs.program("replay.single_raw")
def _winner_kernel(operands, width: int) -> jax.Array:
    """Full-key path. operands = (*key_planes[u8, m] | *key_lanes[u32, m],
    n_real[i32]) -> winner_words[u32, m/32]."""
    *key_ops, n_real = operands
    with jax.named_scope("replay.decode"):
        lanes = (_decode_planes(key_ops),) if width else tuple(key_ops)
    return _sort_winner_pack(lanes, n_real)


def _bitcast_u32(b: jax.Array) -> jax.Array:
    """u8[4k] -> u32[k] (little-endian)."""
    return jax.lax.bitcast_convert_type(b.reshape(-1, 4), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("layout",))
@obs.program("replay.single_fa")
def _winner_kernel_fa_packed(buf, layout) -> jax.Array:
    """Single-transfer variant of `_winner_kernel_fa`: every operand —
    n_real, sub_radix, flag words, ref planes, the sparse DV lane —
    rides in ONE uint8 buffer and is sliced out on device. Over a
    high-latency host<->device link, one H2D beats six.

    layout = (m, ref_width, r_pad, d_pad) — all bucket-padded statics."""
    with jax.named_scope("replay.decode"):
        key, n_real = _decode_fa_packed(buf, layout)
    return _sort_winner_pack((key,), n_real)


def _decode_fa_packed(buf, layout):
    """The packed buffer's operands sliced out and the key lane rebuilt
    from its first-appearance coding: (key[u32, m], n_real)."""
    m, ref_width, r_pad, d_pad = layout
    off = 0

    def take(nbytes):
        # delta-lint: disable=jit-impure (audited: `off` is trace-time
        # python-int bookkeeping — each take() slices at a static offset
        # baked into the jaxpr, not runtime mutation)
        nonlocal off
        s = jax.lax.slice(buf, (off,), (off + nbytes,))
        off += nbytes
        return s

    n_real = _bitcast_u32(take(4))[0].astype(jnp.int32)
    sub_radix = _bitcast_u32(take(4))[0]
    flag_words = _bitcast_u32(take(m // 32 * 4))
    ref_planes = tuple(take(r_pad) for _ in range(ref_width))
    has_sub = d_pad > 0
    if has_sub:
        sub_idx = _bitcast_u32(take(d_pad * 4))
        sub_val = _bitcast_u32(take(d_pad * 4))

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
    return jnp.where(iota < n_real, key, jnp.uint32(0xFFFFFFFF)), n_real


def _pack_fa_operands(fa: "_FAEncoding", n: int) -> tuple[np.ndarray, tuple]:
    """Concatenate the FA operands into one uint8 buffer + its static
    layout key."""
    m = fa.flag_words.shape[0] * 32
    r_pad = fa.ref_planes[0].shape[0] if fa.ref_planes else 0
    d_pad = fa.sub_idx.shape[0]
    parts = [
        np.asarray([n], np.uint32).view(np.uint8),
        np.asarray([fa.sub_radix], np.uint32).view(np.uint8),
        fa.flag_words.view(np.uint8),
        *fa.ref_planes,
    ]
    if d_pad:
        parts += [fa.sub_idx.view(np.uint8), fa.sub_val.view(np.uint8)]
    return parts, (m, len(fa.ref_planes), r_pad, d_pad)


@functools.lru_cache(maxsize=16)
def _concat_chunks_jit(k: int):
    return jax.jit(obs.program("replay.single_fa.concat")(
        lambda *chunks: jnp.concatenate(chunks)))


def _put_chunked(buf: np.ndarray, device):
    """device_put that rides the fast H2D bandwidth bucket: the link
    model (parallel/gate.py) says large transfers collapse to ~29 MB/s
    while <=8 MB chunks sustain ~1 GB/s, so a buffer bigger than the
    fast-bucket size ships as fixed-size chunks and is reassembled by a
    jit'd concatenate. The trailing zero-pad past `buf.nbytes` is never
    read — the packed kernel slices at static offsets that end at the
    real layout length. Disabled (plain device_put) when the model has
    no bandwidth cliff (CPU backends) or the buffer already fits one
    chunk."""
    from delta_tpu.parallel import gate

    chunk = gate.link_model().chunk_bytes()
    if not chunk or buf.nbytes <= chunk:
        return jax.device_put(buf, device)
    k = -(-buf.nbytes // chunk)
    padded = np.zeros(k * chunk, np.uint8)
    padded[:buf.nbytes] = buf
    pieces = [jax.device_put(padded[i * chunk:(i + 1) * chunk], device)
              for i in range(k)]
    return _concat_chunks_jit(k)(*pieces)


class _FAEncoding(NamedTuple):
    """Host-side first-appearance delta encoding of the key lanes."""
    flag_words: np.ndarray     # u32[m/32] is_new bits
    ref_planes: tuple          # u8 planes of explicit codes, bucket-padded
    sub_idx: np.ndarray        # u32[D] rows with non-zero sub lane
    sub_val: np.ndarray        # u32[D]
    sub_radix: int
    nbytes: int


def derive_fa_flags(primary: np.ndarray):
    """is_new flags if `primary` is a dense first-appearance coding
    (every new value == prev_max + 1, new values are 0,1,2,...), else
    None. The single source of truth for FA validity — the single-chip
    encoder and the sharded route both use it."""
    p64 = np.asarray(primary).astype(np.int64, copy=False)
    if len(p64) == 0:
        return np.zeros(0, dtype=bool)
    run_max = np.maximum.accumulate(p64)
    prev_max = np.empty_like(run_max)
    prev_max[0] = -1
    prev_max[1:] = run_max[:-1]
    is_new = p64 == prev_max + 1
    n_new = int(is_new.sum())
    # dense first-appearance check: the j-th new row must carry code j
    if not np.array_equal(p64[is_new], np.arange(n_new, dtype=np.int64)):
        return None
    return is_new


_NATIVE_FA_MIN_ROWS = 200_000    # below this numpy encodes in ~ms anyway
_NATIVE_FA_COMPILE_ROWS = 1_000_000  # worth a one-off g++ build


def _try_fa_encode(lanes: Sequence[np.ndarray], n: int, m: int) -> Optional[_FAEncoding]:
    """Delta-encode lane 0 against first-appearance coding; lanes[1:]
    (tiny ranges, mostly zero — the DV id lane) go sparse. None when the
    stream isn't first-appearance-coded or ranges don't fit.

    Large inputs go through the multithreaded C++ encoder
    (native/src/fa_encode.cpp, same output layout); this numpy
    implementation is the toolchain-less fallback and parity oracle."""
    primary = np.asarray(lanes[0])
    sl = _sub_lane(lanes)
    if sl is None:
        return None
    sub, sub_radix = sl

    if n >= _NATIVE_FA_MIN_ROWS:
        from delta_tpu import native

        enc = native.fa_encode(
            primary, sub, n, m,
            allow_compile=n >= _NATIVE_FA_COMPILE_ROWS)
        if enc is native.NOT_FA:
            return None  # definitive: ship byte planes instead
        if enc is not None:
            full_width = key_byte_width(
                (enc.primary_max + 1) * enc.sub_radix - 1)
            if enc.nbytes >= m * full_width:
                return None  # byte planes ship fewer bytes
            return _FAEncoding(enc.flag_words, enc.ref_planes, enc.sub_idx,
                               enc.sub_val, enc.sub_radix, enc.nbytes)
        # fall through to numpy: toolchain/library unavailable
    is_new = derive_fa_flags(primary)
    if is_new is None:
        return None
    primary_max = int(primary.max()) if n else 0
    refs = primary[~is_new].astype(np.uint32, copy=False)
    return _fa_pack(is_new, refs, primary_max, sub, sub_radix, n, m)


def _sub_lane(lanes: Sequence[np.ndarray]):
    """Combine lanes[1:] into one sub lane. Returns (sub-or-None,
    sub_radix) or None when the ranges don't fit uint32."""
    if len(lanes) <= 1:
        return None, 1
    sub = combine_key_lanes(lanes[1:])
    if sub is None:
        return None
    sub_radix = int(sub.max(initial=0)) + 1
    return (sub if sub_radix > 1 else None), sub_radix


def _fa_pack(
    flags: np.ndarray,
    refs: np.ndarray,
    primary_max: int,
    sub: Optional[np.ndarray],
    sub_radix: int,
    n: int,
    m: int,
) -> Optional[_FAEncoding]:
    """Shared wire-format tail of every first-appearance encoding path:
    pack the is_new flags into bit words, the explicit refs into byte
    planes, the sub lane into sparse (row, value) pairs, and apply the
    economics check (None when plain byte planes would ship fewer
    bytes — remove-heavy streams)."""
    if (primary_max + 1) * sub_radix >= 0xFFFFFFFF:
        return None
    refs = np.ascontiguousarray(refs, dtype=np.uint32)
    r_pad = pad_bucket(len(refs), min_bucket=128)
    ref_width = key_byte_width(int(refs.max(initial=0)))
    ref_planes = _pack_key_planes(refs, ref_width, r_pad - len(refs),
                                  pad_byte=0)
    if sub is not None:
        nz = np.nonzero(sub)[0]
        d_pad = pad_bucket(len(nz), min_bucket=128)
        sub_idx = np.concatenate(
            [nz.astype(np.uint32),
             np.full(d_pad - len(nz), 0xFFFFFFFF, np.uint32)])
        sub_val = np.concatenate(
            [sub[nz].astype(np.uint32), np.zeros(d_pad - len(nz), np.uint32)])
    else:
        sub_idx = np.empty(0, np.uint32)
        sub_val = np.empty(0, np.uint32)

    pad = m - n
    flags = np.asarray(flags, dtype=np.bool_)
    flag_words = _pack_bits(
        np.concatenate([flags, np.zeros(pad, np.bool_)]) if pad else flags)
    nbytes = (flag_words.nbytes + sum(p.nbytes for p in ref_planes)
              + sub_idx.nbytes + sub_val.nbytes)
    full_width = key_byte_width((primary_max + 1) * sub_radix - 1)
    if nbytes >= m * full_width:
        return None
    return _FAEncoding(flag_words, ref_planes, sub_idx, sub_val,
                       sub_radix, nbytes)


def _fa_from_hint(
    flags: np.ndarray,
    refs: np.ndarray,
    n_uniq: int,
    lanes: Sequence[np.ndarray],
    n: int,
    m: int,
) -> Optional[_FAEncoding]:
    """Build the device encoding from a scanner-provided first-appearance
    coding (flags = is_new per row, refs = explicit codes of non-new rows
    in row order) — the host never re-derives what the dictionary pass
    already knew."""
    sl = _sub_lane(lanes)
    if sl is None:
        return None
    sub, sub_radix = sl
    return _fa_pack(flags, refs, n_uniq - 1 if n_uniq else 0,
                    sub, sub_radix, n, m)


class ReplayPending:
    """A launched (asynchronously dispatched) replay: the device owns the
    sort while the host keeps working — call `finish()` to block on the
    winner words and split them into (live, tombstone) masks."""

    __slots__ = ("_winner", "_add_words", "_n", "_perm", "_dispatch")

    def __init__(self, winner, add_words: np.ndarray, n: int, perm,
                 dispatch=None):
        self._winner = winner
        self._add_words = add_words
        self._n = n
        self._perm = perm
        # the launch's (closed) dispatch: the blocking read joins its
        # record, which otherwise holds the launch and not the kernel
        # (None only for the empty replay, which never waits)
        self._dispatch = dispatch

    def finish(self) -> tuple[np.ndarray, np.ndarray]:
        n = self._n
        if n == 0:
            z = np.zeros((0,), dtype=bool)
            return z, z
        with obs.span("replay.wait", rows=n) as sp, self._dispatch.wait():
            winner_words = np.asarray(self._winner)
            sp.set_attr("bytes", winner_words.nbytes)
        with obs.span("replay.unpack", rows=n):
            live_words = winner_words & self._add_words
            tomb_words = winner_words & ~self._add_words
            live = _unpack_bits(live_words, n)
            tomb = _unpack_bits(tomb_words, n)
            if self._perm is not None:
                inv_live = np.zeros(n, dtype=bool)
                inv_tomb = np.zeros(n, dtype=bool)
                inv_live[self._perm] = live
                inv_tomb[self._perm] = tomb
                live, tomb = inv_live, inv_tomb
        return live, tomb


def replay_select(
    key_lanes: Sequence[np.ndarray],
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    device=None,
    fa_hint: Optional[tuple] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-facing wrapper: permutes to chronological order if needed,
    delta- or byte-packs the key lanes (whichever ships fewer bytes),
    runs the winner kernel on device, and splits winners into
    (live_mask, tombstone_mask) numpy bool arrays of the original length
    using the host-resident add bits.

    key_lanes: one or more uint32/int32 arrays jointly identifying the
    logical file (dictionary codes or hash lanes). version/order: the
    chronological coordinate of each row; when rows are already in
    chronological order (the columnarizer's contract) they never leave
    the host.
    """
    return replay_select_launch(
        key_lanes, version, order, is_add, device=device,
        fa_hint=fa_hint).finish()


def replay_select_launch(
    key_lanes: Sequence[np.ndarray],
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    device=None,
    fa_hint: Optional[tuple] = None,
) -> ReplayPending:
    """Asynchronous half of `replay_select`: packs + ships the operands
    and dispatches the device kernel, returning immediately (jax calls
    are async). The caller overlaps host work (e.g. Arrow table
    assembly) with the device sort and calls `.finish()` when it needs
    the masks."""
    n = int(version.shape[0])
    if n == 0:
        return ReplayPending(None, np.empty(0, np.uint32), 0, None)

    with obs.span("replay.pack", rows=n) as sp:
        perm = None
        if not chrono_ok(np.asarray(version), np.asarray(order)):
            perm = np.lexsort((order, version))
            key_lanes = [np.asarray(k)[perm] for k in key_lanes]
            is_add = np.asarray(is_add)[perm]
            fa_hint = None  # hint flags are in original row order

        m = pad_bucket(n)
        pad = m - n
        is_add = np.asarray(is_add, dtype=np.bool_)
        add_words_np = _pack_bits(
            np.concatenate([is_add, np.zeros(pad, np.bool_)])
            if pad else is_add)

        lanes = [np.asarray(k) for k in key_lanes]
        fa = None
        if fa_hint is not None:
            flags, refs, n_uniq = fa_hint
            fa = _fa_from_hint(flags, refs, int(n_uniq), lanes, n, m)
        if fa is None:
            fa = _try_fa_encode(lanes, n, m)

        if fa is not None:
            parts, layout = _pack_fa_operands(fa, n)
            buf = np.concatenate(parts)
            nbytes = buf.nbytes
        else:
            combined = combine_key_lanes(lanes)
            if combined is not None:
                width = key_byte_width(int(combined.max(initial=0)))
                key_ops = _pack_key_planes(combined, width, pad)
            else:
                width = 0
                key_ops = tuple(
                    np.ascontiguousarray(np.concatenate(
                        [np.asarray(k, np.uint32),
                         np.full(pad, _PAD_KEY, np.uint32)])
                        if pad else np.asarray(k, np.uint32))
                    for k in lanes)
            nbytes = sum(int(o.nbytes) for o in key_ops)
        sp.set_attrs(bytes=nbytes, encoding="fa" if fa is not None else "raw")

    # these data-dependent lanes are accounted at runtime through
    # replay.h2d_bytes (no static per-unit budget entry — the FA buffer
    # mixes bitplanes and byte-packed refs); the funnel still records
    # per-lane bytes and the compile/steady-state split per shape bucket
    with obs.span("replay.launch", rows=n, bytes=nbytes):
        if fa is not None:
            with obs.device_dispatch("replay.single_fa", key=(m, layout),
                                     gate="replay", route="single") as dd:
                dd.h2d("fa_buf", buf)
                _H2D_BYTES.inc(nbytes)
                buf = _put_chunked(buf, device)
                winner_words = _winner_kernel_fa_packed(buf, layout)
        else:
            operands = (*key_ops, np.asarray(n, dtype=np.int32))
            with obs.device_dispatch("replay.single_raw",
                                     key=(m, width, len(key_ops)),
                                     gate="replay", route="single") as dd:
                for i, o in enumerate(key_ops):
                    dd.h2d(f"key_plane_{i}", o)
                _H2D_BYTES.inc(nbytes)
                if device is not None:
                    operands = tuple(jax.device_put(o, device)
                                     for o in operands)
                winner_words = _winner_kernel(operands, width=width)

    return ReplayPending(winner_words, add_words_np, n, perm, dispatch=dd)


def python_replay_reference(
    keys: Sequence[tuple],
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """Sequential hash-map replay — the reference semantics
    (`InMemoryLogReplay.scala:52-100`) — used for parity tests and as the
    honest CPU baseline in benchmarks."""
    n = len(keys)
    rows = sorted(range(n), key=lambda i: (int(version[i]), int(order[i])))
    winner: dict = {}
    for i in rows:
        winner[keys[i]] = i
    live = np.zeros(n, dtype=bool)
    tomb = np.zeros(n, dtype=bool)
    for key, i in winner.items():
        if is_add[i]:
            live[i] = True
        else:
            tomb[i] = True
    return live, tomb


def delta_winner_masks(
    keys: Sequence[tuple],
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Last-wins masks over a DELTA batch of actions (the commits an
    incremental `update()` appends on top of a retained snapshot).

    Same contract as python_replay_reference, plus the winner map
    `{key: row}` — the caller uses its key set to clear superseded rows
    in the prior state's masks. Delta batches are O(new commits), so the
    sequential formulation is the right tool here; the device kernels
    above exist for the O(full history) replay.
    """
    n = len(keys)
    rows = sorted(range(n), key=lambda i: (int(version[i]), int(order[i])))
    winner: dict = {}
    for i in rows:
        winner[keys[i]] = i
    live = np.zeros(n, dtype=bool)
    tomb = np.zeros(n, dtype=bool)
    for i in winner.values():
        if is_add[i]:
            live[i] = True
        else:
            tomb[i] = True
    return live, tomb, winner
