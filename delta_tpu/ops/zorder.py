"""Space-filling-curve clustering keys (OPTIMIZE ZORDER BY / Hilbert).

The reference computes Z-order keys with a per-row JVM bit-interleave UDF
(`expressions/InterleaveBits.scala:40`) and Hilbert indexes via a
state-machine table (`HilbertIndex.java` / `HilbertStates.java`). Here
both are branch-free vectorized bit manipulation over whole columns —
XLA fuses the (static) bit loops into a handful of VPU passes, and rows
never leave the device between ranking, curve-key computation, and the
range-partition sort.

Pipeline (`MultiDimClustering.scala:41-69` semantics):
1. `range_rank` — each clustering column → dense uint32 rank (the exact
   equivalent of RangePartitionId's sampled ranges).
2. `interleave_bits` (Z-order) or `hilbert_key` (Hilbert, Skilling's
   public-domain transform) — [k] rank columns → [k] uint32 key words,
   most-significant word first.
3. `curve_order` — lexicographic argsort of the key words; OPTIMIZE
   writes files by slicing that order into target-size ranges.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from delta_tpu import obs


def range_rank(values: jnp.ndarray) -> jnp.ndarray:
    """Dense rank in [0, n) as uint32 (ties broken arbitrarily but
    consistently — fine for clustering)."""
    n = values.shape[0]
    order = jnp.argsort(values)
    ranks = jnp.zeros((n,), dtype=jnp.uint32).at[order].set(
        jnp.arange(n, dtype=jnp.uint32)
    )
    return ranks


def _scale_ranks(ranks: jnp.ndarray, n: int, n_bits: int) -> jnp.ndarray:
    """Spread ranks over the full n_bits key space so interleaving uses
    high bits first."""
    shift = max(0, n_bits - max(1, (n - 1).bit_length()))
    return (ranks << np.uint32(shift)).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def interleave_bits(cols: Sequence[jnp.ndarray], n_bits: int = 32) -> jnp.ndarray:
    """Round-robin bit interleave of k uint32 columns.

    Returns [k, n] uint32 words, word 0 most significant — sorting rows by
    (word0, word1, ...) sorts by the Z-order curve. Matches the reference's
    MSB-first round-robin layout (`InterleaveBits.scala:40`).
    """
    k = len(cols)
    n = cols[0].shape[0]
    total_bits = k * n_bits
    n_words = max(1, -(-total_bits // 32))
    words = [jnp.zeros((n,), dtype=jnp.uint32) for _ in range(n_words)]
    for g in range(total_bits):
        c = g % k              # source column (round-robin)
        s = n_bits - 1 - g // k  # source bit, MSB first
        w, wb = divmod(g, 32)
        bit = (cols[c] >> jnp.uint32(s)) & jnp.uint32(1)
        words[w] = words[w] | (bit << jnp.uint32(31 - wb))
    return jnp.stack(words)


@functools.partial(jax.jit, static_argnames=("n_bits",))
def hilbert_transpose(cols: Sequence[jnp.ndarray], n_bits: int = 16) -> list:
    """Skilling's inverse transform: coordinates → 'transposed' Hilbert
    form (public-domain algorithm, Skilling 2004). All ops are elementwise
    selects over the columns; the bit loop is static."""
    d = len(cols)
    X = [c.astype(jnp.uint32) for c in cols]
    M = jnp.uint32(1 << (n_bits - 1))

    # Inverse undo excess work
    Q = 1 << (n_bits - 1)
    while Q > 1:
        Qc = jnp.uint32(Q)
        P = jnp.uint32(Q - 1)
        for i in range(d):
            has = (X[i] & Qc) != 0
            # if bit set: invert low bits of X[0]; else swap low bits X[0]<->X[i]
            t = (X[0] ^ X[i]) & P
            X0_if = X[0] ^ P
            X0_else = X[0] ^ t
            Xi_else = X[i] ^ t
            X[0] = jnp.where(has, X0_if, X0_else)
            if i != 0:
                X[i] = jnp.where(has, X[i], Xi_else)
        Q >>= 1

    # Gray encode
    for i in range(1, d):
        X[i] = X[i] ^ X[i - 1]
    t = jnp.zeros_like(X[0])
    Q = 1 << (n_bits - 1)
    while Q > 1:
        Qc = jnp.uint32(Q)
        t = jnp.where((X[d - 1] & Qc) != 0, t ^ jnp.uint32(Q - 1), t)
        Q >>= 1
    for i in range(d):
        X[i] = X[i] ^ t
    return X


def hilbert_key(cols: Sequence[jnp.ndarray], n_bits: int = 16) -> jnp.ndarray:
    """Coordinates → sortable Hilbert key words [ceil(k*n_bits/32), n].

    The Hilbert integer is the bit-interleave of the transposed form
    (axis 0 contributes the most significant bit of each group)."""
    X = hilbert_transpose(cols, n_bits=n_bits)
    return interleave_bits(X, n_bits=n_bits)


def curve_order(key_words: jnp.ndarray) -> jnp.ndarray:
    """Row order along the curve: lexicographic argsort of the key words.
    Returns int32 permutation."""
    k, n = key_words.shape
    idx = jnp.arange(n, dtype=jnp.int32)
    operands = tuple(key_words[i] for i in range(k)) + (idx,)
    out = lax.sort(operands, num_keys=k)
    return out[-1]


@functools.partial(jax.jit, static_argnames=("curve",))
@obs.program("zorder.curve_perm")
def _curve_perm(stacked: jnp.ndarray, curve: str) -> jnp.ndarray:
    """One fused device program: rank -> scale -> curve key -> argsort.
    `stacked` is the [n_cols, m] uint32 key matrix — all clustering
    columns ride ONE transfer and one dispatch; the column count and
    the (bucket-padded) row count are static shapes. Padding rows carry
    the all-ones sentinel, rank at the top, and sort to the end of the
    curve (the host drops them from the permutation)."""
    m = stacked.shape[1]
    cols = tuple(stacked[i] for i in range(stacked.shape[0]))
    ranks = [range_rank(c) for c in cols]
    if curve == "hilbert":
        n_bits = 16
        scaled = [
            _scale_ranks(r, m, 32) >> jnp.uint32(32 - n_bits) for r in ranks
        ]
        keys = hilbert_key(scaled, n_bits=n_bits)
    else:
        from delta_tpu.ops.pallas_kernels import interleave_bits_auto

        scaled = [_scale_ranks(r, m, 32) for r in ranks]
        # m is always a tile multiple (pad_bucket), so this is the
        # Pallas VMEM-tile kernel on TPU (jnp fallback elsewhere)
        keys = interleave_bits_auto(scaled, n_bits=32)
    return curve_order(keys)


def zorder_sort_indices(cols: Sequence[np.ndarray], curve: str = "zorder") -> np.ndarray:
    """Host entry: rank columns, build curve keys, return the row
    permutation that clusters rows along the curve.

    Rows are padded to a shape bucket (`ops.replay.pad_bucket`) so
    OPTIMIZE over many different bin sizes compiles a handful of
    programs instead of one per size, and the whole pipeline runs as a
    single jit (one dispatch, fully fused) rather than eager per-op
    round-trips. The per-column u32 keys are stacked into one host
    matrix first, so ALL clustering columns cross the link in a single
    transfer instead of one round trip per column."""
    n = len(cols[0])
    if n == 0:
        return np.empty(0, dtype=np.int32)
    from delta_tpu.ops.replay import pad_bucket

    m = pad_bucket(n, min_bucket=1024)
    # all-ones padding ranks above (or tied with) every real value, so
    # padding rows sort to the end of the curve
    stacked = np.full((len(cols), m), 0xFFFFFFFF, np.uint32)
    for i, c in enumerate(cols):
        stacked[i, :n] = _to_sortable_u32(c)
    # stacked rides as a jit argument (no device_put lane to budget)
    with obs.device_dispatch("zorder.curve_perm",
                             key=(len(cols), m, curve)) as dd:
        dd.h2d("stacked", stacked)
        perm = dd.d2h("perm",
                      np.asarray(_curve_perm(jnp.asarray(stacked), curve)))
    if m > n:
        perm = perm[perm < n]
    return perm


def _to_sortable_u32(col: np.ndarray) -> np.ndarray:
    """Map a numpy column to uint32 preserving order (for ranking)."""
    c = np.asarray(col)
    if c.dtype.kind == "f":
        # IEEE-754 total order trick
        bits = c.astype(np.float32).view(np.uint32)
        mask = np.where(bits >> 31 == 1, np.uint32(0xFFFFFFFF), np.uint32(0x80000000))
        return bits ^ mask
    if c.dtype.kind in ("i",):
        c64 = c.astype(np.int64)
        lo, hi = int(c64.min()), int(c64.max())
        if hi - lo < 2**32:
            return (c64 - lo).astype(np.uint32)
        # wide int64 range: dense host rank preserves order exactly
        order = np.argsort(c64, kind="stable")
        ranks = np.empty(len(c64), dtype=np.uint32)
        ranks[order] = np.arange(len(c64), dtype=np.uint32)
        return ranks
    if c.dtype.kind in ("u", "b"):
        return c.astype(np.uint32)
    if c.dtype.kind in ("U", "S", "O"):
        # strings: rank via numpy argsort on the host (exact order)
        order = np.argsort(c, kind="stable")
        ranks = np.empty(len(c), dtype=np.uint32)
        ranks[order] = np.arange(len(c), dtype=np.uint32)
        return ranks
    if np.issubdtype(c.dtype, np.datetime64):
        return _to_sortable_u32(c.astype("datetime64[us]").astype(np.int64) // 1000)
    raise ValueError(f"cannot build curve key from dtype {c.dtype}")
