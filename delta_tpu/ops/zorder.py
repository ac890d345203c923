"""Space-filling-curve clustering keys (OPTIMIZE ZORDER BY / Hilbert).

The reference computes Z-order keys with a per-row JVM bit-interleave UDF
(`expressions/InterleaveBits.scala:40`) and Hilbert indexes via a
state-machine table (`HilbertIndex.java` / `HilbertStates.java`). Here
both are branch-free vectorized bit manipulation over whole columns —
XLA fuses the (static) bit loops into a handful of VPU passes, and rows
never leave the device between ranking, curve-key computation, and the
range-partition sort.

Pipeline (`MultiDimClustering.scala:41-69` semantics):
1. `range_rank` — each clustering column → dense uint32 rank: a row's
   place in the stable ascending sort of the column, so equal values
   rank by position. (Upstream ranks by `RangePartitionId` over sampled
   ranges: equal values share an id there, and rows inside an output
   file are in no stated order; here the ranks are unique and the order
   along the curve is total.)
2. `interleave_bits` (Z-order) or `hilbert_key` (Hilbert, Skilling's
   public-domain transform) — [k] rank columns → [k] uint32 key words,
   most-significant word first.
3. `curve_order` — lexicographic argsort of the key words; OPTIMIZE
   writes files by slicing that order into target-size ranges.

`_curve_perm` is the three fused into one program whose every sort is
ONE two-operand `lax.sort` in one loop (see there): what the v5e
compiler and the chip are both quick over. Nothing in `delta_tpu/` calls
`range_rank` or `curve_order` since: they stay as the plain statement
of steps 1 and 3, which `tests/test_zorder.py` and
`tests/test_optimize_zorder_reference.py` hold `_curve_perm` to bit for
bit.
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
    """Dense rank in [0, n) as uint32: a row's place in the stable
    ascending sort, so ties rank by position. The reference form: the
    command's path ranks inside `_curve_perm`, which the tests hold to
    this."""
    n = values.shape[0]
    order = jnp.argsort(values)
    ranks = jnp.zeros((n,), dtype=jnp.uint32).at[order].set(
        jnp.arange(n, dtype=jnp.uint32)
    )
    return ranks


def _rank_shift(n: int, n_bits: int) -> int:
    """How far ranks under `n` are shifted up to fill `n_bits`."""
    return max(0, n_bits - max(1, (n - 1).bit_length()))


def _scale_ranks(ranks: jnp.ndarray, n: int, n_bits: int) -> jnp.ndarray:
    """Spread ranks over the full n_bits key space so interleaving uses
    high bits first."""
    return (ranks << np.uint32(_rank_shift(n, n_bits))).astype(jnp.uint32)


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
    Returns int32 permutation. The reference form: the command's path
    orders inside `_curve_perm`, which the tests hold to this."""
    k, n = key_words.shape
    idx = jnp.arange(n, dtype=jnp.int32)
    operands = tuple(key_words[i] for i in range(k)) + (idx,)
    out = lax.sort(operands, num_keys=k)
    return out[-1]


def _curve_keys(ranks: jnp.ndarray, m: int, curve: str) -> jnp.ndarray:
    """[k, m] uint32 ranks -> the curve's key words [w, m], word 0 most
    significant."""
    if curve == "hilbert":
        n_bits = 16
        scaled = [_scale_ranks(r, m, 32) >> jnp.uint32(32 - n_bits)
                  for r in ranks]
        return hilbert_key(scaled, n_bits=n_bits)
    from delta_tpu.ops.pallas_kernels import interleave_bits_auto

    # m is always a tile multiple (pad_bucket), so this is the
    # Pallas VMEM-tile kernel on TPU (jnp fallback elsewhere)
    return interleave_bits_auto([_scale_ranks(r, m, 32) for r in ranks],
                                n_bits=32)


@functools.partial(jax.jit, static_argnames=("curve",))
@obs.program("zorder.curve_perm")
def _curve_perm(stacked: jnp.ndarray, curve: str) -> jnp.ndarray:
    """One fused device program: rank -> scale -> curve key -> argsort.
    `stacked` is the [n_cols, m] uint32 key matrix — all clustering
    columns ride ONE transfer and one dispatch; the column count and
    the (bucket-padded) row count are static shapes. Padding rows carry
    the all-ones sentinel, rank at the top, and sort to the end of the
    curve (the host drops them from the permutation).

    The permutation is bit for bit what `range_rank` a column,
    `interleave_bits` / `hilbert_key` and `curve_order` give. The form
    is the one both the v5e compiler and the chip are quick over at
    three columns of 5.2M rows (PERF.md, PR 55): as three stable
    `argsort`s with their scatters (each of which the compiler lowers
    to a two-operand sort of its own) and one stable four-operand sort,
    seven sorts, it compiled for 76 s on the chip's host and ran in
    0.15 s; on single-operand `uint32` radix passes (`ops/sqlops.py::
    _radix_perm`'s form: a digit above the row's place, two gathers a
    pass) it compiled in 10 s and ran for 3.75 s, a gather of 5.2M rows
    costing the chip 37 ms and a batched one 63 ms a lane. Here every
    sort of the program is ONE `lax.sort((key, place), num_keys=2)` in
    ONE loop, so the compiler builds one sort; the place as second key
    makes every pass total, so none needs stability:

    - a column's ranks are two steps with no gather: the lane sorted
      with the places gives the column's order, and that order sorted
      with the places gives its inverse, which is the ranks;
    - then the key words are made once (the interleave), and the order
      along the curve is a radix sort by whole 32-bit words, least
      significant first, a step a word that holds key bits (the ranks of
      fewer than 2**32 rows leave the key's low bits zero: a word of
      nothing but those is skipped): `word[perm]`, sort, `perm[order]`.
    """
    k, m = stacked.shape
    place = jnp.arange(m, dtype=jnp.int32)
    n_words = jax.eval_shape(
        lambda r: _curve_keys(r, m, curve), stacked).shape[0]
    key_bits = k * (16 if curve == "hilbert" else
                    32 - _rank_shift(m, 32))
    rank_steps = 2 * k
    order_steps = -(-key_bits // 32)    # the words that hold key bits

    def step(i, state):
        ranks, words, perm = state
        ordering = i >= rank_steps
        # the ranks are whole: the key words, once
        words = jax.lax.cond(i == rank_steps,
                             lambda: _curve_keys(ranks, m, curve),
                             lambda: words)

        def rank_key():
            lane = jax.lax.dynamic_index_in_dim(stacked, i // 2, 0,
                                                keepdims=False)
            return jnp.where(i % 2 == 0, lane, perm.astype(jnp.uint32))

        def order_key():
            word = jax.lax.dynamic_index_in_dim(
                words, order_steps - 1 - (i - rank_steps), 0, keepdims=False)
            return jax.lax.cond(i == rank_steps, lambda: word,
                                lambda: word[perm])

        key = jax.lax.cond(ordering, order_key, rank_key)
        order = jax.lax.sort((key, place), num_keys=2, is_stable=False)[1]
        # an odd rank step's order is the inverse of the column's: its ranks
        ranks = jax.lax.cond(
            jnp.logical_and(~ordering, i % 2 == 1),
            lambda: jax.lax.dynamic_update_index_in_dim(
                ranks, order.astype(jnp.uint32), i // 2, 0),
            lambda: ranks)
        perm = jax.lax.cond(i > rank_steps, lambda: perm[order],
                            lambda: order)
        return ranks, words, perm

    _, _, perm = jax.lax.fori_loop(
        0, rank_steps + order_steps, step,
        (jnp.zeros((k, m), jnp.uint32), jnp.zeros((n_words, m), jnp.uint32),
         place))
    return perm


def zorder_sort_indices(cols: Sequence[np.ndarray], curve: str = "zorder") -> np.ndarray:
    """Host entry: rank columns, build curve keys, return the row
    permutation that clusters rows along the curve (`curve_keys`, then
    `curve_perm`)."""
    n = len(cols[0])
    if n == 0:
        return np.empty(0, dtype=np.int32)
    return curve_perm(curve_keys(cols), n, curve)


def curve_keys(cols: Sequence[np.ndarray]) -> np.ndarray:
    """The clustering columns as ONE [n_cols, m] uint32 host matrix of
    order-preserving keys, so that all of them cross the link in a
    single transfer instead of one round trip per column.

    Rows are padded to a shape bucket (`ops.replay.pad_bucket`) so
    OPTIMIZE over many different bin sizes compiles a handful of
    programs instead of one per size."""
    from delta_tpu.ops.replay import pad_bucket

    n = len(cols[0])
    m = pad_bucket(n, min_bucket=1024)
    # all-ones padding ranks above (or tied with, and then behind by
    # position) every real value, so padding rows change no real row's
    # rank and sort to the end of the curve
    stacked = np.full((len(cols), m), 0xFFFFFFFF, np.uint32)
    for i, c in enumerate(cols):
        stacked[i, :n] = _to_sortable_u32(c)
    return stacked


def curve_perm(stacked: np.ndarray, n: int, curve: str = "zorder") -> np.ndarray:
    """The permutation of the first `n` rows of `curve_keys`' matrix
    along the curve: the whole pipeline runs as a single jit (one
    dispatch, fully fused) rather than eager per-op round-trips."""
    k, m = stacked.shape
    # stacked rides as a jit argument (no device_put lane to budget)
    with obs.device_dispatch("zorder.curve_perm", key=(k, m, curve)) as dd:
        dd.set(columns=k, n_pad=m, rows=n)
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
