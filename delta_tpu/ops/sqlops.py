"""Device SQL operators: the execution spine for the SQL engine.

The reference delegates query execution to Spark's distributed columnar
engine (injected at
`spark/src/main/scala/io/delta/sql/DeltaSparkSessionExtension.scala:84-173`;
scans planned via
`spark/src/main/scala/org/apache/spark/sql/delta/stats/PrepareDeltaScan.scala:308`).
This module is the TPU-native replacement for the three relational
operators that dominate that substrate's work on TPC-DS: equi-join,
GROUP BY aggregation, and (window) sort. The division of labor follows
the replay kernel's proven shape (`ops/replay.py`):

- host: dictionary-encode string/float keys to dense uint32 codes
  (pandas factorize — same as `ops/join.py::equi_join_device`) and do
  O(output) gathers/expansions;
- device: the O(n log n) sorts and O(n) segment reductions/scans
  (`jax.ops.segment_*`, `jax.lax.associative_scan`) on bucket-padded
  static shapes so jit caches a bounded number of programs across
  table sizes.

The join's and the ORDER BY's sort is `_radix_perm`: a radix sort from
the least significant digit whose every pass is ONE single-operand
`uint32` `jax.lax.sort`. A multi-operand sort of 64-bit lanes is what
the v5e compiler takes minutes over (a stable three-key int64 sort
125 s on the chip's host, PERF.md); one `uint32` operand it compiles in
seconds, whatever the rows. The order is the total order (keys, then
position), so the result is the stable multi-key sort's, bit for bit.

Aggregation dtype policy: integer columns accumulate in int64 (exact),
floats in float64 — x64 is enabled lazily on first use. The repo's other
kernels are dtype-explicit throughout, so flipping the global flag is
safe for them (verified by the full suite).

Null semantics match pandas GROUP BY (`dropna=False` on keys; null
values excluded from aggregates; all-null group sum/min/max = NULL) so
HostEngine's pandas path stays the bit-exact parity oracle.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from delta_tpu import obs
from delta_tpu.ops.replay import pad_bucket

_PAD_CODE = np.uint32(0xFFFFFFFF)
_x64_enabled = False

# Build sides at or above this many rows fan the segment-reduce
# aggregation out over the engine mesh (`shard_map` over REPLAY_AXIS),
# host-parity-gated like `ops/replay.py::compute_masks_device`. Below
# it the single-chip kernel wins: the shard routing pass costs more
# than the per-shard reduction saves.
DEFAULT_SHARDED_AGG_MIN_ROWS = 2_000_000


def sharded_agg_min_rows() -> int:
    env = os.environ.get("DELTA_TPU_SQL_SHARD_MIN_ROWS")
    if env:
        return int(env)
    return DEFAULT_SHARDED_AGG_MIN_ROWS


def _ensure_x64() -> None:
    """int64/float64 device math for exact aggregation. Lazy so
    processes that never touch the SQL spine keep the default."""
    global _x64_enabled
    if not _x64_enabled:
        jax.config.update("jax_enable_x64", True)
        _x64_enabled = True


# ------------------------------------------------------------- sort ----

_U64_TOP = np.uint64(1) << np.uint64(63)
_WORD_BITS = 63     # of a packed sort word; its top bit is the pad's


def _digit_bits(n: int) -> int:
    """Bits of key a radix pass over `n` rows sorts by: what a uint32
    word has left beside a row's position."""
    width = 32 - max(1, (n - 1).bit_length())
    if width < 1:
        raise ValueError(f"{n} rows are more than one sort can hold")
    return width


def _radix_schedule(bits: Sequence[int], n: int):
    """The passes that sort `n` rows by words of `bits[0]`, `bits[1]`,
    ... significant bits, the first word the primary: `(word_of,
    shift_of, passes)`, least significant digit first. The arrays are as
    long as words of 64 bits would need, so their shape depends on the
    number of words and `n` alone."""
    width = _digit_bits(n)
    most = -(-64 // width)
    word_of = np.zeros(most * len(bits), np.int32)
    shift_of = np.zeros(most * len(bits), np.int32)
    at = 0
    for w in range(len(bits) - 1, -1, -1):
        for shift in range(0, int(bits[w]), width):
            word_of[at], shift_of[at] = w, shift
            at += 1
    return word_of, shift_of, np.int32(at)


def _radix_perm(words, word_of, shift_of, passes):
    """int32 permutation that puts the rows of `words` (uint64 [W, n])
    in ascending order of (word 0, word 1, ..., position). Each pass
    sorts ONE uint32 lane, a digit of the key above the row's place in
    the order so far, so a pass is stable and carries no payload."""
    n = words.shape[1]
    width = _digit_bits(n)
    place_bits = 32 - width
    place = jnp.arange(n, dtype=jnp.uint32)

    def one_pass(i, perm):
        word = jax.lax.dynamic_index_in_dim(words, word_of[i], 0,
                                            keepdims=False)
        digit = ((word >> shift_of[i].astype(jnp.uint64))
                 & jnp.uint64((1 << width) - 1)).astype(jnp.uint32)
        # every word is another (its low bits are the row's place), so
        # the order is total: stability, which the v5e compiler takes
        # three times as long over, has nothing to decide
        took = jax.lax.sort((digit[perm] << place_bits) | place,
                            is_stable=False)
        return perm[(took & jnp.uint32((1 << place_bits) - 1))
                    .astype(jnp.int32)]

    return jax.lax.fori_loop(0, passes, one_pass,
                             jnp.arange(n, dtype=jnp.int32))


def _read(kernel: str, *arrays):
    """The blocking read of a launch's results on the calling thread."""
    with obs.span("sql.wait", kernel=kernel):
        return tuple(np.asarray(a) for a in arrays)


@jax.jit
@obs.program("sqlops.sort")
def _sort_kernel(words, word_of, shift_of, passes):
    return _radix_perm(words, word_of, shift_of, passes)


def _order_code(lane: np.ndarray) -> np.ndarray:
    """uint64 codes in the lane's ascending order, as `jax.lax.sort`
    compares it: integers by value, floats by value with -0.0 = 0.0
    (lanes are NaN-free)."""
    lane = np.asarray(lane)
    if lane.dtype.kind == "f":
        bits = (lane.astype(np.float64) + 0.0).view(np.uint64)
        return np.where(bits >> np.uint64(63), ~bits, bits | _U64_TOP)
    if lane.dtype.kind == "i":
        return lane.astype(np.int64).view(np.uint64) ^ _U64_TOP
    return lane.astype(np.uint64)   # unsigned and bool


def _pack_sort_words(lanes: Sequence[np.ndarray]):
    """The lanes as few uint64 words as hold them, `(words, bits a
    word)`: a lane is its order code less the least of them, so it
    takes the bits of its range, and lanes share a word while they fit
    under its top bit."""
    packed = [(np.zeros(len(lanes[0]), np.uint64), 0)]
    for lane in lanes:
        code = _order_code(lane)
        code = code - code.min()
        bits = int(code.max()).bit_length()
        word, held = packed[-1]
        if held + bits > _WORD_BITS:
            packed.append((code, bits))
        elif bits:
            packed[-1] = ((word << np.uint64(bits)) | code, held + bits)
    return [w for w, _held in packed], [held for _w, held in packed]


def sort_permutation(lanes: Sequence[np.ndarray],
                     device=None) -> np.ndarray:
    """Stable multi-key ascending sort; returns the permutation (int64
    row indices). Lanes are NaN-free numerics, primary first; callers
    encode direction (negate for DESC) and null ordering (a 0/1 null
    lane per key) before calling — the device only ever sorts
    ascending."""
    _ensure_x64()
    n = int(len(lanes[0]))
    if n == 0:
        return np.empty(0, np.int64)
    npad = pad_bucket(n)
    packed, bits = _pack_sort_words(lanes)
    words = np.zeros((len(packed), npad), np.uint64)
    words[:, :n] = packed
    words[0, n:] = np.uint64(1) << np.uint64(bits[0])   # pads sort last
    bits[0] += 1
    word_of, shift_of, passes = _radix_schedule(bits, npad)
    with obs.device_dispatch("sqlops.sort", key=(len(bits), npad),
                             budget="sql-sort-lanes", units=npad,
                             gate="sql") as dd:
        dd.set(n=n, n_pad=npad, words=len(bits), passes=int(passes))
        dd.h2d("words", words, units=len(bits) * npad)
        perm, = _read("sqlops.sort", _sort_kernel(
            jax.device_put(words, device), word_of, shift_of, passes))
    return perm[:n].astype(np.int64)


# --------------------------------------------------- group-by reduce ----

@functools.partial(jax.jit, static_argnames=("op", "n_seg"))
@obs.program("sqlops.segagg")
def _segagg_kernel(codes, v, valid, op: str, n_seg: int):
    """One aggregate over dense group codes. Returns (agg[n_seg],
    valid_count[n_seg])."""
    cnt = jax.ops.segment_sum(valid.astype(jnp.int64), codes,
                              num_segments=n_seg)
    if op == "count":
        return cnt, cnt
    if op == "sum":
        zero = jnp.zeros((), v.dtype)
        s = jax.ops.segment_sum(jnp.where(valid, v, zero), codes,
                                num_segments=n_seg)
        return s, cnt
    if v.dtype.kind == "f":
        big = jnp.array(np.inf, v.dtype)
    else:
        big = jnp.array(np.iinfo(np.int64).max, v.dtype)
    if op == "min":
        s = jax.ops.segment_min(jnp.where(valid, v, big), codes,
                                num_segments=n_seg)
    elif op == "max":
        s = jax.ops.segment_max(jnp.where(valid, v, -big), codes,
                                num_segments=n_seg)
    else:
        raise ValueError(op)
    return s, cnt


def _agg_mesh(n: int, mesh=None):
    """Resolve the mesh for the sharded segment-reduce fan-out; None
    keeps the single-chip kernel (input below the row threshold, a
    1-device mesh, or no usable mesh at all)."""
    if n < sharded_agg_min_rows():
        return None
    if mesh is None:
        try:
            from delta_tpu.parallel.mesh import make_mesh

            mesh = make_mesh()
        except (ImportError, RuntimeError, ValueError):
            return None
    if mesh is None or mesh.devices.size <= 1:
        return None
    return mesh


@functools.lru_cache(maxsize=16)
def _sharded_segagg_fn(mesh, op: str, n_seg: int):
    """Mesh-sharded segment reduce: each shard reduces its row block
    into a full [n_seg] partial, combined with one cross-shard
    psum/pmin/pmax. Per-segment results are identical to the
    single-chip kernel for int64 accumulation (the parity gate in
    tests/test_sql_operand_cache.py pins this); float64 sums may
    differ in the last ulp from the reassociated addition order."""
    from jax.sharding import PartitionSpec as P

    from delta_tpu.parallel.mesh import REPLAY_AXIS
    from delta_tpu.parallel.sharded_replay import shard_map

    def kernel(codes, v, valid):
        cnt = jax.ops.segment_sum(valid.astype(jnp.int64), codes,
                                  num_segments=n_seg)
        cnt = jax.lax.psum(cnt, REPLAY_AXIS)
        if op == "count":
            return cnt, cnt
        if op == "sum":
            zero = jnp.zeros((), v.dtype)
            s = jax.ops.segment_sum(jnp.where(valid, v, zero), codes,
                                    num_segments=n_seg)
            return jax.lax.psum(s, REPLAY_AXIS), cnt
        if v.dtype.kind == "f":
            big = jnp.array(np.inf, v.dtype)
        else:
            big = jnp.array(np.iinfo(np.int64).max, v.dtype)
        if op == "min":
            s = jax.ops.segment_min(jnp.where(valid, v, big), codes,
                                    num_segments=n_seg)
            s = jax.lax.pmin(s, REPLAY_AXIS)
        elif op == "max":
            s = jax.ops.segment_max(jnp.where(valid, v, -big), codes,
                                    num_segments=n_seg)
            s = jax.lax.pmax(s, REPLAY_AXIS)
        else:
            raise ValueError(op)
        return s, cnt

    spec = P(REPLAY_AXIS)
    fn = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=(P(), P()))
    return jax.jit(obs.program("sqlops.segagg_sharded")(fn))


@functools.partial(jax.jit, static_argnames=("n_seg",))
@obs.program("sqlops.group_sizes")
def _group_sizes_kernel(codes, real, n_seg: int):
    return jax.ops.segment_sum(real.astype(jnp.int64), codes,
                               num_segments=n_seg)


@functools.partial(jax.jit, static_argnames=("n_seg",))
@obs.program("sqlops.centered_sumsq")
def _centered_sumsq_kernel(codes, v, valid, means, n_seg: int):
    """Second pass for variance: sum((v - mean[g])^2) over valid rows."""
    d = v - means[codes]
    zero = jnp.zeros((), d.dtype)
    return jax.ops.segment_sum(jnp.where(valid, d * d, zero), codes,
                               num_segments=n_seg)


class GroupAggregator:
    """Padded, device-resident group codes plus per-spec reductions.

    Usage: construct with the row->group code array, then call
    `reduce(values, valid, op)` per aggregate. Ints accumulate in i64,
    floats in f64; `var(values, valid)` runs the exact two-pass
    variance. Results are sliced to `n_groups`.
    """

    def __init__(self, codes: np.ndarray, n_groups: int, device=None,
                 mesh=None):
        _ensure_x64()
        self.n = int(len(codes))
        self.n_groups = int(n_groups)
        self.n_seg = pad_bucket(self.n_groups + 1, min_bucket=256)
        self.npad = pad_bucket(max(self.n, 1))
        self._codes_np = np.asarray(codes)  # host copy for reuse
        codes_p = np.full(self.npad, self.n_seg - 1, np.int32)
        codes_p[:self.n] = codes
        self.device = device
        real = np.zeros(self.npad, bool)
        real[:self.n] = True
        with obs.device_dispatch("sqlops.group_codes", key=(self.npad,),
                                 budget="sql-agg-lanes", units=self.npad,
                                 gate="sql") as dd:
            dd.set(n=self.n, n_pad=self.npad, n_seg=self.n_seg)
            dd.h2d("codes_p", codes_p)
            dd.h2d("real", real)
            self.codes = jax.device_put(codes_p, device)
            self._real = jax.device_put(real, device)
        mesh = _agg_mesh(self.n, mesh)
        if mesh is not None and self.npad % mesh.devices.size:
            mesh = None  # row blocks must split evenly over the mesh
        self._mesh = mesh

    def sizes(self) -> np.ndarray:
        """COUNT(*) per group."""
        out, = _read("sqlops.group_sizes", _group_sizes_kernel(
            self.codes, self._real, n_seg=self.n_seg))
        return out[:self.n_groups]

    def _pad(self, values: np.ndarray, valid: np.ndarray):
        v = np.asarray(values)
        if v.dtype.kind in "ui" or v.dtype == bool:
            v = v.astype(np.int64)
        else:
            v = v.astype(np.float64)
        # both arms are 8 B/unit, so the static budget holds either way
        vp = np.zeros(self.npad, np.int64) if v.dtype.kind != "f" \
            else np.zeros(self.npad, np.float64)
        vp[:self.n] = v
        mp = np.zeros(self.npad, bool)
        mp[:self.n] = valid
        with obs.device_dispatch("sqlops.agg_values", key=(self.npad,),
                                 budget="sql-agg-values", units=self.npad,
                                 gate="sql") as dd:
            dd.set(n_pad=self.npad, n_seg=self.n_seg)
            dd.h2d("vp", vp)
            dd.h2d("mp", mp)
            return (jax.device_put(vp, self.device),
                    jax.device_put(mp, self.device))

    def reduce(self, values, valid, op: str):
        """Returns (agg[n_groups], valid_count[n_groups]) numpy arrays.
        Callers NULL-out groups where count==0 (min_count=1 sum
        semantics) and restore original dtypes."""
        vp, mp = self._pad(values, valid)
        if self._mesh is not None:
            fn = _sharded_segagg_fn(self._mesh, op, self.n_seg)
            agg, cnt = fn(self.codes, vp, mp)
        else:
            agg, cnt = _segagg_kernel(self.codes, vp, mp, op=op,
                                      n_seg=self.n_seg)
        agg, cnt = _read("sqlops.segagg", agg, cnt)
        return agg[:self.n_groups], cnt[:self.n_groups]

    def var(self, values, valid):
        """Two-pass sample variance (exact centering — a single-pass
        sumsq in f64 loses catastrophically on money columns). Returns
        (var[n_groups], count[n_groups]); var is NaN where count < 2."""
        vp, mp = self._pad(values, valid)
        if vp.dtype != np.float64:
            vp = vp.astype(jnp.float64)
        s, cnt = _segagg_kernel(self.codes, vp, mp, op="sum",
                                n_seg=self.n_seg)
        means = s / jnp.maximum(cnt, 1)
        ss = _centered_sumsq_kernel(self.codes, vp, mp, means,
                                    n_seg=self.n_seg)
        cnt_np, ss_np = (a[:self.n_groups] for a in _read(
            "sqlops.centered_sumsq", cnt, ss))
        with np.errstate(invalid="ignore", divide="ignore"):
            var = np.where(cnt_np >= 2, ss_np / np.maximum(cnt_np - 1, 1),
                           np.nan)
        return var, cnt_np

    def count_distinct(self, value_codes: np.ndarray,
                       valid: np.ndarray) -> np.ndarray:
        """COUNT(DISTINCT x) per group: device-sort (group, value)
        pairs, count run boundaries per group."""
        vc = np.asarray(value_codes, np.int64)
        g = self._codes_np.astype(np.int64)  # no D2H round-trip
        keep = np.asarray(valid, bool)
        g, vc = g[keep], vc[keep]
        m = len(g)
        if m == 0:
            return np.zeros(self.n_groups, np.int64)
        mpad = pad_bucket(m)
        gp = np.full(mpad, self.n_seg - 1, np.int64)
        gp[:m] = g
        vp = np.full(mpad, np.iinfo(np.int64).max, np.int64)
        vp[:m] = vc
        with obs.device_dispatch("sqlops.count_distinct", key=(mpad,),
                                 budget="sql-agg-distinct", units=mpad,
                                 gate="sql") as dd:
            dd.h2d("gp", gp)
            dd.h2d("vp", vp)
            out = _count_distinct_kernel(
                jax.device_put(gp, self.device),
                jax.device_put(vp, self.device), n_seg=self.n_seg)
        out, = _read("sqlops.count_distinct", out)
        return out[:self.n_groups]


@functools.partial(jax.jit, static_argnames=("n_seg",))
@obs.program("sqlops.count_distinct")
def _count_distinct_kernel(g, v, n_seg: int):
    sg, sv = jax.lax.sort((g, v), num_keys=2)
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (sg[1:] != sg[:-1]) | (sv[1:] != sv[:-1])])
    # pad group's runs land in segment n_seg-1, sliced off by caller
    return jax.ops.segment_sum(first.astype(jnp.int64), sg,
                               num_segments=n_seg)


# ----------------------------------------------------------- join ----

def _join_order(code, pad, bits):
    """Rows of a join's concatenated operand in the order of (pad,
    code, position), as `(perm int32, first bool)`: `first` marks the
    rows at which the code changes. `code` is uint64 under `2**bits`;
    a pad takes the bit above, so pads sort last. Position follows
    side, left before right, so this is the stable sort by (pad, code,
    side) the expansion wants."""
    n = code.shape[0]
    word = jnp.where(pad, jnp.uint64(1) << bits.astype(jnp.uint64), code)
    width = _digit_bits(n)
    passes = (bits + 1 + (width - 1)) // width
    steps = jnp.arange(-(-64 // width), dtype=jnp.int32)
    perm = _radix_perm(word[None, :], jnp.zeros_like(steps), steps * width,
                       passes)
    ordered = word[perm]
    first = jnp.concatenate([jnp.ones((1,), bool),
                             ordered[1:] != ordered[:-1]])
    return perm, first


@jax.jit
@obs.program("sqlops.join_codes")
def _join_codes_kernel(codes, n_real, bits):
    """Order the concatenated (left ++ right ++ pads) uint32 codes."""
    pad = jnp.arange(codes.shape[0], dtype=jnp.int32) >= n_real
    return _join_order(codes.astype(jnp.uint64), pad, bits)


@jax.jit
@obs.program("sqlops.join_lanes")
def _join_lanes_kernel(l_vals, r_vals, n_l, n_r, least, bits):
    """Order the concatenated padded int64 key lanes by their distance
    from `least`, the least real value of both. Side and position are
    generated ON DEVICE (they never cross the link), and pads are
    identified positionally so any fill value in the padding is safe."""
    nl_pad = l_vals.shape[0]
    vals = jnp.concatenate([l_vals, r_vals])
    at = jnp.arange(vals.shape[0], dtype=jnp.int32)
    right = at >= nl_pad
    pad = jnp.where(right, at - nl_pad >= n_r, at >= n_l)
    return _join_order((vals - least).astype(jnp.uint64), pad, bits)


def _sorted_triples(perm: np.ndarray, first: np.ndarray, n_real: int,
                    right_from: int):
    """What `_expand_pairs` takes, from a join kernel's answer: the real
    rows come first, a run's number stands for its key."""
    s_pos = perm[:n_real].astype(np.int64)
    return (np.cumsum(first[:n_real], dtype=np.int64),
            (s_pos >= right_from).astype(np.int64), s_pos)


def _pairs_of(perm: np.ndarray, first: np.ndarray, n_real: int,
              right_from: int, how: str) -> tuple[np.ndarray, np.ndarray]:
    """A join kernel's answer as row pairs, the host's share of the join
    after the read: span `join.expand`."""
    with obs.span("join.expand", rows=n_real) as sp:
        pairs = _expand_pairs(
            *_sorted_triples(perm, first, n_real, right_from),
            right_from, how)
        sp.set_attr("pairs", len(pairs[0]))
        return pairs


def _expand_pairs(
    s_key: np.ndarray,
    s_side: np.ndarray,
    s_pos: np.ndarray,
    r_offset: int,
    how: str,
) -> tuple[np.ndarray, np.ndarray]:
    """O(output) host pair expansion over key-sorted (key, side,
    position) triples: one run per distinct key, all left x right
    combinations per run; `how`-preserved unmatched rows get the other
    side's index = -1. Right positions are `r_offset`-rebased into
    right-frame indices. The output is variable-size, so this stays
    host-side under XLA's static-shape model."""
    empty = np.empty(0, np.int64)
    m = len(s_key)
    if m == 0:
        return empty, empty

    starts = np.flatnonzero(
        np.concatenate([[True], s_key[1:] != s_key[:-1]]))
    run_len = np.diff(np.concatenate([starts, [m]]))
    n_r = np.add.reduceat(s_side, starts).astype(np.int64)
    n_l = run_len - n_r

    pairs = n_l * n_r
    total = int(pairs.sum())
    run_of = np.repeat(np.arange(len(starts)), pairs)
    off = np.concatenate([[0], np.cumsum(pairs)[:-1]])
    within = np.arange(total, dtype=np.int64) - off[run_of]
    nr_run = n_r[run_of]
    li = within // nr_run
    ri = within - li * nr_run
    l_idx = s_pos[starts[run_of] + li]
    r_idx = s_pos[starts[run_of] + n_l[run_of] + ri] - r_offset

    extras_l = extras_r = None
    if how != "inner":
        run_of_sorted = np.repeat(np.arange(len(starts)), run_len)
    if how in ("left", "outer"):
        sel = (n_r[run_of_sorted] == 0) & (s_side == 0)
        extras_l = s_pos[sel]
    if how in ("right", "outer"):
        sel = (n_l[run_of_sorted] == 0) & (s_side == 1)
        extras_r = s_pos[sel] - r_offset
    if extras_l is not None and len(extras_l):
        l_idx = np.concatenate([l_idx, extras_l])
        r_idx = np.concatenate([r_idx, np.full(len(extras_l), -1,
                                               np.int64)])
    if extras_r is not None and len(extras_r):
        l_idx = np.concatenate([l_idx, np.full(len(extras_r), -1,
                                               np.int64)])
        r_idx = np.concatenate([r_idx, extras_r])
    return l_idx.astype(np.int64), r_idx.astype(np.int64)


def join_pairs(
    l_codes: np.ndarray,
    r_codes: np.ndarray,
    how: str = "inner",
    device=None,
) -> tuple[np.ndarray, np.ndarray]:
    """General many-to-many equi-join on pre-densified uint32 codes
    (< 0xFFFFFFFF). Returns (l_idx, r_idx) int64 pair indices;
    unmatched rows preserved by `how` appear with the other side's
    index = -1. Device does the combined O(n log n) sort; the host does
    the O(output) pair expansion with vectorized numpy.

    Unlike `ops/join.py::equi_join_codes` (MERGE's cardinality-
    restricted 1-match variant) the output here is variable-size — the
    expansion must live host-side under XLA's static-shape model.
    """
    _ensure_x64()
    nl, nr = int(len(l_codes)), int(len(r_codes))
    n = nl + nr
    empty = np.empty(0, np.int64)
    if n == 0:
        return empty, empty
    npad = pad_bucket(n)
    codes = np.zeros(npad, np.uint32)
    codes[:nl] = l_codes
    codes[nl:n] = r_codes
    bits = int(codes.max()).bit_length()
    with obs.device_dispatch("sqlops.join_codes", key=(npad,),
                             budget="sql-join-lanes", units=npad,
                             gate="sql") as dd:
        dd.set(n=n, n_pad=npad, bits=bits)
        dd.h2d("codes", codes)
        perm, first = _read("sqlops.join_codes", *_join_codes_kernel(
            jax.device_put(codes, device), np.int32(n), np.int32(bits)))
    return _pairs_of(perm, first, n, nl, how)


def join_pairs_lanes(
    l_vals: np.ndarray,
    r_vals: Optional[np.ndarray] = None,
    r_resident: Optional[Tuple[object, int, int, int]] = None,
    how: str = "inner",
    device=None,
) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """Single-key many-to-many equi-join directly on int64 value lanes
    — no host factorize, and the side/iota lanes are generated on
    device, so only the key values ever cross the link (8 B/row vs the
    16 B/row `join_pairs` ships for codes + side + iota).

    `r_resident` is `(device_lane, n_rows, least, most)` from the
    operand cache (`sqlengine/operands.py`): the build side then costs
    ZERO H2D bytes. Exactly one of `r_vals` / `r_resident` must be
    given. None where the keys of both sides span 2**63 or more: the
    sort's word has no bit left for the pads, and the caller joins on
    joint codes instead.
    Output contract matches `join_pairs` (pair order is value-sorted
    rather than first-appearance-sorted; both are valid many-to-many
    expansions of the same multiset)."""
    _ensure_x64()
    nl = int(len(l_vals))
    l_vals = np.asarray(l_vals, np.int64)
    if r_resident is not None:
        r_dev, nr, r_least, r_most = r_resident
        nr = int(nr)
        nr_pad = int(r_dev.shape[0])
    else:
        r_vals = np.asarray(r_vals, np.int64)
        nr = int(len(r_vals))
        nr_pad = pad_bucket(max(nr, 1))
        r_least, r_most = ((int(r_vals.min()), int(r_vals.max())) if nr
                           else (None, None))
    empty = np.empty(0, np.int64)
    if nl + nr == 0:
        return empty, empty
    ends = [v for v in (r_least, r_most) if v is not None]
    if nl:
        ends += [int(l_vals.min()), int(l_vals.max())]
    least = min(ends)
    bits = (max(ends) - least).bit_length()
    if bits >= _WORD_BITS:
        return None     # no room for the pad's bit: the caller's codes do
    nl_pad = pad_bucket(max(nl, 1))
    lp = np.zeros(nl_pad, np.int64)
    lp[:nl] = l_vals
    with obs.device_dispatch("sqlops.join_lanes",
                             key=(nl_pad, nr_pad),
                             budget="sql-join-values", units=nl_pad,
                             gate="sql") as dd:
        dd.set(n_l=nl, n_r=nr, nl_pad=nl_pad, nr_pad=nr_pad, bits=bits,
               resident=r_resident is not None)
        dd.h2d("lp", lp)
        l_dev = jax.device_put(lp, device)
        if r_resident is None:
            rp = np.zeros(nr_pad, np.int64)
            rp[:nr] = r_vals
            dd.h2d("rp", rp, units=nr_pad)
            r_dev = jax.device_put(rp, device)
        perm, first = _read("sqlops.join_lanes", *_join_lanes_kernel(
            l_dev, r_dev, np.int32(nl), np.int32(nr), np.int64(least),
            np.int32(bits)))
    # the kernel's real rows are the left's, then the right's; a right
    # row's position is past the left's pad
    return _pairs_of(perm, first, nl + nr, nl_pad, how)


# --------------------------------------------------------- windows ----

_NEG = np.int64(-(1 << 62))


@jax.jit
@obs.program("sqlops.window_ranks")
def _ranks_kernel(pb, kb):
    """Sorted-order rank family. pb[i]: row i starts a partition;
    kb[i]: row i starts an order-key run (kb includes pb positions).
    Returns (row_number, rank, dense_rank), all 1-based int64."""
    n = pb.shape[0]
    iota = jnp.arange(n, dtype=jnp.int64)
    neg = jnp.int64(_NEG)
    start = jax.lax.cummax(jnp.where(pb, iota, neg))
    row_number = iota - start + 1
    kstart = jax.lax.cummax(jnp.where(kb, iota, neg))
    rank = kstart - start + 1
    kcum = jnp.cumsum(kb.astype(jnp.int64))
    kcum_at_start = jax.lax.cummax(jnp.where(pb, kcum, neg))
    dense = kcum - kcum_at_start + 1
    return row_number, rank, dense


def window_ranks(pb: np.ndarray, kb: np.ndarray, device=None):
    """Host wrapper: bucket-pads the boundary lanes (pads start their
    own partitions so they can't bleed backwards) and slices."""
    _ensure_x64()
    n = len(pb)
    if n == 0:
        z = np.empty(0, np.int64)
        return z, z, z
    npad = pad_bucket(n)
    pbp = np.ones(npad, bool)
    kbp = np.ones(npad, bool)
    pbp[:n] = pb
    kbp[:n] = kb | pb
    with obs.device_dispatch("sqlops.window_ranks", key=(npad,),
                             budget="sql-window-ranks", units=npad,
                             gate="sql") as dd:
        dd.h2d("pbp", pbp)
        dd.h2d("kbp", kbp)
        rn, rk, dr = _ranks_kernel(jax.device_put(pbp, device),
                                   jax.device_put(kbp, device))
    rn, rk, dr = _read("sqlops.window_ranks", rn, rk, dr)
    return rn[:n], rk[:n], dr[:n]


@functools.partial(jax.jit, static_argnames=("op",))
@obs.program("sqlops.window_running")
def _segscan_kernel(v, valid, pb, op: str):
    """Segmented running aggregate in sorted order. Partitions are
    contiguous; pb marks starts. Returns (running[n], run_count[n])."""
    n = v.shape[0]
    iota = jnp.arange(n, dtype=jnp.int64)
    neg = jnp.int64(_NEG)
    start = jax.lax.cummax(jnp.where(pb, iota, neg))
    cnt_cum = jnp.cumsum(valid.astype(jnp.int64))
    cnt_base = jnp.where(start > 0,
                         cnt_cum[jnp.maximum(start - 1, 0)], 0)
    rcount = cnt_cum - cnt_base
    if op in ("sum", "mean"):
        zero = jnp.zeros((), v.dtype)
        c = jnp.cumsum(jnp.where(valid, v, zero))
        base = jnp.where(start > 0, c[jnp.maximum(start - 1, 0)],
                         zero)
        rsum = c - base
        if op == "mean":
            return rsum / jnp.maximum(rcount, 1), rcount
        return rsum, rcount
    if op == "count":
        return rcount.astype(jnp.float64), rcount
    # min/max: segmented scan via associative combine with reset flag
    if op == "min":
        fill = jnp.array(np.inf, v.dtype)
        red = jnp.minimum
    else:
        fill = jnp.array(-np.inf, v.dtype)
        red = jnp.maximum

    def comb(a, b):
        va, ba = a
        vb, bb = b
        return jnp.where(bb, vb, red(va, vb)), ba | bb

    vf = jnp.where(valid, v, fill)
    out, _ = jax.lax.associative_scan(comb, (vf, pb))
    return out, rcount


def window_running(v: np.ndarray, valid: np.ndarray, pb: np.ndarray,
                   op: str, device=None):
    """Running sum/mean/min/max/count within contiguous partitions (the
    SQL default RANGE UNBOUNDED PRECEDING..CURRENT ROW before peer
    sharing). Returns (values f64[n], counts i64[n]); rows where
    count==0 are NULL (callers mask)."""
    _ensure_x64()
    n = len(v)
    if n == 0:
        return np.empty(0, np.float64), np.empty(0, np.int64)
    npad = pad_bucket(n)
    vp = np.zeros(npad, np.float64)
    vp[:n] = np.asarray(v, np.float64)
    mp = np.zeros(npad, bool)
    mp[:n] = valid
    pbp = np.ones(npad, bool)
    pbp[:n] = pb
    with obs.device_dispatch("sqlops.window_running", key=(npad,),
                             budget="sql-window-running", units=npad,
                             gate="sql") as dd:
        dd.h2d("vp", vp)
        dd.h2d("mp", mp)
        dd.h2d("pbp", pbp)
        out, cnt = _segscan_kernel(jax.device_put(vp, device),
                                   jax.device_put(mp, device),
                                   jax.device_put(pbp, device), op=op)
    out, cnt = _read("sqlops.window_running", out, cnt)
    return out[:n], cnt[:n]


@jax.jit
@obs.program("sqlops.window_peer_last")
def _peer_last_kernel(vals, counts, kb):
    """RANGE-frame peer sharing: every row takes the running value at
    the LAST row of its order-key run."""
    n = vals.shape[0]
    krun = jnp.cumsum(kb.astype(jnp.int64)) - 1
    iota = jnp.arange(n, dtype=jnp.int64)
    last = jax.ops.segment_max(iota, krun, num_segments=n)
    take = last[krun]
    return vals[take], counts[take]


def window_peer_last(vals: np.ndarray, counts: np.ndarray,
                     kb: np.ndarray, pb: Optional[np.ndarray] = None,
                     device=None):
    """`kb` marks order-key run starts; peers never span partitions,
    so pass `pb` (or pre-OR it in) — and row 0 always starts a run
    (forced here so a raw diff-based lane can't wrap the first run
    into the padding segment)."""
    _ensure_x64()
    n = len(vals)
    if n == 0:
        return vals, counts
    npad = pad_bucket(n)
    vp = np.zeros(npad, np.float64)
    vp[:n] = vals
    cp = np.zeros(npad, np.int64)
    cp[:n] = counts
    kbp = np.ones(npad, bool)
    kbp[:n] = kb if pb is None else (np.asarray(kb) | np.asarray(pb))
    kbp[0] = True
    with obs.device_dispatch("sqlops.window_peer_last", key=(npad,),
                             budget="sql-window-peers", units=npad,
                             gate="sql") as dd:
        dd.h2d("vp", vp)
        dd.h2d("cp", cp)
        dd.h2d("kbp", kbp)
        v_out, c_out = _peer_last_kernel(jax.device_put(vp, device),
                                         jax.device_put(cp, device),
                                         jax.device_put(kbp, device))
    v_out, c_out = _read("sqlops.window_peer_last", v_out, c_out)
    return v_out[:n], c_out[:n]
