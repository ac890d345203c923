"""Device SQL operator kernels (`ops/sqlops.py`) vs host oracles:
sort permutation vs numpy lexsort, group-by reductions vs pandas
groupby, join pair expansion vs pandas merge, window rank family and
running frames vs pandas transforms. These are the unit layer under
the TPC-DS corpus parity tests (test_tpcds.py runs the full engine on
both substrates)."""

import numpy as np
import pandas as pd
import pytest

from delta_tpu.ops.sqlops import (
    GroupAggregator,
    join_pairs,
    sort_permutation,
    window_peer_last,
    window_ranks,
    window_running,
)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# ------------------------------------------------------------- sort --

def test_sort_permutation_single_key(rng):
    v = rng.standard_normal(10_000)
    perm = sort_permutation([v])
    assert np.array_equal(v[perm], np.sort(v))


def test_sort_permutation_multi_key_stable(rng):
    a = rng.integers(0, 50, 5_000).astype(np.int64)
    b = rng.standard_normal(5_000)
    perm = sort_permutation([a, b])
    ref = np.lexsort((b, a))
    assert np.array_equal(perm, ref)


def test_sort_permutation_stability_on_ties(rng):
    a = rng.integers(0, 10, 4_000).astype(np.int64)
    perm = sort_permutation([a])
    # stable: equal keys keep original relative order
    ref = np.argsort(a, kind="stable")
    assert np.array_equal(perm, ref)


def test_sort_permutation_empty():
    assert len(sort_permutation([np.empty(0, np.float64)])) == 0


# --------------------------------------------------------- group-by --

def _pd_group(codes, values, valid, op):
    s = pd.Series(np.where(valid, values.astype(float), np.nan))
    g = s.groupby(codes)
    if op == "sum":
        return g.sum(min_count=1)
    if op == "count":
        return g.count()
    return getattr(g, op)()


@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_group_reduce_float(rng, op):
    n, G = 50_000, 700
    codes = rng.integers(0, G, n).astype(np.int32)
    v = rng.standard_normal(n) * 1e3
    valid = rng.random(n) > 0.1
    ga = GroupAggregator(codes, G)
    agg, cnt = ga.reduce(v, valid, op)
    ref = _pd_group(codes, v, valid, op).reindex(range(G))
    got = agg.astype(float).copy()
    got[cnt == 0] = np.nan
    if op == "count":
        got = agg.astype(float)  # count of empty group = 0, not NaN
        ref = ref.fillna(0)
    np.testing.assert_allclose(got, ref.to_numpy(), rtol=1e-12,
                               equal_nan=True)


def test_group_reduce_int_exact(rng):
    # int64 accumulation must be exact where f64 would round
    n = 100
    codes = np.zeros(n, np.int32)
    v = np.full(n, (1 << 53) + 1, np.int64)  # not representable in f64
    ga = GroupAggregator(codes, 1)
    agg, cnt = ga.reduce(v, np.ones(n, bool), "sum")
    assert agg[0] == ((1 << 53) + 1) * n
    assert cnt[0] == n


def test_group_sizes_and_all_null_group(rng):
    codes = np.array([0, 0, 1, 2, 2, 2], np.int32)
    v = np.arange(6, dtype=np.float64)
    valid = np.array([True, True, False, True, True, True])
    ga = GroupAggregator(codes, 3)
    assert ga.sizes().tolist() == [2, 1, 3]
    agg, cnt = ga.reduce(v, valid, "sum")
    assert cnt.tolist() == [2, 0, 3]  # group 1 is all-null -> NULL sum


def test_group_var_two_pass(rng):
    n, G = 20_000, 40
    codes = rng.integers(0, G, n).astype(np.int32)
    # large offset: single-pass sumsq would lose precision
    v = rng.standard_normal(n) + 1e8
    valid = rng.random(n) > 0.05
    ga = GroupAggregator(codes, G)
    var, cnt = ga.var(v, valid)
    ref = pd.Series(np.where(valid, v, np.nan)).groupby(codes).var()
    np.testing.assert_allclose(var, ref.to_numpy(), rtol=1e-6,
                               equal_nan=True)


def test_group_count_distinct(rng):
    n, G = 30_000, 100
    codes = rng.integers(0, G, n).astype(np.int32)
    vals = rng.integers(0, 50, n)
    valid = rng.random(n) > 0.2
    ga = GroupAggregator(codes, G)
    got = ga.count_distinct(vals, valid)
    ref = (pd.DataFrame({"g": codes,
                         "v": np.where(valid, vals.astype(float),
                                       np.nan)})
           .groupby("g")["v"].nunique().reindex(range(G), fill_value=0))
    assert got.tolist() == ref.astype(int).tolist()


# ------------------------------------------------------------- join --

def _pd_join(lk, rk, how):
    left = pd.DataFrame({"k": lk, "li": np.arange(len(lk))})
    right = pd.DataFrame({"k": rk, "ri": np.arange(len(rk))})
    out = left.merge(right, on="k", how=how)
    li = out["li"].fillna(-1).astype(np.int64)
    ri = out["ri"].fillna(-1).astype(np.int64)
    return set(zip(li.tolist(), ri.tolist()))


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_join_pairs_vs_pandas(rng, how):
    lk = rng.integers(0, 500, 3_000).astype(np.uint32)
    rk = rng.integers(200, 700, 2_000).astype(np.uint32)
    li, ri = join_pairs(lk, rk, how=how)
    assert set(zip(li.tolist(), ri.tolist())) == _pd_join(lk, rk, how)


def test_join_pairs_many_to_many(rng):
    lk = np.array([1, 1, 2, 3], np.uint32)
    rk = np.array([1, 1, 1, 3, 4], np.uint32)
    li, ri = join_pairs(lk, rk, how="inner")
    # key 1: 2x3 pairs; key 3: 1
    assert len(li) == 7
    assert set(zip(li.tolist(), ri.tolist())) == {
        (0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (1, 2), (3, 3)}


def test_join_pairs_empty_sides():
    e = np.empty(0, np.uint32)
    k = np.array([1, 2], np.uint32)
    li, ri = join_pairs(e, k, how="inner")
    assert len(li) == 0
    li, ri = join_pairs(e, k, how="outer")
    assert set(ri.tolist()) == {0, 1} and set(li.tolist()) == {-1}
    li, ri = join_pairs(k, e, how="left")
    assert set(li.tolist()) == {0, 1} and set(ri.tolist()) == {-1}


# ---------------------------------------------------------- windows --

def _boundaries(parts, keys):
    n = len(parts[0]) if parts else len(keys[0])
    pb = np.zeros(n, bool)
    pb[0] = True
    for p in parts:
        pb[1:] |= p[1:] != p[:-1]
    kb = pb.copy()
    for k in keys:
        kb[1:] |= k[1:] != k[:-1]
    return pb, kb


def test_window_ranks_vs_pandas(rng):
    n = 20_000
    part = np.sort(rng.integers(0, 300, n))
    key = rng.integers(0, 20, n)
    # sort within partitions by key (contiguity contract)
    order = np.lexsort((key, part))
    part, key = part[order], key[order]
    pb, kb = _boundaries([part], [key])
    rn, rk, dr = window_ranks(pb, kb)
    df = pd.DataFrame({"p": part, "k": key})
    g = df.groupby("p")["k"]
    assert np.array_equal(rn, g.cumcount().to_numpy() + 1)
    assert np.array_equal(rk, g.rank(method="min").astype(int)
                          .to_numpy())
    assert np.array_equal(dr, g.rank(method="dense").astype(int)
                          .to_numpy())


@pytest.mark.parametrize("op", ["sum", "mean", "min", "max", "count"])
def test_window_running_vs_pandas(rng, op):
    n = 10_000
    part = np.sort(rng.integers(0, 100, n))
    v = rng.standard_normal(n)
    valid = rng.random(n) > 0.1
    pb = np.zeros(n, bool)
    pb[0] = True
    pb[1:] = part[1:] != part[:-1]
    got, cnt = window_running(v, valid, pb, op)
    s = pd.Series(np.where(valid, v, np.nan))
    expand = {"sum": lambda x: x.expanding().sum(),
              "mean": lambda x: x.expanding().mean(),
              "min": lambda x: x.expanding().min(),
              "max": lambda x: x.expanding().max(),
              "count": lambda x: x.expanding().count()}[op]
    ref = s.groupby(part).transform(expand).to_numpy()
    got = got.copy()
    if op != "count":
        got[cnt == 0] = np.nan
    np.testing.assert_allclose(got, np.nan_to_num(ref, nan=np.nan),
                               rtol=1e-9, equal_nan=True)


def test_window_peer_last(rng):
    # RANGE frame: peers (equal order keys) share the run's last value
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    cnts = np.array([1, 2, 3, 4, 5], np.int64)
    kb = np.array([True, False, True, False, False])
    v, c = window_peer_last(vals, cnts, kb)
    assert v.tolist() == [2.0, 2.0, 5.0, 5.0, 5.0]
    assert c.tolist() == [2, 2, 5, 5, 5]


def test_x64_flip_coexists_with_replay_kernels(rng):
    # sqlops enables jax_enable_x64 lazily; the replay kernels are
    # dtype-explicit and must produce identical masks afterwards
    from delta_tpu.ops.replay import python_replay_reference, replay_select

    sort_permutation([rng.standard_normal(64)])  # flips x64 on
    n = 20_000
    pk = rng.integers(0, 2_000, n).astype(np.uint32)
    dk = np.zeros(n, np.uint32)
    ver = np.sort(rng.integers(0, 500, n)).astype(np.int32)
    change = np.nonzero(np.diff(ver))[0] + 1
    starts = np.concatenate([[0], change])
    lens = np.diff(np.concatenate([starts, [n]]))
    order = (np.arange(n) - np.repeat(starts, lens)).astype(np.int32)
    is_add = rng.random(n) < 0.7
    live, tomb = replay_select([pk, dk], ver, order, is_add)
    live_o, tomb_o = python_replay_reference(
        list(zip(pk.tolist(), dk.tolist())), ver, order, is_add)
    assert np.array_equal(np.asarray(live), live_o)
    assert np.array_equal(np.asarray(tomb), tomb_o)


def test_sort_permutation_bool_null_lane(rng):
    # the documented null-ordering lane pattern: bool lanes must work
    v = np.array([3.0, np.nan, 1.0, np.nan, 2.0])
    null_lane = np.isnan(v)  # NULLS LAST ascending
    perm = sort_permutation([null_lane, np.nan_to_num(v, nan=0.0)])
    assert perm.tolist() == [2, 4, 0, 1, 3]


def test_window_peer_last_first_run_unflagged():
    # a raw diff-based kb lane may leave row 0 unflagged; the first
    # run must not wrap into the padding segment
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    cnts = np.array([1, 2, 3, 4], np.int64)
    kb = np.array([False, False, True, False])
    v, c = window_peer_last(vals, cnts, kb)
    assert v.tolist() == [2.0, 2.0, 4.0, 4.0]
    assert c.tolist() == [2, 2, 4, 4]


# ---------------------------------------- the radix form of the sorts --
# What the join's and the ORDER BY's sort were until PR 49: one stable
# multi-operand `lax.sort`. The radix form (`_radix_perm`: passes of one
# single-operand uint32 sort) has to give the same rows in the same
# order, bit for bit.

def _stable_join_lanes(l_vals, r_vals, nl_pad, nr_pad):
    """(s_val, s_side, s_pos) of the real rows, by the stable form."""
    import jax
    import jax.numpy as jnp

    nl, nr = len(l_vals), len(r_vals)
    lp = np.full(nl_pad, 7, np.int64)       # any fill: pads are positional
    lp[:nl] = l_vals
    rp = np.full(nr_pad, -7, np.int64)
    rp[:nr] = r_vals
    vals = jnp.concatenate([jnp.asarray(lp), jnp.asarray(rp)])
    iota = jnp.arange(vals.shape[0], dtype=jnp.int64)
    side = (iota >= nl_pad).astype(jnp.uint8)
    local = jnp.where(side == 1, iota - nl_pad, iota)
    pad = (local >= jnp.where(side == 1, nr, nl)).astype(jnp.uint8)
    s_pad, s_val, s_side, s_pos = (np.asarray(a) for a in jax.lax.sort(
        (pad, vals, side, iota), num_keys=3, is_stable=True))
    real = s_pad == 0
    return s_val[real], s_side[real].astype(np.int64), s_pos[real]


JOIN_CASES = {
    "duplicates": (lambda r: r.integers(0, 9, 700), lambda r: r.integers(0, 9, 300)),
    "ties_on_both_sides": (lambda r: np.repeat([5, 5, 6], 50), lambda r: np.repeat([5, 6, 6], 40)),
    "negative_and_wide": (lambda r: r.integers(-(1 << 40), 1 << 40, 500),
                          lambda r: r.integers(-(1 << 40), 1 << 40, 500)),
    "mostly_pads": (lambda r: r.integers(0, 3, 3), lambda r: r.integers(0, 3, 1025)),
    "empty_left": (lambda r: np.empty(0, np.int64), lambda r: r.integers(0, 4, 64)),
    "one_value": (lambda r: np.zeros(33, np.int64), lambda r: np.zeros(17, np.int64)),
    "int64_ends": (lambda r: np.array([-(1 << 61), (1 << 61) - 1, 0, 0]),
                   lambda r: np.array([(1 << 61) - 1, -(1 << 61), 0])),
}


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", sorted(JOIN_CASES))
def test_radix_join_order_is_the_stable_sorts(case, seed):
    from delta_tpu.ops import sqlops
    from delta_tpu.ops.replay import pad_bucket

    rng = np.random.default_rng(seed)
    l_vals, r_vals = (np.asarray(make(rng), np.int64)
                      for make in JOIN_CASES[case])
    sqlops._ensure_x64()
    nl, nr = len(l_vals), len(r_vals)
    nl_pad, nr_pad = pad_bucket(max(nl, 1)), pad_bucket(max(nr, 1))
    lp = np.zeros(nl_pad, np.int64)
    lp[:nl] = l_vals
    rp = np.zeros(nr_pad, np.int64)
    rp[:nr] = r_vals
    both = np.concatenate([l_vals, r_vals])
    least = int(both.min())
    bits = (int(both.max()) - least).bit_length()
    perm, first = (np.asarray(a) for a in sqlops._join_lanes_kernel(
        lp, rp, np.int32(nl), np.int32(nr), np.int64(least), np.int32(bits)))
    s_key, s_side, s_pos = sqlops._sorted_triples(perm, first, nl + nr, nl_pad)
    want_val, want_side, want_pos = _stable_join_lanes(l_vals, r_vals,
                                                       nl_pad, nr_pad)
    assert np.array_equal(s_pos, want_pos)
    assert np.array_equal(s_side, want_side)
    # a run's number stands for its key: the same runs
    assert np.array_equal(np.diff(s_key) != 0, np.diff(want_val) != 0)
    for how in ("inner", "left", "right", "outer"):
        got = sqlops.join_pairs_lanes(l_vals, r_vals=r_vals, how=how)
        want = sqlops._expand_pairs(want_val, want_side, want_pos,
                                    nl_pad, how)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_radix_join_codes_is_the_stable_sorts(rng, how):
    import jax

    from delta_tpu.ops import sqlops
    from delta_tpu.ops.replay import pad_bucket

    lk = rng.integers(0, 40, 900).astype(np.uint32)
    rk = rng.integers(20, 60, 500).astype(np.uint32)
    nl, n = len(lk), len(lk) + len(rk)
    npad = pad_bucket(n)
    codes = np.full(npad, 0xFFFFFFFF, np.uint32)
    codes[:nl], codes[nl:n] = lk, rk
    side = np.zeros(npad, np.uint32)
    side[nl:] = 1
    s_code, s_side, s_pos = (np.asarray(a) for a in jax.lax.sort(
        (codes, side, np.arange(npad, dtype=np.int64)), num_keys=2,
        is_stable=True))
    real = s_code != 0xFFFFFFFF
    want = sqlops._expand_pairs(s_code[real], s_side[real], s_pos[real],
                                nl, how)
    got = join_pairs(lk, rk, how=how)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


def test_a_join_whose_keys_span_the_whole_of_int64_declines():
    from delta_tpu.ops import sqlops

    ends = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max])
    assert sqlops.join_pairs_lanes(ends, r_vals=ends) is None


SORT_CASES = {
    "duplicates_and_ties": lambda r, n: [r.integers(0, 4, n), r.integers(0, 3, n).astype(np.float64)],
    "null_lanes": lambda r, n: [(r.random(n) < 0.3).astype(np.uint8), r.integers(-50, 50, n),
                                r.random(n) < 0.5, -r.integers(0, 5, n).astype(np.float64)],
    "signed_zeros": lambda r, n: [r.choice([-0.0, 0.0, -1.5, 2.5], n), r.integers(0, 2, n)],
    "wide_lanes": lambda r, n: [r.integers(-(1 << 62), 1 << 62, n), r.standard_normal(n),
                                r.integers(-(1 << 62), 1 << 62, n)],
    "constant": lambda r, n: [np.zeros(n, np.int64), np.ones(n)],
    "float32_and_extremes": lambda r, n: [r.choice([np.inf, -np.inf, 1e-300, -1e300, 0.5], n),
                                          r.standard_normal(n).astype(np.float32)],
}


@pytest.mark.parametrize("n", [1, 255, 1000, 1025])
@pytest.mark.parametrize("case", sorted(SORT_CASES))
def test_radix_sort_permutation_is_the_stable_sorts(case, n):
    import jax

    from delta_tpu.ops import sqlops

    lanes = [np.asarray(a) for a in SORT_CASES[case](
        np.random.default_rng(n), n)]
    sqlops._ensure_x64()
    operands = tuple(a.astype(np.float64) if a.dtype == np.float32
                     else a.astype(np.uint8) if a.dtype == bool else a
                     for a in lanes)
    want = np.asarray(jax.lax.sort(
        operands + (np.arange(n, dtype=np.int64),),
        num_keys=len(lanes), is_stable=True)[-1])
    got = sort_permutation(lanes)
    assert got.dtype == np.int64
    assert np.array_equal(got, want)


def test_sort_words_pack_the_lanes_that_fit(rng):
    from delta_tpu.ops import sqlops

    lanes = [rng.integers(0, 2, 100).astype(np.uint8),       # 1 bit
             rng.integers(0, 1 << 20, 100),                  # <= 20 bits
             np.zeros(100),                                  # 0 bits
             rng.integers(-(1 << 62), 1 << 62, 100)]         # 63 bits
    words, bits = sqlops._pack_sort_words(lanes)
    assert len(words) == 2 and all(w.dtype == np.uint64 for w in words)
    assert bits[0] <= 1 + 20 and bits[1] <= 63
    assert int(words[0].max()) < 1 << bits[0]
