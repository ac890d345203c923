"""Device-on-merit benchmark + interconnect cost model (VERDICT r3
ask #4).

Measures, on the real attached accelerator:

1. the LINK: H2D/D2H bandwidth at several transfer sizes and the
   dispatch round-trip latency (tiny-op RTT);
2. three workloads device-vs-host, each with the device COMPUTE time
   isolated by timing the jitted kernel on already-resident operands
   (block_until_ready, best of k):
     - replay @ N rows (FA-coded transfer, the product path),
     - blockwise replay @ N rows (resident bitset, streamed blocks),
     - MERGE-style sort join @ N rows;
   the host side is the strongest vectorized numpy formulation of the
   same algorithm (argsort/searchsorted/lexsort), not a Python loop;
3. a transfer/compute cost model: measured wall ≈ bytes/BW + k·RTT +
   t_compute, validated against the measured walls, then re-evaluated
   with PCIe gen4 x16 parameters (BW 16 GB/s[*], RTT 10 µs) to project
   what the same kernels do on a directly-attached device.

[*] a deliberately conservative effective PCIe figure; real pinned-
memory transfers reach ~20+ GB/s.

Output: one JSON document (default `DEVICE_MERIT.json` at the repo
root) with the raw measurements, the model fit, the per-workload
verdicts, and the projections — the checked-in artifact the round-3
verdict asked for. Run SOLO: background CPU work corrupts the host
baselines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

PCIE_BW_BYTES_S = 16e9
PCIE_RTT_S = 10e-6


def _best(fn, k=3):
    out = []
    for _ in range(k):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return min(out)


# ------------------------------------------------------------- link --


def measure_link(device):
    import jax
    import jax.numpy as jnp

    sizes = [8 << 20, 64 << 20]
    h2d, d2h = {}, {}
    for size in sizes:
        buf = np.random.default_rng(0).integers(
            0, 255, size, dtype=np.uint8)
        t = _best(lambda: jax.device_put(buf, device).block_until_ready())
        h2d[size] = size / t

        def pull():
            # fresh device array per rep: jax caches np.asarray results
            dbuf = jax.device_put(buf, device)
            dbuf.block_until_ready()
            t0 = time.perf_counter()
            np.asarray(dbuf)
            return time.perf_counter() - t0

        t = min(pull() for _ in range(3))
        d2h[size] = size / t
    one = jax.device_put(np.zeros(8, np.float32), device)
    inc = jax.jit(lambda x: x + 1)
    inc(one).block_until_ready()  # compile
    rtt = _best(lambda: inc(one).block_until_ready(), k=5)
    return {
        "h2d_bytes_per_s": {str(k): round(v) for k, v in h2d.items()},
        "d2h_bytes_per_s": {str(k): round(v) for k, v in d2h.items()},
        "rtt_s": rtt,
        # sustained figure: the LARGEST transfer's bandwidth (small
        # sizes are RTT/warmup-dominated and can read as outliers)
        "bw_bytes_per_s": h2d[sizes[-1]],
    }


# -------------------------------------------------------- workloads --


def _fa_stream(n, seed=0):
    from delta_tpu.utils.synth import fa_history

    pk, dk, ver, order, add, _size = fa_history(
        n, seed=seed, dv_frac=0.02)
    return pk, dk, ver, order, add


def wl_replay(n, device):
    """Full replay: device product path (FA-coded transfer) vs numpy
    lexsort last-wins."""
    from delta_tpu.ops.replay import replay_select

    pk, dk, ver, order, add = _fa_stream(n)

    def dev():
        live, _ = replay_select([pk, dk], ver, order, add,
                                device=device)
        return int(live.sum())

    dev()  # compile + warm
    t_dev = _best(dev, k=2)

    def host():
        key = pk.astype(np.uint64) * np.uint64(4) + dk
        shift = np.uint64(max(1, int(n - 1).bit_length()))
        k = (key << shift) | np.arange(n, dtype=np.uint64)
        srt = np.sort(k)
        kk = srt >> shift
        boundary = np.empty(n, bool)
        boundary[:-1] = kk[:-1] != kk[1:]
        boundary[-1] = True
        idx = (srt & np.uint64((1 << int(shift)) - 1))[boundary]
        return int(add[idx.astype(np.int64)].sum())

    live_h = host()
    t_host = _best(host, k=2)
    assert dev() == live_h
    # device compute isolated: resident operands (raw key lane)
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = (pk.astype(np.uint32) << np.uint32(2)) | dk
    dkey = jax.device_put(key, device)
    dadd = jax.device_put(add, device)

    @jax.jit
    def kern(key, addv):
        iota = jnp.arange(key.shape[0], dtype=jnp.uint32)
        s_key, s_add = lax.sort(
            (key, addv.astype(jnp.uint8)), num_keys=1, is_stable=True)
        is_last = jnp.concatenate(
            [s_key[:-1] != s_key[1:], jnp.ones((1,), bool)])
        return jnp.sum((is_last & (s_add == 1)).astype(jnp.int32))

    kern(dkey, dadd).block_until_ready()
    t_comp = _best(lambda: kern(dkey, dadd).block_until_ready(), k=3)
    bytes_moved = n * 1.0 + n // 8  # FA coding ~1B/row + winner words
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_blockwise(n, device):
    """Blockwise (>HBM) replay with resident bitset vs the same numpy
    lexsort (the host has no memory pressure at these sizes, so this
    is a fair strongest-host baseline)."""
    from delta_tpu.ops.replay_blockwise import replay_select_blockwise

    pk, dk, ver, order, add = _fa_stream(n, seed=1)

    def dev():
        live, _ = replay_select_blockwise(
            [pk, dk], ver, order, add, device=device)
        return int(live.sum())

    got = dev()
    t_dev = _best(dev, k=2)

    def host():
        key = pk.astype(np.uint64) * np.uint64(4) + dk
        shift = np.uint64(max(1, int(n - 1).bit_length()))
        k = (key << shift) | np.arange(n, dtype=np.uint64)
        srt = np.sort(k)
        kk = srt >> shift
        boundary = np.empty(n, bool)
        boundary[:-1] = kk[:-1] != kk[1:]
        boundary[-1] = True
        idx = (srt & np.uint64((1 << int(shift)) - 1))[boundary]
        return int(add[idx.astype(np.int64)].sum())

    assert host() == got
    t_host = _best(host, k=2)
    # isolated compute: one resident block step x number of blocks
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops.replay import _PAD_KEY, pad_bucket
    from delta_tpu.ops.replay_blockwise import (
        DEFAULT_BLOCK_ROWS,
        _block_kernel_impl,
    )

    m = pad_bucket(min(DEFAULT_BLOCK_ROWS, n))
    n_blocks = -(-n // m)
    # densify exactly like the real blockwise path: the kernel's seen
    # bitset is sized to the unique-key space, so raw sparse keys would
    # clamp out of range and measure a degenerate access pattern
    wide = (pk.astype(np.uint64) << np.uint64(2)) | dk
    _, dense = np.unique(wide, return_inverse=True)
    key32 = dense.astype(np.uint32)[:m]
    blk = np.full(m, _PAD_KEY, np.uint32)
    blk[:len(key32)] = key32
    n_words = -(-(int(key32.max()) + 1) // 32)
    step = jax.jit(lambda seen, keys: _block_kernel_impl(
        seen, keys, jnp.int32(m), m))
    seen0 = jax.device_put(
        jnp.zeros((pad_bucket(max(n_words, 1024)),), jnp.uint32),
        device)
    dblk = jax.device_put(blk, device)
    step(seen0, dblk)[0].block_until_ready()
    t_block = _best(
        lambda: step(seen0, dblk)[0].block_until_ready(), k=3)
    t_comp = t_block * n_blocks
    bytes_moved = n * 4.0 + n // 8  # u32 key blocks + winner words
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_merge_join(n, device):
    """MERGE match-finding: device sort/segment equi-join vs numpy
    argsort + searchsorted."""
    import jax

    from delta_tpu.ops.join import equi_join_codes

    rng = np.random.default_rng(2)
    target = rng.permutation(np.arange(n, dtype=np.uint32))
    source = rng.integers(0, n * 2, n // 2).astype(np.uint32)

    def dev():
        match_src, _n_multi, _sm = equi_join_codes(
            target, source, device=device)
        return int((match_src >= 0).sum())

    got = dev()
    t_dev = _best(dev, k=2)

    def host():
        ss = np.sort(source)
        pos = np.searchsorted(ss, target)
        pos_c = np.clip(pos, 0, len(ss) - 1)
        hit = ss[pos_c] == target
        return int(hit.sum())

    assert host() == got
    t_host = _best(host, k=2)
    # device compute isolated with resident operands
    import jax.numpy as jnp

    dt = jax.device_put(target, device)
    ds = jax.device_put(source, device)

    @jax.jit
    def kern(t, s):
        ss = jnp.sort(s)
        pos = jnp.searchsorted(ss, t)
        pos_c = jnp.clip(pos, 0, s.shape[0] - 1)
        return jnp.sum((ss[pos_c] == t).astype(jnp.int32))

    kern(dt, ds).block_until_ready()
    t_comp = _best(lambda: kern(dt, ds).block_until_ready(), k=3)
    bytes_moved = n * 8 + (n // 2) * 8 + n * 4
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_sql_groupby(n, device):
    """SQL GROUP BY spine: device segment reduce (sum+count over dense
    group codes, `ops/sqlops.py::GroupAggregator`) vs the displaced
    substrate — pandas groupby — AND the strongest numpy formulation
    (np.bincount weighted sums), reported against the stronger of the
    two."""
    import jax
    import pandas as pd

    from delta_tpu.ops import sqlops

    rng = np.random.default_rng(11)
    G = max(n // 100, 16)
    codes = rng.integers(0, G, n).astype(np.int32)
    v = rng.standard_normal(n) * 100.0
    valid = np.ones(n, bool)

    def dev():
        ga = sqlops.GroupAggregator(codes, G, device=device)
        s, c = ga.reduce(v, valid, "sum")
        return float(s.sum()), int(c.sum())

    got = dev()
    t_dev = _best(dev, k=2)

    def host_pandas():
        g = pd.Series(v).groupby(codes)
        s = g.sum()
        c = g.count()
        return float(s.sum()), int(c.sum())

    def host_numpy():
        s = np.bincount(codes, weights=v, minlength=G)
        c = np.bincount(codes, minlength=G)
        return float(s.sum()), int(c.sum())

    hp = host_pandas()
    assert abs(hp[0] - got[0]) < 1e-6 * max(1, abs(got[0]))
    assert hp[1] == got[1]
    t_pandas = _best(host_pandas, k=2)
    t_numpy = _best(host_numpy, k=2)
    t_host = min(t_pandas, t_numpy)

    # isolated compute: resident padded operands through the jit kernel
    npad = sqlops.pad_bucket(n)
    n_seg = sqlops.pad_bucket(G + 1, min_bucket=256)
    cp = np.full(npad, n_seg - 1, np.int32)
    cp[:n] = codes
    vp = np.zeros(npad, np.float64)
    vp[:n] = v
    mp = np.zeros(npad, bool)
    mp[:n] = valid
    dc = jax.device_put(cp, device)
    dv = jax.device_put(vp, device)
    dm = jax.device_put(mp, device)

    def comp():
        s, c = sqlops._segagg_kernel(dc, dv, dm, op="sum", n_seg=n_seg)
        s.block_until_ready()

    comp()
    t_comp = _best(comp, k=3)
    bytes_moved = n * (4 + 8 + 1) + G * 16
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_host_pandas_s": t_pandas, "t_host_numpy_s": t_numpy,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_sql_join(n, device):
    """SQL many-to-many equi-join spine: device sort + host pair
    expansion (`ops/sqlops.py::join_pairs`) vs pandas merge (the
    displaced substrate)."""
    import pandas as pd

    import jax

    from delta_tpu.ops import sqlops

    rng = np.random.default_rng(12)
    nl, nr = n, n // 2
    lk = rng.integers(0, n, nl).astype(np.uint32)
    rk = rng.integers(0, n, nr).astype(np.uint32)

    def dev():
        li, ri = sqlops.join_pairs(lk, rk, how="inner", device=device)
        return len(li)

    got = dev()
    t_dev = _best(dev, k=2)

    left = pd.DataFrame({"k": lk})
    right = pd.DataFrame({"k": rk})

    def host():
        return len(left.merge(right, on="k", how="inner"))

    assert host() == got
    t_host = _best(host, k=2)

    # isolated compute: the combined sort on resident operands
    npad = sqlops.pad_bucket(nl + nr)
    codes = np.full(npad, 0xFFFFFFFF, np.uint32)
    codes[:nl] = lk
    codes[nl:nl + nr] = rk
    side = np.zeros(npad, np.uint32)
    side[nl:] = 1
    iota = np.arange(npad, dtype=np.int64)
    dc = jax.device_put(codes, device)
    ds = jax.device_put(side, device)
    di = jax.device_put(iota, device)

    def comp():
        out = sqlops._join_sort_kernel(dc, ds, di)
        out[0].block_until_ready()

    comp()
    t_comp = _best(comp, k=3)
    bytes_moved = npad * (4 + 4 + 8) * 2  # up + sorted lanes down
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_sql_sort(n, device):
    """SQL ORDER BY / window sort spine: device stable multi-lane sort
    permutation vs numpy lexsort (stronger than pandas sort_values)."""
    import jax

    from delta_tpu.ops import sqlops

    rng = np.random.default_rng(13)
    a = rng.integers(0, 1000, n).astype(np.int64)
    b = rng.standard_normal(n)

    def dev():
        return len(sqlops.sort_permutation([a, b], device=device))

    dev()
    t_dev = _best(dev, k=2)

    def host():
        return len(np.lexsort((b, a)))

    t_host = _best(host, k=2)
    assert np.array_equal(sqlops.sort_permutation([a, b], device=device),
                          np.lexsort((b, a)))

    npad = sqlops.pad_bucket(n)
    ap = np.full(npad, np.iinfo(np.int64).max, np.int64)
    ap[:n] = a
    bp = np.full(npad, np.inf, np.float64)
    bp[:n] = b
    iota = np.arange(npad, dtype=np.int64)
    da = jax.device_put(ap, device)
    db = jax.device_put(bp, device)
    di = jax.device_put(iota, device)

    def comp():
        sqlops._sort_kernel((da, db, di), num_keys=2) \
            .block_until_ready()

    comp()
    t_comp = _best(comp, k=3)
    bytes_moved = n * (8 + 8) + n * 8
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


def wl_page_decode(n, device):
    """Checkpoint Parquet page decode: the thrift/page split + Pallas
    bit-unpack + dictionary-gather path (log/page_decode.py) vs
    pyarrow's C++ reader on the same single column."""
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from delta_tpu.log.page_decode import read_checkpoint_column

    rng = np.random.default_rng(21)
    vals = rng.integers(0, 60_000, n)  # dictionary-encodable domain
    path = tempfile.mktemp(suffix=".parquet")
    pq.write_table(pa.table({"x": pa.array(vals, pa.int64())}), path)

    def dev():
        v, ok = read_checkpoint_column(path, "x", device=device)
        return int(v[ok].sum())

    got = dev()
    t_dev = _best(dev, k=2)

    def host():
        return int(pq.read_table(path, columns=["x"])
                   .column("x").to_numpy().sum())

    assert host() == got
    t_host = _best(host, k=2)

    # isolated compute: the unpack kernel on resident padded words
    import jax

    from delta_tpu.ops import sqlops  # noqa: F401  (x64 on)
    from delta_tpu.ops.pallas_kernels import (
        _TILE,
        unpack_bitpacked_tiled,
    )

    w = 16
    groups = -(-n // 32)
    padded = -(-groups // _TILE) * _TILE
    words = rng.integers(0, 1 << 32, (w, padded), dtype=np.uint64)         .astype(np.uint32)
    # pin x32: Mosaic lowers the kernel with i32 grid indexing and a
    # prior sql workload flipped global x64 in this process
    with jax.enable_x64(False):
        dw = jax.device_put(words, device)
        unpack_bitpacked_tiled(dw, w).block_until_ready()
        t_comp = _best(
            lambda: unpack_bitpacked_tiled(dw, w).block_until_ready(),
            k=3)
    bytes_moved = padded * w * 4 + n * 4
    os.unlink(path)
    return {"n": n, "t_device_s": t_dev, "t_host_s": t_host,
            "t_device_compute_s": t_comp,
            "bytes_transferred_est": int(bytes_moved),
            "device_wins": t_dev < t_host}


# ------------------------------------------------------- cost model --


def model(link, wl, k_rtts=4):
    """Predicted wall on the measured link and projected wall on PCIe
    from the same isolated compute + byte counts."""
    bw = link["bw_bytes_per_s"]
    rtt = link["rtt_s"]
    comp = wl.get("t_device_compute_s", 0.0)
    b = wl["bytes_transferred_est"]
    return {
        "predicted_link_s": b / bw + k_rtts * rtt + comp,
        "projected_pcie_s": b / PCIE_BW_BYTES_S + k_rtts * PCIE_RTT_S
        + comp,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="DEVICE_MERIT.json")
    ap.add_argument("--replay-rows", type=int, default=30_000_000)
    ap.add_argument("--blockwise-rows", type=int, default=100_000_000)
    ap.add_argument("--join-rows", type=int, default=10_000_000)
    ap.add_argument("--sql-rows", type=int, default=10_000_000)
    args = ap.parse_args()

    import jax

    device = jax.devices()[0]
    print(f"device: {device}", file=sys.stderr)
    from delta_tpu.utils.alloc import tune_allocator

    tune_allocator()

    link = measure_link(device)
    print(f"link: bw={link['bw_bytes_per_s'] / 1e6:.1f}MB/s "
          f"rtt={link['rtt_s'] * 1e3:.1f}ms", file=sys.stderr)

    out = {"device": str(device), "link": link, "workloads": {}}
    for name, fn, n in (
            ("replay_fa", wl_replay, args.replay_rows),
            ("blockwise_replay", wl_blockwise, args.blockwise_rows),
            ("merge_join", wl_merge_join, args.join_rows),
            ("sql_groupby", wl_sql_groupby, args.sql_rows),
            ("sql_join", wl_sql_join, args.sql_rows),
            ("sql_sort", wl_sql_sort, args.sql_rows),
            ("page_decode", wl_page_decode, args.sql_rows)):
        print(f"== {name} @ {n} rows", file=sys.stderr)
        try:
            wl = fn(n, device)
        except Exception as exc:
            # record the failure honestly instead of losing the run
            import traceback

            traceback.print_exc()
            out["workloads"][name] = {"n": n, "error": str(exc)[:300]}
            continue
        wl["model"] = model(link, wl)
        wl["projected_pcie_wins"] = (
            wl["model"]["projected_pcie_s"] < wl["t_host_s"])
        out["workloads"][name] = wl
        print(f"  device {wl['t_device_s']:.2f}s vs host "
              f"{wl['t_host_s']:.2f}s -> "
              f"{'DEVICE WINS' if wl['device_wins'] else 'host wins'}; "
              f"pcie projection {wl['model']['projected_pcie_s']:.2f}s",
              file=sys.stderr)

    out["any_device_win_measured"] = any(
        w.get("device_wins") for w in out["workloads"].values())
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"metric": "device_merit_wins",
                      "value": sum(bool(w.get("device_wins"))
                                   for w in out["workloads"].values()),
                      "unit": "workloads",
                      "vs_baseline": 0.0}))


if __name__ == "__main__":
    main()
