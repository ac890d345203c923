"""Benchmark: end-to-end snapshot state reconstruction (table load).

North star (BASELINE.md config 2 / SURVEY.md §6): load a 100k-commit /
10M-file `_delta_log` — LIST -> read -> parse -> replay -> aggregates —
and beat a fair host implementation of the reference's `DefaultEngine`
semantics.

The BASELINE is deliberately strong (not a strawman):
- same LIST + one preallocated parallel read into a single buffer,
- pyarrow's C++ JSON reader over that buffer (the honest stand-in for
  Jackson in `DefaultJsonHandler.java` — same class of optimized native
  columnar JSON parse),
- vectorized add/remove extraction (Arrow kernels),
- pandas factorize + numpy lexsort last-wins replay — the VECTORIZED
  formulation of `InMemoryLogReplay.scala:52` (the round-1 Python-dict
  loop is reported as a secondary diagnostic line only),
- numpy aggregates.

OURS is the real product path: `Table.for_path(...).latest_snapshot()`
with the TpuEngine — native SIMD scanner with in-scan path dictionary,
zero-copy Arrow assembly, device sort/segmented-reduce replay.

Prints ONE JSON line:
  {"metric": "e2e_snapshot_load_actions_per_sec", "value": ...,
   "unit": "actions/s", "vs_baseline": ...}

Env knobs:
  BENCH_COMMITS   (default 100_000; 100 files/commit -> 10M actions)
  BENCH_WORKDIR   (default /tmp/delta_tpu_bench; the generated log is
                   cached there across runs, keyed by
                   (commits, files/commit, seed))
  BENCH_DEVICE_TIMEOUT (seconds, default 1800)
  BENCH_KERNEL_DIAG=0 to skip the kernel-level diagnostic lines
  BENCH_SHARDED=0 to skip the 8-emulated-device sharded replay metric
  BENCH_SHARD_ROWS     rows for the sharded scaling runs (default 4M)
  BENCH_KERNEL_FLOOR   hard floor for kernel-vs-vectorized (default 0.4)
  BENCH_STRICT=1       also assert the aspirational gates (kernel >=
                       1.0x host-vectorized, sharded 1->8 scaling >= 3x)

The replay-route gate itself has its own knobs (DELTA_TPU_REPLAY_ROUTE,
DELTA_TPU_SHARDED_MIN_ROWS, DELTA_TPU_LINK_*, DELTA_TPU_H2D_CHUNK,
DELTA_TPU_REPLAY_SHARDS, DELTA_TPU_RESIDENT) — see
delta_tpu/parallel/gate.py and docs/architecture.md.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

FILES_PER_COMMIT = 100
INCREMENTAL_COMMITS = 100  # appended for the update() metric


# --------------------------------------------------------------- synth log


def synth_delta_log(path: str, commits: int, files_per_commit: int,
                    remove_fraction: float = 0.2, seed: int = 0) -> None:
    """Write a synthetic `_delta_log` shaped like a real history: every
    commit adds UUID-fresh files with stats and removes a slice of
    earlier-added ones.

    Fast path requirements at the 100k-commit / 10M-action scale:
    removal picks are swap-popped from the alive list (`alive.pop(j)`
    at a random index memmoves half of an 8M-entry list per pick —
    that made cold generation O(n^2), ~20 minutes; swap-pop is O(1)
    and order doesn't matter for a random victim), and the per-commit
    RNG draws are batched into single vectorized calls. Cold
    generation now lands well under 200s on one core."""
    rng = np.random.default_rng(seed)
    log = os.path.join(path, "_delta_log")
    os.makedirs(log, exist_ok=True)
    protocol = '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}'
    metadata = (
        '{"metaData":{"id":"bench","format":{"provider":"parquet",'
        '"options":{}},"schemaString":"{\\"type\\":\\"struct\\",'
        '\\"fields\\":[{\\"name\\":\\"x\\",\\"type\\":\\"long\\",'
        '\\"nullable\\":true,\\"metadata\\":{}}]}",'
        '"partitionColumns":[],"configuration":{}}}'
    )
    alive: list = []
    fid = 0
    n_rm = int(files_per_commit * remove_fraction)
    n_add_max = files_per_commit - n_rm
    for v in range(commits):
        lines = []
        if v == 0:
            lines.append(protocol)
            lines.append(metadata)
        k = min(n_rm, len(alive))
        if k:
            # one vectorized draw; each pick is uniform over the list
            # length at its own step (lengths shrink by one per pick)
            picks = rng.integers(
                0, np.arange(len(alive), len(alive) - k, -1))
            for j in picks:
                p = alive[j]
                alive[j] = alive[-1]
                alive.pop()
                lines.append(
                    f'{{"remove":{{"path":"{p}","deletionTimestamp":{v},'
                    f'"dataChange":true}}}}'
                )
        uuids = rng.integers(0, 1 << 60, size=n_add_max)
        for u in uuids:
            p = f"part-{fid:010d}-{u:016x}.parquet"
            fid += 1
            alive.append(p)
            lo, hi = fid * 1000, (fid + 1) * 1000
            lines.append(
                f'{{"add":{{"path":"{p}","partitionValues":{{}},'
                f'"size":1048576,"modificationTime":{v},"dataChange":true,'
                f'"stats":"{{\\"numRecords\\":1000,'
                f'\\"minValues\\":{{\\"x\\":{lo}}},'
                f'\\"maxValues\\":{{\\"x\\":{hi}}},'
                f'\\"nullCount\\":{{\\"x\\":0}}}}"}}}}'
            )
        with open(os.path.join(log, f"{v:020d}.json"), "w") as f:
            f.write("\n".join(lines) + "\n")


def ensure_log(workdir: str, commits: int, seed: int = 0) -> str:
    # the cache key is (commits, files/commit, seed); the seed suffix
    # also retires pre-swap-pop cached logs, whose removal pattern
    # differs from what the current generator would produce
    path = os.path.join(
        workdir, f"log_{commits}x{FILES_PER_COMMIT}_s{seed}")
    marker = os.path.join(
        path, "_delta_log", f"{commits - 1:020d}.json")
    if not os.path.exists(marker):
        print(f"generating {commits}-commit synthetic log...",
              file=sys.stderr)
        t0 = time.perf_counter()
        synth_delta_log(path, commits, FILES_PER_COMMIT, seed=seed)
        print(f"  generated in {time.perf_counter() - t0:.0f}s",
              file=sys.stderr)
    # the incremental phase appends commits >= `commits` and removes them
    # when done; a crashed prior run may have left strays in the cached
    # log, which would skew every later measurement
    log = os.path.join(path, "_delta_log")
    for name in os.listdir(log):
        m = re.match(r"^(\d{20})\.json$", name)
        if m and int(m.group(1)) >= commits:
            print(f"  removing stale appended commit {name}",
                  file=sys.stderr)
            os.remove(os.path.join(log, name))
    return path


def append_commits(path: str, start_version: int, k: int):
    """Append `k` synthetic commits continuing the history at
    `start_version` — the workload behind the incremental update()
    metric. Same shape as synth_delta_log commits (adds + removes of
    files added by EARLIER appended commits, so replay does real
    last-wins work). Returns (written_paths, n_actions)."""
    rng = np.random.default_rng(start_version)
    log = os.path.join(path, "_delta_log")
    alive: list = []
    written = []
    n_actions = 0
    fid = 0
    n_rm = int(FILES_PER_COMMIT * 0.2)
    for i in range(k):
        v = start_version + i
        lines = []
        if alive and n_rm:
            for _ in range(min(n_rm, len(alive))):
                j = int(rng.integers(0, len(alive)))
                p = alive[j]
                alive[j] = alive[-1]
                alive.pop()
                lines.append(
                    f'{{"remove":{{"path":"{p}","deletionTimestamp":{v},'
                    f'"dataChange":true}}}}'
                )
        for _ in range(FILES_PER_COMMIT - n_rm):
            p = f"inc-{v:010d}-{fid:06d}.parquet"
            fid += 1
            alive.append(p)
            lines.append(
                f'{{"add":{{"path":"{p}","partitionValues":{{}},'
                f'"size":1048576,"modificationTime":{v},"dataChange":true,'
                f'"stats":"{{\\"numRecords\\":1000}}"}}}}'
            )
        fp = os.path.join(log, f"{v:020d}.json")
        with open(fp, "w") as f:
            f.write("\n".join(lines) + "\n")
        written.append(fp)
        n_actions += len(lines)
    return written, n_actions


# ---------------------------------------------------------------- baseline


def baseline_load(path: str) -> tuple[float, int, int]:
    """Fair host DefaultEngine-semantics load. Returns (seconds,
    num_files, num_actions). Both sides get the same allocator tuning
    (utils/alloc.py) and both are measured warm (best of two runs) —
    on lazily-faulted VM memory a cold run is dominated by hypervisor
    page-fault costs that a long-running engine never pays."""
    from delta_tpu.engine.host import HostEngine

    eng = HostEngine()  # constructor applies the shared allocator tuning
    r1 = _baseline_once(eng, path)
    r2 = _baseline_once(eng, path)
    return min(r1, r2, key=lambda r: r[0])


def _baseline_once(eng, path: str) -> tuple[float, int, int]:
    import pandas as pd
    import pyarrow as pa

    from delta_tpu.log.segment import build_log_segment
    from delta_tpu.replay.columnar import (
        _extract_file_actions,
        _parse_buffer_generic,
        _read_commits_buffer,
    )
    from delta_tpu.utils import filenames as fn

    t0 = time.perf_counter()
    segment = build_log_segment(eng.fs, os.path.join(path, "_delta_log"))
    infos = [(fn.delta_version(f.path), f.path, f.size)
             for f in segment.deltas]
    read = _read_commits_buffer(eng, infos)
    if read is None:
        raise RuntimeError(
            "baseline read failed: listed sizes disagree with bytes read "
            f"(was the cached log under {path} modified?)")
    buf, starts, vers = read
    generic = _parse_buffer_generic(buf, starts, vers)
    if generic is None:
        raise RuntimeError(
            "baseline parse failed: row count disagrees with line "
            f"accounting for the log under {path}")
    tbl, versions, orders, _ = generic
    blocks = []
    for c in ("add", "remove"):
        b = _extract_file_actions(tbl, c, versions, orders)
        if b is not None:
            blocks.append(b)
    fa = pa.concat_tables(blocks)
    n = fa.num_rows
    paths = fa.column("path").combine_chunks()
    codes, _ = pd.factorize(paths.to_pandas(), sort=False)
    ver_np = np.asarray(fa.column("version"), np.int64)
    ord_np = np.asarray(fa.column("order"), np.int32)
    is_add = np.asarray(fa.column("is_add"), bool)
    perm = np.lexsort((ord_np, ver_np))
    shift = np.uint64(max(1, int(n - 1).bit_length()))
    k = codes[perm].astype(np.uint64) << shift
    k |= np.arange(n, dtype=np.uint64)
    srt = np.sort(k)
    kk = srt >> shift
    boundary = np.empty(n, bool)
    boundary[:-1] = kk[:-1] != kk[1:]
    boundary[-1] = True
    winners = perm[(srt & np.uint64((1 << int(shift)) - 1))[boundary]
                   .astype(np.int64)]
    live_idx = winners[is_add[winners]]
    sizes = np.asarray(fa.column("size").combine_chunks().fill_null(0),
                       np.int64)
    total_size = int(sizes[live_idx].sum())
    dt = time.perf_counter() - t0
    assert total_size >= 0
    return dt, int(len(live_idx)), n


# ------------------------------------------------------------- device side


_DEVICE_CODE = r"""
import os, sys, time, json, hashlib
sys.path.insert(0, {repo!r})
import jax
jax.devices()  # device init outside the timed region
import pyarrow as pa
import bench
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.table import Table
from delta_tpu.replay.columnar import clear_parse_cache
out = []
tbl = snap = None
for run in range(3):
    if snap is not None:
        del snap
    t0 = time.perf_counter()
    tbl = Table.for_path({path!r}, TpuEngine())
    snap = tbl.latest_snapshot()
    nf = snap.num_files
    sz = snap.state.size_in_bytes
    out.append(time.perf_counter() - t0)
    print(f"  device e2e run{{run}}: {{out[-1]:.1f}}s files={{nf}}",
          file=sys.stderr)
result = {{"cold": out[0], "warm": min(out), "files": nf}}

# ---- incremental update(): append commits, advance, verify vs cold ----
def live_digest(s):
    st = s.state  # raw columns only: never trigger the stats decode
    paths = (st.file_actions_raw.column("path")
             .filter(pa.array(st.live_mask)).to_pylist())
    h = hashlib.sha256()
    for p in sorted(paths):
        h.update(p.encode())
    return (s.version, st.num_files, st.size_in_bytes,
            int(st.tombstone_mask.sum()), h.hexdigest())

base_v = snap.version
written, n_appended = bench.append_commits(
    {path!r}, base_v + 1, bench.INCREMENTAL_COMMITS)
try:
    t0 = time.perf_counter()
    snap2 = tbl.update()
    upd_s = time.perf_counter() - t0
    nf2 = snap2.num_files
    assert snap2.version == base_v + bench.INCREMENTAL_COMMITS, \
        (snap2.version, base_v)
    print(f"  device update(): {{upd_s * 1000:.0f}}ms for "
          f"{{n_appended}} appended actions, files={{nf2}}",
          file=sys.stderr)
    del snap  # keep peak memory at two materialized states
    clear_parse_cache()
    t0 = time.perf_counter()
    cold = Table.for_path({path!r}, TpuEngine()).latest_snapshot()
    cold_nf = cold.num_files
    cold_s = time.perf_counter() - t0
    print(f"  device cold reload at v{{cold.version}}: {{cold_s:.1f}}s",
          file=sys.stderr)
    parity = live_digest(snap2) == live_digest(cold)
    if not parity:
        print(f"  INCREMENTAL PARITY MISMATCH: {{live_digest(snap2)}} vs "
              f"{{live_digest(cold)}}", file=sys.stderr)
    result.update(update_s=upd_s, update_actions=n_appended,
                  update_files=nf2, cold_after_append_s=cold_s,
                  parity=parity)
finally:
    for fp in written:
        try:
            os.remove(fp)
        except OSError:
            pass
print("DEVICE_RESULT=" + json.dumps(result))
"""


def device_load_subprocess(path: str, timeout_s: int) -> dict:
    """Run the product load in a child process so a wedged accelerator
    runtime can't hang the driver."""
    repo = os.path.dirname(os.path.abspath(__file__))
    code = _DEVICE_CODE.format(repo=repo, path=path)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=repo,
        capture_output=True, text=True, timeout=timeout_s,
    )
    for line in proc.stderr.splitlines():
        if "WARNING" not in line:
            print(line, file=sys.stderr)
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE_RESULT="):
            return json.loads(line.split("=", 1)[1])
    raise RuntimeError(
        f"device load failed (rc={proc.returncode}): {proc.stderr[-800:]}")


# ------------------------------------------------------- kernel diagnostics


def synth_history(n_actions: int, seed: int = 0):
    """Synthetic pre-encoded action stream (see round-1 bench): ~85% of
    rows introduce a fresh first-appearance path code, ~15% reference an
    earlier one, ~2% carry a DV lane."""
    rng = np.random.default_rng(seed)
    is_new = rng.random(n_actions) < 0.85
    is_new[0] = True
    new_count = np.cumsum(is_new)
    back_ref = (rng.random(n_actions) * (new_count - 1)).astype(np.int64)
    pk = np.where(is_new, new_count - 1, back_ref).astype(np.uint32)
    is_add = is_new.copy()
    readd = (~is_new) & (rng.random(n_actions) < 0.15)
    is_add |= readd
    dk = np.zeros(n_actions, dtype=np.uint32)
    dv_rows = rng.random(n_actions) < 0.02
    dk[dv_rows] = rng.integers(1, 4, int(dv_rows.sum())).astype(np.uint32)
    n_commits = max(2, n_actions // 100)
    ver = np.sort(rng.integers(0, n_commits, n_actions)).astype(np.int32)
    change = np.nonzero(np.diff(ver))[0] + 1
    starts = np.concatenate([[0], change])
    lens = np.diff(np.concatenate([starts, [n_actions]]))
    order = (np.arange(n_actions) - np.repeat(starts, lens)).astype(np.int32)
    return pk, dk, ver, order, is_add


def kernel_baseline_vectorized(pk, dk, is_add) -> tuple[float, int]:
    """Vectorized numpy host replay (lexsort + last-wins per key) — the
    honest host-hardware formulation of the same algorithm the device
    kernel runs (VERDICT round-1 item 1a)."""
    n = len(pk)
    t0 = time.perf_counter()
    key = pk.astype(np.uint64) * np.uint64(int(dk.max()) + 1) + dk
    shift = np.uint64(max(1, int(n - 1).bit_length()))
    k = (key << shift) | np.arange(n, dtype=np.uint64)
    srt = np.sort(k)
    kk = srt >> shift
    boundary = np.empty(n, bool)
    boundary[:-1] = kk[:-1] != kk[1:]
    boundary[-1] = True
    idx = (srt & np.uint64((1 << int(shift)) - 1))[boundary].astype(np.int64)
    live = int(is_add[idx].sum())
    return time.perf_counter() - t0, live


def kernel_baseline_dict(pk, dk, is_add) -> tuple[float, int]:
    """Round-1 sequential Python-dict replay — secondary diagnostic."""
    t0 = time.perf_counter()
    winner = {}
    pk_l = pk.tolist()
    dk_l = dk.tolist()
    add_l = is_add.tolist()
    for i in range(len(pk_l)):
        winner[(pk_l[i], dk_l[i])] = i
    live = sum(1 for i in winner.values() if add_l[i])
    return time.perf_counter() - t0, live


_KERNEL_DEVICE_CODE = r"""
import sys, time, json
sys.path.insert(0, {repo!r})
import numpy as np
import jax
jax.devices()
import bench
from delta_tpu.ops.replay import replay_select
pk, dk, ver, order, is_add = bench.synth_history({n})
replay_select([pk, dk], ver, order, is_add)  # compile warmup
times = []
for _ in range(3):
    t0 = time.perf_counter()
    live, tomb = replay_select([pk, dk], ver, order, is_add)
    times.append(time.perf_counter() - t0)
print("KERNEL_RESULT=" + json.dumps({{"secs": min(times),
                                      "live": int(live.sum()),
                                      "backend": jax.default_backend()}}))
"""


def kernel_diagnostics(n: int, timeout_s: int) -> None:
    """Single-chip replay kernel vs the honest host baselines. Emits the
    `replay_kernel_vs_host_vectorized` metric: BENCH_KERNEL_FLOOR
    (default 0.4) is a hard regression floor; the >=1.0x target is
    recorded via `gate_ok` and asserted only under BENCH_STRICT=1."""
    pk, dk, ver, order, is_add = synth_history(n)
    vec_s, vec_live = kernel_baseline_vectorized(pk, dk, is_add)
    dict_s, dict_live = kernel_baseline_dict(pk, dk, is_add)
    assert vec_live == dict_live
    repo = os.path.dirname(os.path.abspath(__file__))
    code = _KERNEL_DEVICE_CODE.format(repo=repo, n=n)
    dev_s = None
    backend = None
    try:
        proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                              capture_output=True, text=True,
                              timeout=timeout_s)
        for line in proc.stdout.splitlines():
            if line.startswith("KERNEL_RESULT="):
                r = json.loads(line.split("=", 1)[1])
                assert r["live"] == vec_live, (r["live"], vec_live)
                dev_s = r["secs"]
                backend = r.get("backend")
    except Exception as e:
        print(f"kernel diagnostic device run failed: {e}", file=sys.stderr)
    print(f"kernel diag @{n} rows: numpy-vectorized {n / vec_s / 1e6:.1f}M/s"
          f"  python-dict {n / dict_s / 1e6:.2f}M/s"
          + (f"  device[{backend}] {n / dev_s / 1e6:.1f}M/s"
               f"  (vs vectorized {vec_s / dev_s:.2f}x,"
               f" vs dict {dict_s / dev_s:.1f}x)" if dev_s else ""),
          file=sys.stderr)
    ratio = (vec_s / dev_s) if dev_s else 0.0
    gate_ok = ratio >= 1.0
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "replay_kernel_vs_host_vectorized",
        "value": round(ratio, 3),
        "unit": "x",
        "rows": n,
        "backend": backend,
        "host_vectorized_m_per_s": round(n / vec_s / 1e6, 2),
        "device_m_per_s": round(n / dev_s / 1e6, 2) if dev_s else 0.0,
        "gate_ok": gate_ok,
    }))
    # the floor guards the accelerator path (where transfer economics
    # decide the ratio); an XLA-CPU "device" losing a sort race to
    # numpy on the same silicon is expected, not a regression
    if dev_s and backend not in (None, "cpu"):
        floor = float(os.environ.get("BENCH_KERNEL_FLOOR", 0.4))
        assert ratio >= floor, (
            f"single-chip kernel regressed to {ratio:.2f}x the "
            f"host-vectorized baseline (floor {floor}x)")
        if os.environ.get("BENCH_STRICT") == "1":
            assert gate_ok, (
                f"BENCH_STRICT: kernel {ratio:.2f}x < 1.0x host-vectorized")


# ------------------------------------------------------- sharded replay


_SHARD_DEVICE_CODE = r"""
import sys, time, json
sys.path.insert(0, {repo!r})
import numpy as np
import jax
jax.devices()
import bench
from jax.sharding import NamedSharding, PartitionSpec as P
from delta_tpu.parallel import sharded_replay as sr
from delta_tpu.parallel.mesh import REPLAY_AXIS, make_mesh

rows = {rows}
pk, dk, ver, order, is_add = bench.synth_history(rows)
is_new = sr.derive_fa_flags(pk)
out = {{}}
for s in (1, 2, 8):
    mesh = make_mesh(n_devices=s)
    spec = NamedSharding(mesh, P(REPLAY_AXIS, None))
    fa = sr.route_to_shards_fa(pk, dk, is_new, is_add, s)
    has_sub = fa.sub_radix > 1
    ops = [fa.flag_words, *fa.ref_planes]
    if has_sub:
        ops += [np.uint32(fa.sub_radix), fa.sub_idx, fa.sub_val]
    ops += [fa.n_real, fa.add_words]
    device_ops = tuple(
        o if np.isscalar(o) or o.ndim == 0 else jax.device_put(o, spec)
        for o in ops)
    fn = sr.build_sharded_replay_fa_fn(mesh, len(fa.ref_planes), has_sub)
    w, nl = fn(*device_ops)          # compile + warm outside the clock
    np.asarray(w)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        w, nl = fn(*device_ops)
        np.asarray(w)                # D2H of the packed winner words
        times.append(time.perf_counter() - t0)
    out[str(s)] = {{"secs": min(times), "live": int(nl)}}
    print(f"  sharded replay S={{s}}: {{min(times) * 1000:.0f}}ms",
          file=sys.stderr)
    if s == 8:
        # per-chip critical path: one shard's slice of the S=8 routing
        # on a single device. Emulated devices time-share the host's
        # cores, so on a core-starved box wall-clock hides the real
        # scaling; real multi-chip wall-clock follows this number.
        mesh1 = make_mesh(n_devices=1)
        spec1 = NamedSharding(mesh1, P(REPLAY_AXIS, None))
        ops1 = tuple(
            o if np.isscalar(o) or o.ndim == 0
            else jax.device_put(np.ascontiguousarray(o[:1]), spec1)
            for o in ops)
        fn1 = sr.build_sharded_replay_fa_fn(
            mesh1, len(fa.ref_planes), has_sub)
        w, _ = fn1(*ops1)
        np.asarray(w)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            w, _ = fn1(*ops1)
            np.asarray(w)
            times.append(time.perf_counter() - t0)
        out["critical_path_8"] = {{"secs": min(times)}}
        print(f"  per-chip critical path at S=8: "
              f"{{min(times) * 1000:.0f}}ms", file=sys.stderr)
print("SHARD_RESULT=" + json.dumps(out))
"""


def sharded_metrics(timeout_s: int) -> None:
    """Per-chip scaling of the sharded replay phase on 8 emulated host
    devices: route once per shard count, then time the compiled
    shard_map kernel (per-shard sort + winner pack + scalar psum)
    including the packed-words D2H. Emits
    `sharded_replay_actions_per_sec` with the 1/2/8-shard breakdown;
    the >=3x 1->8 scaling target is recorded via `gate_ok` and
    asserted only under BENCH_STRICT=1."""
    rows = int(os.environ.get("BENCH_SHARD_ROWS", 4_000_000))
    repo = os.path.dirname(os.path.abspath(__file__))
    code = _SHARD_DEVICE_CODE.format(repo=repo, rows=rows)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    result = None
    try:
        proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                              capture_output=True, text=True,
                              timeout=timeout_s, env=env)
        for line in proc.stderr.splitlines():
            if "WARNING" not in line:
                print(line, file=sys.stderr)
        for line in proc.stdout.splitlines():
            if line.startswith("SHARD_RESULT="):
                result = json.loads(line.split("=", 1)[1])
        if result is None:
            raise RuntimeError(
                f"no SHARD_RESULT (rc={proc.returncode}): "
                f"{proc.stderr[-400:]}")
        lives = {result[k]["live"] for k in ("1", "2", "8")}
        assert len(lives) == 1, f"live-count disagreement across S: {result}"
        pk, dk, _, _, is_add = synth_history(rows)
        _, vec_live = kernel_baseline_vectorized(pk, dk, is_add)
        assert lives == {vec_live}, (lives, vec_live)
    except Exception as e:
        print(f"sharded replay metric unavailable: {e}", file=sys.stderr)
        print(json.dumps({
            "metric": "sharded_replay_actions_per_sec",
            "value": 0.0, "unit": "actions/s", "gate_ok": False,
        }))
        return
    s1, s2, s8 = (result[k]["secs"] for k in ("1", "2", "8"))
    cp8 = result.get("critical_path_8", {}).get("secs")
    cores = os.cpu_count() or 1
    scaling_wall = s1 / s8
    scaling_cp = (s1 / cp8) if cp8 else 0.0
    # 8 emulated devices time-share the host's cores: on a box with
    # fewer cores than shards, wall-clock can't show the scaling (the
    # work is real and serialized); the per-chip critical path is what
    # real multi-chip wall-clock follows, so the gate falls back to it
    gate_ok = (scaling_wall >= 3.0
               or (cores < 8 and scaling_cp >= 3.0))
    print(f"sharded replay @{rows} rows ({cores}-core host, emulated "
          f"devices): S=1 {s1 * 1000:.0f}ms  S=2 {s2 * 1000:.0f}ms  "
          f"S=8 {s8 * 1000:.0f}ms  wall scaling {scaling_wall:.2f}x"
          + (f"  per-chip critical path {cp8 * 1000:.0f}ms "
             f"({scaling_cp:.1f}x)" if cp8 else ""),
          file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "sharded_replay_actions_per_sec",
        "value": round(rows / s8, 1),
        "unit": "actions/s",
        "rows": rows,
        "host_cores": cores,
        "shard_seconds": {k: round(result[k]["secs"], 4)
                          for k in ("1", "2", "8")},
        "critical_path_8_seconds": round(cp8, 4) if cp8 else None,
        "scaling_1_to_8_wall": round(scaling_wall, 2),
        "scaling_1_to_8_critical_path": round(scaling_cp, 2),
        "gate_ok": gate_ok,
    }))
    if os.environ.get("BENCH_STRICT") == "1":
        assert gate_ok, (
            f"BENCH_STRICT: sharded 1->8 scaling {scaling_wall:.2f}x wall "
            f"/ {scaling_cp:.2f}x critical-path < 3.0x")


# --------------------------------------------------------------------- main


def analyzer_scan_metric():
    """delta-lint full-repo scan time: a secondary metric so an
    accidentally quadratic rule (the lint runs in tier-1 CI) shows up
    as a >10s regression here instead of as slow test runs. Also times
    the ``--changed`` cache-hit path (must stay sub-second: that is the
    CI re-run hot path) and reports the unsuppressed finding count —
    the repo's contract is zero, so any nonzero value is a regression
    even when the scan stays fast."""
    import tempfile

    import delta_tpu
    from delta_tpu.tools.analyzer import analyze_paths
    from delta_tpu.tools.analyzer.cache import analyze_paths_cached

    pkg = os.path.dirname(os.path.abspath(delta_tpu.__file__))
    t0 = time.perf_counter()
    report = analyze_paths([pkg], root=os.path.dirname(pkg))
    scan_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "cache.json")
        analyze_paths_cached([pkg], root=os.path.dirname(pkg),
                             cache_path=cache)  # populate
        t1 = time.perf_counter()
        cached_report, stats = analyze_paths_cached(
            [pkg], root=os.path.dirname(pkg), cache_path=cache)
        cached_s = time.perf_counter() - t1
    cache_ok = (stats["cache"] == "hit"
                and len(cached_report.findings) == len(report.findings))

    print(f"delta-lint repo scan: {scan_s:.2f}s over "
          f"{report.files_scanned} files, {len(report.findings)} "
          f"finding(s), {len(report.suppressed)} suppressed; "
          f"cached re-scan {cached_s:.3f}s ({stats['cache']})",
          file=sys.stderr)
    print(json.dumps({
        "metric": "analyzer_findings_total",
        "value": len(report.findings),
        "unit": "findings",
        "suppressed": len(report.suppressed),
        "by_rule": report.by_rule(),
        "clean": report.ok,
    }))
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "analyzer_repo_scan_seconds",
        "value": round(scan_s, 3),
        "unit": "s",
        "files": report.files_scanned,
        "cached_rescan_seconds": round(cached_s, 3),
        "cache_ok": cache_ok,
        "clean": report.ok,
    }))


def trace_overhead_metric(workdir: str) -> None:
    """delta-trace overhead: snapshot-load with DELTA_TPU_TRACE=on vs
    off on a small host-engine log, plus a direct measurement of the
    disabled fast path (the cost every untraced production call pays).

    The asserted number is the DISABLED path: per-call no-op span()
    cost x the span count an identical traced load emits, as a fraction
    of the untraced load time. The on-vs-off wall delta is printed as a
    diagnostic only (sub-second loads make it noisy). One traced run is
    exported as a Chrome trace artifact next to the cached log."""
    from delta_tpu import obs
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.replay.columnar import clear_parse_cache
    from delta_tpu.table import Table

    commits = int(os.environ.get("BENCH_TRACE_COMMITS", 500))
    path = ensure_log(workdir, commits)

    def load(mode: str) -> float:
        obs.set_trace_mode(mode)
        clear_parse_cache()
        eng = HostEngine()
        t0 = time.perf_counter()
        snap = Table.for_path(path, eng).latest_snapshot()
        _ = snap.state
        return time.perf_counter() - t0

    try:
        load("off")  # warm page cache / allocator before either side
        off_s = min(load("off"), load("off"))
        obs.reset_trace_buffer()
        on_s = min(load("on"), load("on"))
        spans = obs.get_finished_spans()
        n_spans = len(spans) // 2  # two ON loads in the buffer

        artifact = os.path.join(workdir, "snapshot_load_trace.json")
        from delta_tpu.obs.export import write_chrome_trace

        half = spans[len(spans) // 2:]  # the second (warmer) load
        write_chrome_trace(artifact, half)

        # disabled fast path, measured directly
        obs.set_trace_mode("off")
        n_calls = 200_000
        t0 = time.perf_counter()
        for _ in range(n_calls):
            with obs.span("bench.noop", table="x"):
                pass
        noop_per_call_s = (time.perf_counter() - t0) / n_calls
        overhead_pct = 100.0 * (noop_per_call_s * n_spans) / off_s
        on_vs_off_pct = 100.0 * (on_s - off_s) / off_s

        print(f"trace overhead @{commits} commits: off {off_s:.3f}s, "
              f"on {on_s:.3f}s ({on_vs_off_pct:+.1f}%), {n_spans} spans, "
              f"no-op span {noop_per_call_s * 1e9:.0f}ns/call -> disabled-"
              f"path overhead {overhead_pct:.3f}%", file=sys.stderr)
        print(f"chrome trace artifact: {artifact}", file=sys.stderr)
        assert overhead_pct < 2.0, (
            f"disabled-path trace overhead {overhead_pct:.2f}% >= 2%")
        # secondary metric line (the driver reads the LAST line only)
        print(json.dumps({
            "metric": "trace_overhead_pct",
            "value": round(overhead_pct, 4),
            "unit": "%",
            "on_vs_off_pct": round(on_vs_off_pct, 2),
            "spans_per_load": n_spans,
            "noop_span_ns": round(noop_per_call_s * 1e9, 1),
            "chrome_trace": artifact,
        }))
    finally:
        obs.set_trace_mode("off")
        obs.reset_trace_buffer()


def checkpoint_read_metric(workdir: str) -> None:
    """Checkpoint-path read throughput, gated: time cold loads that
    reconstruct state from a multipart checkpoint on BOTH routes — the
    host Arrow reader and the forced device page-decode
    (log/page_decode.py one-dispatch-per-part plan) — over the same
    log. The emitted headline value is the better route's rate, gated
    to 0 when the routes' reconstructed states diverge or the device
    route was vacuous (no part actually decoded on device, or any part
    fell back); capture conditions ride on the metric line so
    comparable runs can be grouped."""
    from delta_tpu import obs
    from delta_tpu.config import settings
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.log.checkpointer import write_checkpoint
    from delta_tpu.obs.registry import metrics_snapshot, registry
    from delta_tpu.replay.columnar import clear_parse_cache
    from delta_tpu.table import Table

    commits = int(os.environ.get("BENCH_CHECKPOINT_COMMITS", 2000))
    path = os.path.join(
        workdir, f"ckpt_log_{commits}x{FILES_PER_COMMIT}_s0")
    log = os.path.join(path, "_delta_log")
    if not os.path.exists(os.path.join(log, "_last_checkpoint")):
        print(f"generating {commits}-commit checkpointed log...",
              file=sys.stderr)
        synth_delta_log(path, commits, FILES_PER_COMMIT)
        table = Table.for_path(path, HostEngine())
        snap = table.latest_snapshot()
        old = settings.checkpoint_part_size
        # ~8 parts so the batched read path has real overlap to exploit
        settings.checkpoint_part_size = max(1, snap.num_files // 8)
        try:
            write_checkpoint(table.engine, snap)
        finally:
            settings.checkpoint_part_size = old

    def load() -> tuple[float, object]:
        clear_parse_cache()
        t0 = time.perf_counter()
        snap = Table.for_path(path, TpuEngine()).latest_snapshot()
        n = snap.state.file_actions.num_rows
        return time.perf_counter() - t0, snap

    def digest(snap) -> tuple:
        t = snap.state.add_files_table
        return (snap.num_files,
                tuple(sorted(t.column("path").to_pylist())),
                tuple(sorted(t.column("size").to_pylist())))

    os.environ["DELTA_TPU_DEVICE_DECODE"] = "off"
    try:
        load()  # warm page cache before any timed run
        (s1, host_snap), (s2, _) = load(), load()
        host_s = min(s1, s2)
        os.environ["DELTA_TPU_DEVICE_DECODE"] = "force"
        load()  # device warm-up (compile the decode shape buckets)
        registry().reset()
        (s3, dev_snap), (s4, _) = load(), load()
        dev_s = min(s3, s4)
    finally:
        del os.environ["DELTA_TPU_DEVICE_DECODE"]

    n = host_snap.state.file_actions.num_rows
    counters = metrics_snapshot()["counters"]
    dev_parts = counters.get("decode.device_parts", 0)
    dev_fallbacks = counters.get("decode.device_fallbacks", 0)
    # parity + non-vacuity gates: the device number only counts if the
    # device route really ran every part and reproduced the host state
    parity = digest(host_snap) == digest(dev_snap)
    vacuous = dev_parts == 0 or dev_fallbacks > 0
    best_s = host_s if vacuous else min(host_s, dev_s)
    n_parts = len([f for f in os.listdir(log) if ".checkpoint" in f])
    print(f"checkpoint read @{commits} commits: host {host_s:.2f}s, "
          f"device {dev_s:.2f}s for {n} actions across {n_parts} "
          f"part file(s) ({n / best_s / 1e6:.2f}M actions/s, "
          f"device_parts={dev_parts}, fallbacks={dev_fallbacks}, "
          f"parity={'OK' if parity else 'MISMATCH'})", file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "checkpoint_read_actions_per_sec",
        "value": round(n / best_s, 1) if parity else 0.0,
        "unit": "actions/s",
        "actions": n,
        "parts": n_parts,
        "host_seconds": round(host_s, 3),
        "device_seconds": round(dev_s, 3),
        "vs_host": round(host_s / dev_s, 3) if parity else 0.0,
        "device_parts": int(dev_parts),
        "device_fallbacks": int(dev_fallbacks),
        "conditions": obs.capture_conditions(cache_state="warm"),
    }))


def checkpoint_write_metric(workdir: str) -> None:
    """Checkpoint WRITE throughput + incremental reuse: over a
    dedicated synth log, time a fresh multipart checkpoint through the
    serialize→upload funnel (the profitability gate stands down to the
    serial pool path on this local workdir by design — recorded via
    `pipelined`), assert the written checkpoint reloads to the same
    state as the live log, then append two add-only commits and
    measure how many file parts the second, incremental checkpoint
    reuses from the first instead of re-serializing."""
    import hashlib

    from delta_tpu import obs
    from delta_tpu.config import settings
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.log.checkpointer import write_checkpoint
    from delta_tpu.log.last_checkpoint import read_last_checkpoint
    from delta_tpu.replay.columnar import clear_parse_cache
    from delta_tpu.table import Table
    from delta_tpu.write import ckpt_pipeline

    commits = int(os.environ.get("BENCH_CKPT_WRITE_COMMITS", 500))
    path = os.path.join(
        workdir, f"ckpt_write_log_{commits}x{FILES_PER_COMMIT}_s0")
    log = os.path.join(path, "_delta_log")
    if not os.path.exists(os.path.join(log, f"{commits - 1:020d}.json")):
        print(f"generating {commits}-commit write-bench log...",
              file=sys.stderr)
        synth_delta_log(path, commits, FILES_PER_COMMIT)
    # restore the cached log to a bare commit history: a previous run's
    # checkpoint would turn the timed write into a put-if-absent no-op,
    # and its appended commits would shift this run's reuse arithmetic
    for f in os.listdir(log):
        if ".checkpoint" in f or f == "_last_checkpoint":
            os.remove(os.path.join(log, f))
        elif (f.endswith(".json") and f[:-5].isdigit()
              and int(f[:-5]) >= commits):
            os.remove(os.path.join(log, f))

    def digest() -> tuple:
        clear_parse_cache()
        snap = Table.for_path(path, HostEngine()).latest_snapshot()
        at = snap.state.add_files_table
        h = hashlib.sha1()
        for row in sorted(zip(at.column("path").to_pylist(),
                              at.column("size").to_pylist())):
            h.update(repr(row).encode())
        return snap.version, snap.state.num_files, h.hexdigest()

    eng = HostEngine()
    clear_parse_cache()
    snap = Table.for_path(path, eng).latest_snapshot()
    live = digest()
    old = settings.checkpoint_part_size
    # ~8 file parts so both the funnel and the reuse split have real
    # part structure to work with
    settings.checkpoint_part_size = max(1, snap.state.num_files // 8)
    bytes_c = obs.counter("checkpoint.bytes_written")
    reused_c = obs.counter("checkpoint.parts_reused")
    try:
        pipelined = ckpt_pipeline.profitable(eng, log, 9)
        b0 = bytes_c.value
        t0 = time.perf_counter()
        info = write_checkpoint(eng, snap)
        write_s = time.perf_counter() - t0
        nbytes = bytes_c.value - b0
        parity_ok = digest() == live  # reload now resolves via the hint
        gbps = nbytes / write_s / 1e9
        n_parts = len(info.partManifest["parts"]) if info.partManifest else 0
        print(f"checkpoint write @{commits} commits: {nbytes / 1e6:.1f}MB "
              f"in {write_s:.2f}s ({gbps:.3f}GB/s) across {n_parts} file "
              f"part(s), pipelined={pipelined}, parity_ok={parity_ok}",
              file=sys.stderr)
        # secondary metric line (the driver reads the LAST line only)
        print(json.dumps({
            "metric": "checkpoint_write_gbps",
            "value": round(gbps, 4),
            "unit": "GB/s",
            "bytes": nbytes,
            "seconds": round(write_s, 3),
            "file_parts": n_parts,
            "pipelined": pipelined,
            "gate_ok": parity_ok,
        }))
        if os.environ.get("BENCH_STRICT") == "1":
            assert parity_ok, (
                "BENCH_STRICT: checkpoint reload digest != live digest")

        # append-only growth, then an incremental checkpoint seeded
        # with the previous hint's part manifest
        for v in (commits, commits + 1):
            lines = [
                f'{{"add":{{"path":"inc-{v:06d}-{i:04d}.parquet",'
                f'"partitionValues":{{}},"size":1048576,'
                f'"modificationTime":{v},"dataChange":true}}}}'
                for i in range(FILES_PER_COMMIT)
            ]
            with open(os.path.join(log, f"{v:020d}.json"), "w") as fh:
                fh.write("\n".join(lines) + "\n")
        clear_parse_cache()
        snap2 = Table.for_path(path, eng).latest_snapshot()
        live2 = digest()
        prev = read_last_checkpoint(eng.fs, log)
        r0 = reused_c.value
        info2 = write_checkpoint(eng, snap2, prev_info=prev)
        reused = reused_c.value - r0
        total = (len(info2.partManifest["parts"])
                 if info2.partManifest else 0)
        reuse_pct = 100.0 * reused / total if total else 0.0
        parity2_ok = digest() == live2
        print(f"incremental checkpoint: reused {reused}/{total} file "
              f"part(s) ({reuse_pct:.1f}%), parity_ok={parity2_ok}",
              file=sys.stderr)
        print(json.dumps({
            "metric": "incremental_checkpoint_reuse_pct",
            "value": round(reuse_pct, 1),
            "unit": "%",
            "parts_reused": reused,
            "file_parts": total,
            "gate_ok": bool(reuse_pct > 0.0 and parity2_ok),
        }))
        if os.environ.get("BENCH_STRICT") == "1":
            assert parity2_ok, (
                "BENCH_STRICT: incremental checkpoint reload digest "
                "!= live digest")
            assert reuse_pct > 0.0, (
                "BENCH_STRICT: append-only workload reused no parts")
    finally:
        settings.checkpoint_part_size = old


def retry_overhead_metric(workdir: str) -> None:
    """delta-resilience overhead on the fault-free path: every storage
    hop runs through `io_call(endpoint, fn)` (breaker check + retry
    closure), so the cost every healthy production call pays is that
    wrapper's no-fault overhead. Asserted the same way as the trace
    metric: per-call wrapper cost x the storage-call count of a cold
    snapshot load, as a fraction of the load time."""
    from delta_tpu import obs
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.replay.columnar import clear_parse_cache
    from delta_tpu.resilience import io_call, reset as resilience_reset
    from delta_tpu.table import Table

    commits = int(os.environ.get("BENCH_TRACE_COMMITS", 500))
    path = ensure_log(workdir, commits)

    def load() -> float:
        clear_parse_cache()
        eng = HostEngine()
        t0 = time.perf_counter()
        snap = Table.for_path(path, eng).latest_snapshot()
        _ = snap.state
        return time.perf_counter() - t0

    load()  # warm page cache / allocator
    reads = obs.counter("storage.read.calls")
    lists = obs.counter("storage.list.calls")
    before = reads.value + lists.value
    load_s = min(load(), load())
    n_io = (reads.value + lists.value - before) // 2  # two timed loads

    # the wrapped-vs-bare closure cost, measured directly
    resilience_reset()

    def fn() -> None:
        return None

    n_calls = 200_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        fn()
    bare_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_calls):
        io_call("bench-noop", fn)
    wrapped_s = time.perf_counter() - t0
    per_call_s = max(0.0, (wrapped_s - bare_s) / n_calls)
    overhead_pct = 100.0 * (per_call_s * n_io) / load_s

    print(f"retry overhead @{commits} commits: load {load_s:.3f}s, "
          f"{n_io} storage calls, io_call wrapper "
          f"{per_call_s * 1e9:.0f}ns/call -> fault-free-path overhead "
          f"{overhead_pct:.3f}%", file=sys.stderr)
    assert overhead_pct < 2.0, (
        f"fault-free retry-path overhead {overhead_pct:.2f}% >= 2%")
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "retry_overhead_pct",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "storage_calls_per_load": n_io,
        "io_call_ns": round(per_call_s * 1e9, 1),
    }))


def chaos_recovery_metric() -> None:
    """Commit throughput under a fixed seeded chaos schedule: transient
    errors + torn sidecar writes on an in-memory store, absorbed by the
    shared RetryPolicy. Measures how fast the commit path recovers, not
    raw storage speed (backoff sleeps are shrunk via the env knobs so
    the number tracks retry machinery, not wall-clock naps)."""
    import pyarrow as pa

    from delta_tpu.engine.host import HostEngine
    from delta_tpu.models.actions import AddFile
    from delta_tpu.resilience import (ChaosSchedule, ChaosStore,
                                      reset as resilience_reset)
    from delta_tpu.storage.logstore import InMemoryLogStore
    from delta_tpu.table import Table

    n_commits = int(os.environ.get("BENCH_CHAOS_COMMITS", 80))
    store = ChaosStore(
        InMemoryLogStore(),
        ChaosSchedule(seed=42, error_rate=0.05, torn_write_rate=0.25),
        sleep=lambda s: None)
    eng = HostEngine(store_resolver=lambda p: store)
    overrides = {"DELTA_TPU_RETRY_BASE_MS": "1",
                 "DELTA_TPU_RETRY_CAP_MS": "5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    resilience_reset()
    try:
        import delta_tpu.api as dta

        path = "memory://bench-chaos/tbl"
        dta.write_table(path, pa.table({"x": pa.array([0], type=pa.int64())}),
                        engine=eng)
        t = Table.for_path(path, eng)
        t0 = time.perf_counter()
        for i in range(n_commits):
            txn = t.create_transaction_builder().build()
            txn.add_file(AddFile(
                path=f"bench-{i}.parquet", partitionValues={}, size=100 + i,
                modificationTime=1000 + i, dataChange=True))
            txn.commit()
        chaos_s = time.perf_counter() - t0
        assert t.latest_snapshot().version == n_commits, \
            "chaos bench lost a commit"
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience_reset()

    rate = n_commits / chaos_s
    print(f"chaos recovery @seed 42: {n_commits} commits in "
          f"{chaos_s:.2f}s under {store.fault_counts} -> "
          f"{rate:.0f} commits/s", file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "chaos_recovery_commits_per_sec",
        "value": round(rate, 1),
        "unit": "commits/s",
        "commits": n_commits,
        "faults": dict(store.fault_counts),
    }))


def device_chaos_soak_metric() -> None:
    """Workload throughput under seeded device-fault chaos at the
    dispatch funnel (dispatch errors, simulated RESOURCE_EXHAUSTED,
    transfer stalls, recompile storms). Runs the same five-route
    workload fault-free and under chaos, verifies bit-identical
    convergence, and reports the chaos-run rate — how fast the
    absorb/shed/host-twin machinery recovers, not raw device speed
    (injected stalls sleep zero seconds)."""
    import numpy as np
    import pyarrow as pa

    import delta_tpu.api as dta
    from delta_tpu import obs as _obs
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.expressions import col, lit
    from delta_tpu.resilience import reset as resilience_reset
    from delta_tpu.resilience.device_chaos import (ChaosEngine,
                                                   DeviceChaosSchedule)
    from delta_tpu.sql import sql as _sql
    from delta_tpu.tables import Table

    rows = int(os.environ.get("BENCH_DEVICE_CHAOS_ROWS", 2000))

    def engine():
        eng = TpuEngine()
        eng.use_device_parse = True
        eng.use_device_decode = True
        eng.use_device_skip = True
        eng.use_device_sql = True
        return eng

    def batch(start, n):
        x = np.arange(start, start + n, dtype=np.int64)
        return pa.table({"x": x, "g": x % 7})

    def workload(eng, path):
        dta.write_table(path, batch(0, rows), engine=eng)
        for b in range(1, 4):
            dta.write_table(path, batch(b * rows, rows), engine=eng,
                            mode="append")
        Table.for_path(path, eng).checkpoint()
        for b in range(4, 6):
            dta.write_table(path, batch(b * rows, rows), engine=eng,
                            mode="append")
        snap = Table.for_path(path, eng).latest_snapshot()
        filtered = dta.read_table(
            path, engine=eng, filter=col("x") > lit(9 * rows // 2))
        agg = _sql(f"SELECT g, SUM(x) AS s, COUNT(*) AS c "
                   f"FROM '{path}' GROUP BY g ORDER BY g", engine=eng)
        full = dta.read_table(path, engine=eng)
        return (snap.version,
                sorted(filtered.column("x").to_pylist()),
                agg.to_pydict(),
                sorted(full.column("x").to_pylist()))

    resilience_reset()
    clean = workload(engine(), "memory://bench-dchaos-clean/tbl")
    chaos = ChaosEngine(
        DeviceChaosSchedule(seed=42, dispatch_error_rate=0.15,
                            oom_rate=0.08, stall_rate=0.08,
                            recompile_rate=0.08),
        sleep=lambda s: None)
    t0 = time.perf_counter()
    try:
        with chaos:
            faulty = workload(engine(), "memory://bench-dchaos-42/tbl")
    finally:
        resilience_reset()
    chaos_s = time.perf_counter() - t0
    assert faulty == clean, "device chaos soak diverged from fault-free"
    assert chaos.total_faults > 0, "device chaos soak injected nothing"

    fallbacks = {
        g: _obs.counter(f"{g}.device_fallbacks").value
        for g in ("replay", "parse", "decode", "skip", "sql")}
    n_ops = 6 + 3  # commits + reads per workload run
    rate = n_ops / chaos_s
    print(f"device chaos soak @seed 42: {chaos.total_faults} faults "
          f"{dict(chaos.fault_counts)} absorbed in {chaos_s:.2f}s, "
          f"bit-identical convergence -> {rate:.1f} ops/s",
          file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "device_chaos_soak_ops_per_sec",
        "value": round(rate, 1),
        "unit": "ops/s",
        "faults": dict(chaos.fault_counts),
        "fallbacks": fallbacks,
    }))


def contended_commits_metric() -> None:
    """Multi-writer commit throughput, solo vs group commit, under an
    injected ~2ms storage round trip (every op sleeps, so the number
    tracks round trips — the thing batching amortizes — not Python
    speed). W writers each push a fixed number of commits at one table;
    solo mode pays one conflict check + one arbiter round trip per
    commit (plus rebase re-reads under contention), batched mode rides
    `DELTA_TPU_GROUP_COMMIT` so a burst shares ONE snapshot read and
    ONE claim. Gate (ISSUE 13): at 8+ writers batched must beat solo."""
    import threading

    import pyarrow as pa

    from delta_tpu.engine.host import HostEngine
    from delta_tpu.models.actions import AddFile
    from delta_tpu.resilience import (ChaosSchedule, ChaosStore,
                                      reset as resilience_reset)
    from delta_tpu.storage.logstore import InMemoryLogStore
    from delta_tpu.table import Table

    import delta_tpu.api as dta

    per_writer = int(os.environ.get("BENCH_CONTENDED_COMMITS", 3))
    rtt_s = float(os.environ.get("BENCH_CONTENDED_RTT_MS", 2.0)) / 1000.0

    def run(n_writers: int, batched: bool) -> float:
        store = ChaosStore(
            InMemoryLogStore(),
            ChaosSchedule(seed=7, latency_rate=1.0,
                          latency_s=(rtt_s, rtt_s)),
            sleep=time.sleep)
        eng = HostEngine(store_resolver=lambda p: store)
        mode = "batched" if batched else "solo"
        path = f"memory://bench-contended-{mode}-{n_writers}/tbl"
        store.enabled = False  # setup at full speed
        dta.write_table(path, pa.table({"x": pa.array([0], pa.int64())}),
                        engine=eng)
        table = Table.for_path(path, eng)
        store.enabled = True
        errors: list = []

        def writer(wid: int) -> None:
            try:
                for i in range(per_writer):
                    txn = table.start_transaction()
                    txn.add_file(AddFile(
                        path=f"w{wid}-{i}.parquet", partitionValues={},
                        size=100, modificationTime=1, dataChange=True))
                    txn.commit()
            except Exception as e:  # pragma: no cover - surfaces below
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(n_writers)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        elapsed = time.perf_counter() - t0
        assert not errors, f"contended bench writer failed: {errors}"
        store.enabled = False
        total = n_writers * per_writer
        assert table.latest_snapshot().version == total, \
            "contended bench lost a commit"
        return total / elapsed

    overrides = {"DELTA_TPU_RETRY_BASE_MS": "1",
                 "DELTA_TPU_RETRY_CAP_MS": "5"}
    saved = {k: os.environ.get(k)
             for k in (*overrides, "DELTA_TPU_GROUP_COMMIT",
                       "DELTA_TPU_GROUP_COMMIT_WINDOW_MS")}
    os.environ.update(overrides)
    resilience_reset()
    results = {}
    try:
        for n_writers in (2, 8, 32):
            os.environ.pop("DELTA_TPU_GROUP_COMMIT", None)
            solo = run(n_writers, batched=False)
            os.environ["DELTA_TPU_GROUP_COMMIT"] = "1"
            os.environ["DELTA_TPU_GROUP_COMMIT_WINDOW_MS"] = "4"
            grouped = run(n_writers, batched=True)
            results[n_writers] = (solo, grouped)
            print(f"contended commits @{n_writers} writers x "
                  f"{per_writer}: solo {solo:.0f}/s, "
                  f"batched {grouped:.0f}/s "
                  f"({grouped / solo:.2f}x)", file=sys.stderr)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience_reset()

    solo8, grouped8 = results[8]
    if grouped8 <= solo8:
        print(f"CONTENDED REGRESSION: batched ({grouped8:.0f}/s) did "
              f"not beat solo ({solo8:.0f}/s) at 8 writers",
              file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "contended_commits_per_sec",
        "value": round(grouped8, 1),
        "unit": "commits/s",
        "writers": 8,
        "vs_solo": round(grouped8 / solo8, 3),
        "by_writers": {str(w): {"solo": round(s, 1),
                                "batched": round(g, 1)}
                       for w, (s, g) in results.items()},
    }))


def serve_metrics() -> None:
    """Multi-tenant snapshot service under load: N clients x M tables
    against `DeltaServeServer` — once clean, once with the full
    telemetry plane armed (tracing + flight recorder + a concurrent
    Prometheus scraper), and once under a seeded ChaosStore (transient
    errors + stale listings, zero injected latency so the number tracks
    the serve/retry machinery, not naps).

    Gates:
    - telemetry_overhead_pct: the telemetry plane at production cadence
      (head-based trace sampling per BENCH_TRACE_SAMPLE, one Prometheus
      scrape per BENCH_SCRAPE_INTERVAL_S) must cost < 3% of clean
      per-request latency. The armed run above samples EVERY trace and
      scrapes at 50Hz — a stress configuration whose wall-clock delta
      is printed as a diagnostic only, same convention as
      trace_overhead_metric: the asserted number is derived from unit
      costs x production cadence, not from sub-millisecond wall deltas;
    - the chaos run is judged by the declarative SLO burn-rate engine
      (p99 objective = 10x the measured clean p99, the same bound the
      old hand-rolled assert enforced) instead of ad-hoc threshold
      math; on breach the flight-recorder dump is archived as a bench
      artifact next to BENCH_WORKDIR."""
    import threading as th

    import pyarrow as pa

    import delta_tpu.api as dta
    from delta_tpu import obs
    from delta_tpu.connect import connect
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.errors import (DeadlineExceededError,
                                  ServiceOverloadedError)
    from delta_tpu.resilience import (ChaosSchedule, ChaosStore,
                                      reset as resilience_reset)
    from delta_tpu.serve import DeltaServeServer, ServeConfig
    from delta_tpu.storage.logstore import InMemoryLogStore

    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    n_tables = int(os.environ.get("BENCH_SERVE_TABLES", 4))
    n_ops = int(os.environ.get("BENCH_SERVE_OPS", 40))
    telemetry_gate_pct = float(
        os.environ.get("BENCH_TELEMETRY_GATE_PCT", 3.0))
    artifact_dir = os.path.join(
        os.environ.get("BENCH_WORKDIR", "/tmp/delta_tpu_bench"),
        "bench_artifacts")
    overrides = {"DELTA_TPU_RETRY_BASE_MS": "1",
                 "DELTA_TPU_RETRY_CAP_MS": "5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    resilience_reset()

    def run(tag: str, chaos: bool, telemetry: bool = False,
            slo_p99_ms: float = 0.0):
        store = ChaosStore(
            InMemoryLogStore(),
            ChaosSchedule(seed=77, error_rate=0.15, stale_list_rate=0.05),
            sleep=lambda s: None)
        store.enabled = False
        eng = HostEngine(store_resolver=lambda p: store)
        paths = [f"memory://bench-serve-{tag}/t{i}"
                 for i in range(n_tables)]
        for p in paths:
            dta.write_table(p, pa.table(
                {"x": pa.array(list(range(64)), type=pa.int64())}),
                engine=eng)
        cfg = dict(workers=4, max_queue=64, drain_grace_s=2.0)
        if slo_p99_ms > 0:
            cfg.update(slo_p99_ms=slo_p99_ms, slo_shed_rate=0.95,
                       slo_deadline_rate=0.95,
                       slo_dump_dir=artifact_dir)
        if telemetry:
            obs.reset_trace_buffer()
            obs.set_trace_mode("on")  # flight recorder arms at start
        srv = DeltaServeServer(
            "127.0.0.1", 0, engine=eng,
            config=ServeConfig.from_env(**cfg))
        srv.start_background()
        # warmup before the clock: first requests pay lazy imports and
        # cold snapshot loads, which would otherwise dominate p99
        with connect(*srv.address, reconnect=False) as w:
            for p in paths:
                w.read_table(p)
        if telemetry:
            obs.reset_trace_buffer()  # don't count warmup spans
        store.enabled = chaos
        lat_ms, counts = [], {"ok": 0, "stale": 0, "shed": 0,
                              "deadline": 0}
        lock = th.Lock()
        stop_scrape = th.Event()

        def scraper():
            # a live Prometheus scrape loop: the exposition render is
            # part of the telemetry plane whose cost is being gated
            with connect(*srv.address, reconnect=False) as c:
                while not stop_scrape.is_set():
                    c.metrics_text()
                    stop_scrape.wait(0.02)

        scrape_thread = None
        if telemetry:
            scrape_thread = th.Thread(target=scraper, daemon=True)
            scrape_thread.start()

        def client(ci):
            with connect(*srv.address, tenant=f"tenant-{ci % 4}",
                         reconnect=False) as c:
                for k in range(n_ops):
                    p = paths[(ci + k) % n_tables]
                    t1 = time.perf_counter()
                    try:
                        if k % 3 == 2:
                            c.table_version(p)
                        else:
                            c.read_table(p)
                        kind = ("stale" if c.last_envelope.get("stale")
                                else "ok")
                    except ServiceOverloadedError:
                        kind = "shed"
                    except DeadlineExceededError:
                        kind = "deadline"
                    dt = (time.perf_counter() - t1) * 1000.0
                    with lock:
                        lat_ms.append(dt)
                        counts[kind] += 1

        threads = [th.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stop_scrape.set()
        if scrape_thread is not None:
            scrape_thread.join(timeout=5)
        verdict = srv.slo_verdict()
        if verdict is not None and not verdict.ok:
            # archive the whole flight ring as a bench artifact (the
            # server already dumped per-objective worst traces into
            # artifact_dir on the breach itself)
            dump = os.path.join(artifact_dir, f"flight_{tag}_ring.jsonl")
            n_spans = srv.flight.dump_jsonl(dump)
            print(f"serve {tag}: SLO breach — archived {n_spans} "
                  f"span(s) -> {dump}", file=sys.stderr)
        srv.shutdown(2.0)
        n_spans = 0
        if telemetry:
            n_spans = len(obs.get_finished_spans())
            obs.set_trace_mode("off")
            obs.reset_trace_buffer()
        lat_ms.sort()
        p50 = lat_ms[len(lat_ms) // 2]
        p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
        return (len(lat_ms) / wall, p50, p99, counts,
                dict(store.fault_counts), verdict, n_spans)

    try:
        clean_qps, clean_p50, clean_p99, clean_counts, _, _, _ = run(
            "clean", chaos=False)
        telem_qps, telem_p50, telem_p99, _, _, _, telem_spans = run(
            "telemetry", chaos=False, telemetry=True)
        resilience_reset()  # fresh breakers for the fault run
        # the chaos gate, now declarative: the SLO engine's p99
        # objective carries the same bound the old hand-rolled
        # `chaos_p99 <= 10x clean_p99` assert enforced (clean p99
        # floored at 1ms so an unloaded box can't fail on sub-ms
        # jitter); the verdict is multi-window burn rate, not a single
        # max, so one straggler can't fail a healthy run
        slo_p99_ms = 10.0 * max(clean_p99, 1.0)
        chaos_qps, chaos_p50, chaos_p99, chaos_counts, faults, verdict, \
            _ = run("chaos", chaos=True, telemetry=True,
                    slo_p99_ms=slo_p99_ms)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resilience_reset()

    print(f"serve clean: {clean_qps:.0f} qps p50={clean_p50:.2f}ms "
          f"p99={clean_p99:.2f}ms {clean_counts}", file=sys.stderr)
    print(f"serve telemetry-armed: {telem_qps:.0f} qps "
          f"p50={telem_p50:.2f}ms p99={telem_p99:.2f}ms", file=sys.stderr)
    print(f"serve chaos: {chaos_qps:.0f} qps p50={chaos_p50:.2f}ms "
          f"p99={chaos_p99:.2f}ms {chaos_counts} faults={faults} "
          f"slo_ok={verdict.ok if verdict else None}", file=sys.stderr)

    # telemetry-plane cost at PRODUCTION cadence, derived from unit
    # costs (the trace_overhead_metric convention). The armed run
    # samples every trace and scrapes at 50Hz — its wall delta is a
    # stress diagnostic, far above what a deployment pays with
    # head-based sampling and a ~15s scrape interval, and too noisy to
    # gate on at sub-millisecond p50s anyway. Asserted instead:
    #   sample_rate x spans/request x enabled-span unit cost
    #   + render unit cost / (scrape_interval x qps)
    # as a fraction of the clean per-request latency (floored at 1ms).
    from delta_tpu import obs
    from delta_tpu.obs import FlightRecorder

    sample_rate = float(os.environ.get("BENCH_TRACE_SAMPLE", 0.01))
    scrape_interval_s = float(
        os.environ.get("BENCH_SCRAPE_INTERVAL_S", 15.0))
    total_reqs = n_clients * n_ops
    # conservative: telem_spans also includes the 50Hz scraper's own
    # request spans, so spans/request rounds up
    spans_per_req = telem_spans / max(total_reqs, 1)

    obs.reset_trace_buffer()
    obs.set_trace_mode("on")
    flight = FlightRecorder(max_traces=64)
    obs.add_exporter(flight)
    n_unit = 20_000
    t0 = time.perf_counter()
    for _ in range(n_unit):
        with obs.span("bench.telemetry.unit", table="x"):
            pass
    span_unit_ms = (time.perf_counter() - t0) * 1000.0 / n_unit
    obs.remove_exporter(flight)
    obs.set_trace_mode("off")
    obs.reset_trace_buffer()

    n_render = 200
    t0 = time.perf_counter()
    for _ in range(n_render):
        obs.render_prometheus()
    render_unit_ms = (time.perf_counter() - t0) * 1000.0 / n_render

    trace_cost_ms = sample_rate * spans_per_req * span_unit_ms
    scrape_cost_ms = render_unit_ms / max(
        scrape_interval_s * clean_qps, 1e-9)
    overhead_pct = 100.0 * (trace_cost_ms + scrape_cost_ms) \
        / max(clean_p50, 1.0)
    armed_delta_pct = (telem_p50 - clean_p50) / max(clean_p50, 1.0) \
        * 100.0
    print(f"telemetry: {spans_per_req:.1f} spans/req, enabled span "
          f"{span_unit_ms * 1e3:.1f}us, /metrics render "
          f"{render_unit_ms:.2f}ms -> {overhead_pct:.4f}% at sample="
          f"{sample_rate:g} scrape={scrape_interval_s:g}s (armed "
          f"stress run p50 delta {armed_delta_pct:+.1f}%, diagnostic "
          f"only)", file=sys.stderr)
    assert overhead_pct < telemetry_gate_pct, \
        (f"telemetry plane at production cadence costs "
         f"{overhead_pct:.3f}% of clean p50 ({clean_p50:.3f}ms), "
         f"gate is {telemetry_gate_pct:g}%")
    assert verdict is not None, "chaos run armed SLOs but got no verdict"
    assert verdict.ok, \
        (f"serve chaos run breached its SLOs: "
         f"{[b.objective for b in verdict.breaches]} "
         f"burn_rates={verdict.burn_rates} — flight dump archived "
         f"under {artifact_dir}")
    print(json.dumps({
        "metric": "serve_qps",
        "value": round(clean_qps, 1),
        "unit": "requests/s",
        "clients": n_clients,
        "tables": n_tables,
        "p50_ms": round(clean_p50, 2),
        "p99_ms": round(clean_p99, 2),
    }))
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "sample_rate": sample_rate,
        "scrape_interval_s": scrape_interval_s,
        "spans_per_request": round(spans_per_req, 1),
        "enabled_span_us": round(span_unit_ms * 1e3, 1),
        "render_ms": round(render_unit_ms, 3),
        "clean_p50_ms": round(clean_p50, 3),
        "armed_p50_ms": round(telem_p50, 3),
        "armed_qps": round(telem_qps, 1),
        "armed_delta_pct": round(armed_delta_pct, 1),
        "gate_pct": telemetry_gate_pct,
    }))
    print(json.dumps({
        "metric": "serve_p99_ms_chaos",
        "value": round(chaos_p99, 2),
        "unit": "ms",
        "qps": round(chaos_qps, 1),
        "p50_ms": round(chaos_p50, 2),
        "outcomes": chaos_counts,
        "faults": faults,
        "slo": verdict.to_dict(),
        "slo_p99_objective_ms": round(slo_p99_ms, 2),
    }))


# ------------------------------------------------- device JSON parse


def device_parse_metric() -> None:
    """Device JSON action-parse kernels vs the host scanner over the
    SAME in-memory commit buffer (cache-insensitive: direct window
    parses, no parse cache, no filesystem in the timed loop). Emits
    `device_parse_actions_per_sec`; value is 0 when the device route
    falls back or row parity fails."""
    commits = int(os.environ.get("BENCH_PARSE_COMMITS", 2000))
    fpc = 50
    rng = np.random.default_rng(11)
    sizes = rng.integers(1, 1 << 40, commits * fpc)
    mods = rng.integers(1, 1 << 41, commits * fpc)
    blobs = []
    k = 0
    for v in range(commits):
        lines = []
        for i in range(fpc):
            lines.append(
                '{"add":{"path":"part-%05d-%04d-c000.snappy.parquet",'
                '"partitionValues":{},"size":%d,"modificationTime":%d,'
                '"dataChange":true,"stats":"{\\"numRecords\\":%d}"}}'
                % (v, i, sizes[k], mods[k], i))
            k += 1
        if v:
            lines.append(
                '{"remove":{"path":"part-%05d-0000-c000.snappy.parquet",'
                '"deletionTimestamp":%d,"dataChange":true}}'
                % (v - 1, 10_000 + v))
        lines.append('{"commitInfo":{"operation":"WRITE","ver":%d}}' % v)
        blobs.append(("\n".join(lines) + "\n").encode())
    starts = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=starts[1:])
    buf = b"".join(blobs)
    versions = np.arange(commits, dtype=np.int64)
    n_lines = commits * (fpc + 2) - 1

    from delta_tpu.replay.device_parse import parse_commits_device

    os.environ["DELTA_TPU_DEVICE_PARSE"] = "force"
    try:
        dev_out = parse_commits_device(buf, starts, versions)
        if dev_out is None:
            print("device parse fell back to host on the bench corpus",
                  file=sys.stderr)
            print(json.dumps({"metric": "device_parse_actions_per_sec",
                              "value": 0.0, "unit": "actions/s",
                              "vs_host": 0.0}))
            return
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            parse_commits_device(buf, starts, versions)
            times.append(time.perf_counter() - t0)
        dev_s = min(times)
    finally:
        del os.environ["DELTA_TPU_DEVICE_PARSE"]

    from delta_tpu import native
    from delta_tpu.replay.columnar import _parse_buffer_generic
    from delta_tpu.replay.native_parse import parse_commits_native

    host_kind = "native-simd"
    if native.available(allow_compile=True):
        host = lambda: parse_commits_native(buf, starts, versions)  # noqa: E731
    else:
        host_kind = "arrow-generic"
        host = lambda: _parse_buffer_generic(buf, starts, versions)  # noqa: E731
    host_out = host()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        host()
        times.append(time.perf_counter() - t0)
    host_s = min(times)

    dev_t, host_t = dev_out[0], host_out[0]
    parity = (dev_t.num_rows == host_t.num_rows
              and dev_t.column("path").to_pylist()
              == host_t.column("path").to_pylist()
              and dev_t.column("size").to_pylist()
              == host_t.column("size").to_pylist())
    print(f"device parse @{n_lines} lines ({len(buf) / 1e6:.0f}MB): "
          f"device {n_lines / dev_s / 1e6:.2f}M actions/s, "
          f"{host_kind} host {n_lines / host_s / 1e6:.2f}M actions/s, "
          f"parity={'OK' if parity else 'MISMATCH'}", file=sys.stderr)
    print(json.dumps({
        "metric": "device_parse_actions_per_sec",
        "value": round(n_lines / dev_s, 1) if parity else 0.0,
        "unit": "actions/s",
        "vs_host": round(host_s / dev_s, 3) if parity else 0.0,
        "host_kind": host_kind,
        "window_mb": round(len(buf) / 1e6, 1),
    }))


# ------------------------------------------------- device scan planning


def scan_plan_metric() -> None:
    """Batched device data-skipping vs its numpy host twin over the
    SAME resident stats index (planning only, no data read; the index
    is built once and both routes reuse it). Emits
    `scan_plan_files_skipped_per_sec`; value is 0 when the routes'
    skipped-file sets differ or the index was rebuilt instead of
    reused."""
    import threading

    import pyarrow as pa

    from delta_tpu import obs
    from delta_tpu.expressions.tree import Comparison, In, col, lit
    from delta_tpu.stats.skipping import skipping_mask

    n_files = int(os.environ.get("BENCH_SCAN_FILES", 200_000))
    rng = np.random.default_rng(17)
    lo = rng.integers(0, 1 << 32, n_files)
    width = rng.integers(1, 1 << 16, n_files)
    flo = rng.uniform(-1e6, 1e6, n_files)
    plo = rng.uniform(0.0, 1000.0, n_files)
    nc = rng.integers(0, 5, n_files)
    stats = [
        '{"numRecords":50,"minValues":{"k":%d,"f":%.3f,"price":%.2f},'
        '"maxValues":{"k":%d,"f":%.3f,"price":%.2f},'
        '"nullCount":{"k":%d,"f":0,"price":0}}'
        % (lo[i], flo[i], plo[i],
           lo[i] + width[i], flo[i] + 10.0, plo[i] + 50.0, nc[i])
        for i in range(n_files)
    ]
    files = pa.table({
        "path": [f"f{i}.parquet" for i in range(n_files)],
        "stats": pa.array(stats, pa.string()),
    })

    class _State:
        """Duck-typed SnapshotState: the fields snapshot_stats_index
        needs (plain attribute keeps `add_files_table` identity)."""

        def __init__(self, f):
            self.add_files_table = f
            self.stats_index = None
            self._stats_index_lock = threading.Lock()

    # 3 comparisons + a 40-value In-list: 43 atoms, all compiled (no
    # Arrow fallback) — the timed loop is pure plan work
    conjs = [
        Comparison(">=", col("k"), lit(1 << 31)),
        Comparison("<", col("k"), lit((1 << 31) + (1 << 29))),
        Comparison(">", col("f"), lit(0.0)),
        In(col("price"), tuple(float(v) for v in range(100, 140))),
    ]

    def run(route):
        os.environ["DELTA_TPU_DEVICE_SKIP"] = route
        try:
            mask = skipping_mask(files, conjs, None, state=st)
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                skipping_mask(files, conjs, None, state=st)
                times.append(time.perf_counter() - t0)
            return mask, min(times)
        finally:
            del os.environ["DELTA_TPU_DEVICE_SKIP"]

    st = _State(files)
    builds = obs.counter("scan.stats_index_builds")
    b0 = builds.value
    dev_mask, dev_s = run("force")
    host_mask, host_s = run("off")
    built = builds.value - b0  # 8 plans, one shared index build

    skipped = int((~dev_mask).sum())
    parity = bool((dev_mask == host_mask).all()) and built == 1
    print(f"scan planning @{n_files} files ({len(conjs)} conjuncts, "
          f"{skipped} skipped): device {skipped / dev_s / 1e6:.1f}M "
          f"skips/s, host twin {skipped / host_s / 1e6:.1f}M skips/s, "
          f"index builds={built}, "
          f"parity={'OK' if parity else 'MISMATCH'}", file=sys.stderr)
    print(json.dumps({
        "metric": "scan_plan_files_skipped_per_sec",
        "value": round(skipped / dev_s, 1) if parity else 0.0,
        "unit": "files/s",
        "vs_host": round(host_s / dev_s, 3) if parity else 0.0,
        "files": n_files,
        "skipped": skipped,
        "kept": n_files - skipped,
        "stats_index_builds": built,
    }))


def device_obs_metric(workdir: str) -> None:
    """Device-execution observability (PR 15): disabled-path overhead
    gate, runtime transfer-budget audit over real dispatches, and the
    gate-calibration join across all three routing gates.

    The calibration drive writes the gate's placeholder link out as the
    link model (DELTA_TPU_LINK_MODEL) so every economics decision carries a
    nonzero per-route prediction even on CPU containers, then runs real
    work through the production hooks: replay via `replay_select` (or
    the host twin under `gate_observation`), commit-JSON parse via the
    device path with its honest mid-flight host fallback, skipping via
    `skipping_mask` with an opted-in engine duck. Artifacts: the gate
    log JSONL (`delta-gate` input) and a DEVICE_MERIT-shaped capture.

    The asserted number is the DISABLED path, same shape as
    `trace_overhead_pct`: per-call no-op `device_dispatch` cost x the
    dispatch count an identical observed run records, as a fraction of
    the unobserved run time. Gate: < 2%."""
    import threading

    import pyarrow as pa

    from delta_tpu import obs
    from delta_tpu.expressions.tree import Comparison, In, col, lit
    from delta_tpu.ops.replay import replay_select
    from delta_tpu.parallel import gate
    from delta_tpu.replay import device_parse as _dp
    from delta_tpu.replay.columnar import parse_commit_batch
    from delta_tpu.stats.skipping import skipping_mask

    n = int(os.environ.get("BENCH_DEVICE_OBS_ROWS", 2_000_000))
    pk, dk, ver, order, is_add = synth_history(n)

    # commit blobs for the parse drive: the cached bench log's own JSON
    log_path = ensure_log(workdir, int(os.environ.get(
        "BENCH_TRACE_COMMITS", 500)))
    ldir = os.path.join(log_path, "_delta_log")
    blobs = []
    for name in sorted(os.listdir(ldir)):
        if name.endswith(".json"):
            with open(os.path.join(ldir, name), "rb") as f:
                blobs.append((int(name.split(".")[0]), f.read()))
    datas = [b for _, b in blobs]
    buf = b"".join(datas)
    starts = np.cumsum([0] + [len(b) for b in datas]).astype(np.int64)
    versions = np.array([v for v, _ in blobs], dtype=np.int64)
    nbytes = int(starts[-1])

    # skip-gate fixture: real stats index, engine duck opted in so the
    # route comes from the economics (not env force) and carries the
    # per-route prediction
    n_files = int(os.environ.get("BENCH_DEVICE_OBS_FILES", 120_000))
    rng = np.random.default_rng(29)
    lo = rng.integers(0, 1 << 32, n_files)
    width = rng.integers(1, 1 << 16, n_files)
    stats = [
        '{"numRecords":50,"minValues":{"k":%d},"maxValues":{"k":%d},'
        '"nullCount":{"k":%d}}'
        % (lo[i], lo[i] + width[i], int(rng.integers(0, 5)))
        for i in range(n_files)
    ]
    files = pa.table({
        "path": [f"f{i}.parquet" for i in range(n_files)],
        "stats": pa.array(stats, pa.string()),
    })

    class _State:
        def __init__(self, f):
            self.add_files_table = f
            self.stats_index = None
            self._stats_index_lock = threading.Lock()

    class _Engine:
        use_device_skip = True

    conjs = [
        Comparison(">=", col("k"), lit(1 << 31)),
        Comparison("<", col("k"), lit((1 << 31) + (1 << 29))),
        In(col("k"), tuple(range(100, 140))),
    ]
    st = _State(files)

    def drive() -> None:
        # replay gate: route by economics, observe the chosen side
        route = gate.replay_route(n, n_shards=1)
        if route == "host":
            with obs.gate_observation("replay", "host"):
                kernel_baseline_vectorized(pk, dk, is_add)
        else:
            replay_select([pk, dk], ver, order, is_add)
        # parse gate: device attempt with the production host fallback
        route = gate.parse_route(nbytes, engine_enabled=True)
        if route == "device":
            out = _dp.parse_commits_device(buf, starts, versions)
            if out is None:
                obs.gate_fell_back("parse", "host",
                                   reason="device-parse-unavailable")
                with obs.gate_observation("parse", "host"):
                    parse_commit_batch(blobs)
        else:
            with obs.gate_observation("parse", "host"):
                parse_commit_batch(blobs)
        # skip gate: economics + join happen inside stats/skipping
        skipping_mask(files, conjs, None, engine=_Engine(), state=st)

    # the gate's placeholder link, written out so a CPU run prices it
    # too (without DELTA_TPU_LINK_MODEL the CPU model is free-transfer)
    link_path = os.path.join(workdir, "link_model.json")
    with open(link_path, "w") as f:
        json.dump({"link": {
            "h2d_bytes_per_s": {str(k): v
                                for k, v in gate._FALLBACK_H2D.items()},
            "rtt_s": gate._FALLBACK_RTT_S}}, f)
    os.environ["DELTA_TPU_LINK_MODEL"] = link_path
    gate.reset_model_cache()
    try:
        obs.set_device_obs_mode("off")
        drive()  # warm compile caches / allocator on both sides
        t0 = time.perf_counter()
        drive()
        off_s = time.perf_counter() - t0

        obs.set_device_obs_mode("on")
        obs.reset_device_obs()
        disp = obs.counter("device.dispatches")
        viol = obs.counter("device.budget_violations")
        d0, v0 = disp.value, viol.value
        drive()
        obs.flush_gate_decisions()
        n_disp = disp.value - d0
        n_viol = viol.value - v0

        # disabled fast path, measured directly
        obs.set_device_obs_mode("off")
        n_calls = 200_000
        t0 = time.perf_counter()
        for _ in range(n_calls):
            with obs.device_dispatch("bench.noop", key=(1,)) as dd:
                dd.h2d("x", 8)
        noop_per_call_s = (time.perf_counter() - t0) / n_calls
        overhead_pct = 100.0 * (noop_per_call_s * n_disp) / off_s

        gate_log = os.path.join(workdir, "gate_log.jsonl")
        n_records = obs.dump_gate_log(gate_log)
        merit_path = os.path.join(workdir, "device_merit_capture.json")
        capture = obs.export_device_merit()
        with open(merit_path, "w") as f:
            json.dump(capture, f, indent=2, sort_keys=True)
            f.write("\n")

        calib = {
            g: {r: rr["median_abs_err_pct"]
                for r, rr in gs["routes"].items()}
            for g, gs in obs.summarize_gates().items()
        }
        joined = sum(
            rr["joined"] for gs in obs.summarize_gates().values()
            for rr in gs["routes"].values())
        print(f"device obs @{n} rows: {n_disp} dispatches, "
              f"{n_viol} budget violations, {joined} gate joins, "
              f"no-op dispatch {noop_per_call_s * 1e9:.0f}ns/call -> "
              f"disabled-path overhead {overhead_pct:.3f}% of "
              f"{off_s:.3f}s; calibration |err| {calib}", file=sys.stderr)
        print(f"gate log: {gate_log} ({n_records} records); "
              f"merit capture: {merit_path}", file=sys.stderr)
        assert n_viol == 0, (
            f"{n_viol} transfer-budget violations on clean hot paths")
        assert len(calib) == 3, f"expected 3 calibrated gates: {calib}"
        assert overhead_pct < 2.0, (
            f"disabled-path device-obs overhead {overhead_pct:.2f}% >= 2%")
        # secondary metric line (the driver reads the LAST line only)
        print(json.dumps({
            "metric": "device_obs_overhead_pct",
            "value": round(overhead_pct, 4),
            "unit": "%",
            "noop_dispatch_ns": round(noop_per_call_s * 1e9, 1),
            "dispatches_per_run": n_disp,
            "budget_violations": n_viol,
            "gate_joins": joined,
            "calibration_abs_err_pct": calib,
            "gate_log": gate_log,
            "merit_capture": merit_path,
        }))
    finally:
        obs.set_device_obs_mode(None)
        obs.reset_device_obs()
        del os.environ["DELTA_TPU_LINK_MODEL"]
        gate.reset_model_cache()


_HBM_DEVICE_CODE = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.devices()  # device init outside the timed region
from delta_tpu import obs
from delta_tpu.obs import hbm
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.models.actions import AddFile
from delta_tpu.models.schema import INTEGER, StructField, StructType
from delta_tpu.parallel.resident import release_snapshot_resident
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.stats.device_index import snapshot_stats_index
from delta_tpu.table import Table

root = {table_dir!r}
commits = {commits}
files_per_commit = {files}

t = Table.for_path(root, TpuEngine(replay_shards=8))
t.create_transaction_builder().with_schema(
    StructType([StructField("x", INTEGER)])).build().commit()
for i in range(commits):
    txn = t.start_transaction()
    for j in range(files_per_commit):
        txn.add_file(AddFile(
            path=f"p{{i}}_{{j}}.parquet", partitionValues={{}},
            size=100 + j, modificationTime=1000 + i, dataChange=True,
            stats=json.dumps({{"numRecords": 10 * j,
                               "minValues": {{"x": j}},
                               "maxValues": {{"x": j + 100}}}})))
    txn.commit()
del t

def load():
    # full cold device residency: sharded replay key lane + stats-index
    # lanes, exactly what a serve worker establishes per table
    clear_parse_cache()
    t0 = time.perf_counter()
    snap = Table.for_path(root, TpuEngine(replay_shards=8)) \
        .latest_snapshot()
    _ = snap.state.live_mask
    idx = snapshot_stats_index(snap.state, snap.state.add_files_table)
    if idx is not None:
        idx.device_lanes()
    return time.perf_counter() - t0, snap

# enabled path: op count + resident bytes + reconciliation verdict
obs.set_hbm_obs_mode("on")
obs.reset_hbm_obs()
ops0 = hbm.ledger_op_count()
on_s, snap = load()
n_ops = hbm.ledger_op_count() - ops0
resident_bytes = hbm.ledger().total_bytes()
by_kind = {{k: e["nbytes"] for k, e in hbm.rollup(by="kind").items()}}
audit = hbm.audit()
release_snapshot_resident(snap)
audit_clean_after = hbm.ledger().total_bytes() == 0
del snap
obs.reset_hbm_obs()

# disabled path: the production-load comparison base (best of two)
obs.set_hbm_obs_mode("off")
offs = []
for _ in range(2):
    off_s, snap = load()
    offs.append(off_s)
    release_snapshot_resident(snap)
    del snap

# disabled fast path, measured directly (3 ledger ops per iteration)
n_calls = 200_000
t0 = time.perf_counter()
for _ in range(n_calls):
    h = hbm.register(None, kind=hbm.KIND_REPLAY_KEYS, nbytes=8)
    h.touch()
    h.release()
noop_per_op_s = (time.perf_counter() - t0) / (n_calls * 3)

print("HBM_RESULT=" + json.dumps({{
    "on_s": on_s, "off_s": min(offs), "n_ops": n_ops,
    "noop_per_op_s": noop_per_op_s,
    "resident_bytes": resident_bytes, "by_kind": by_kind,
    "audit_ok": bool(audit["ok"]),
    "verified_bytes": audit["verified_bytes"],
    "ledger_bytes": audit["ledger_bytes"],
    "release_clean": audit_clean_after,
    "conditions": obs.capture_conditions(cache_state="cold"),
}}))
"""


def hbm_overhead_metric(workdir: str, timeout_s: int = 600) -> None:
    """HBM resident-ledger accounting cost + the cold-load resident
    footprint, on 8 emulated host devices (subprocess, like
    `sharded_metrics`, so the forced device count can't leak into the
    driver's jax runtime).

    The asserted number is the DISABLED path, same shape as
    `trace_overhead_pct`: per-op no-op ledger cost x the ledger-op
    count an identical accounted cold load performs (register + grow +
    touch + release across replay key lanes, stats-index lanes, and
    checkpoint handoff), as a fraction of the unaccounted load time.
    Gate: < 2%. The same run emits `hbm_resident_bytes_cold_load` —
    the byte-exact device footprint a serve worker pins per table,
    stamped with capture conditions — and asserts the reconciliation
    audit came back clean (ledger == live arrays, zero leaks)."""
    commits = int(os.environ.get("BENCH_HBM_COMMITS", 8))
    files = int(os.environ.get("BENCH_HBM_FILES", 400))
    repo = os.path.dirname(os.path.abspath(__file__))
    # fresh table every run: the builder only ever appends commits
    table_dir = os.path.join(
        workdir, f"hbm_table_c{commits}_f{files}_{os.getpid()}")
    os.makedirs(table_dir, exist_ok=True)
    code = _HBM_DEVICE_CODE.format(repo=repo, table_dir=table_dir,
                                   commits=commits, files=files)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    result = None
    try:
        proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                              capture_output=True, text=True,
                              timeout=timeout_s, env=env)
        for line in proc.stdout.splitlines():
            if line.startswith("HBM_RESULT="):
                result = json.loads(line.split("=", 1)[1])
        if result is None:
            raise RuntimeError(
                f"no HBM_RESULT (rc={proc.returncode}): "
                f"{proc.stderr[-400:]}")
    except Exception as e:
        print(f"hbm accounting metric unavailable: {e}", file=sys.stderr)
        print(json.dumps({"metric": "hbm_accounting_overhead_pct",
                          "value": 0.0, "unit": "%", "gate_ok": False}))
        return
    finally:
        import shutil

        shutil.rmtree(table_dir, ignore_errors=True)

    overhead_pct = (100.0 * result["noop_per_op_s"] * result["n_ops"]
                    / result["off_s"])
    print(f"hbm accounting @{commits}x{files} files: off "
          f"{result['off_s']:.3f}s, on {result['on_s']:.3f}s, "
          f"{result['n_ops']} ledger ops, no-op ledger op "
          f"{result['noop_per_op_s'] * 1e9:.0f}ns -> disabled-path "
          f"overhead {overhead_pct:.4f}%", file=sys.stderr)
    print(f"hbm cold-load resident footprint: "
          f"{result['resident_bytes']} B ({result['by_kind']}), "
          f"audit ok={result['audit_ok']} verified "
          f"{result['verified_bytes']}/{result['ledger_bytes']} B, "
          f"release clean={result['release_clean']}", file=sys.stderr)
    assert result["audit_ok"], "hbm reconciliation audit reported drift"
    assert result["verified_bytes"] == result["ledger_bytes"], (
        "hbm audit not byte-exact: verified "
        f"{result['verified_bytes']} != ledger {result['ledger_bytes']}")
    assert result["release_clean"], (
        "release_snapshot_resident left ledger entries behind")
    assert overhead_pct < 2.0, (
        f"disabled-path hbm accounting overhead {overhead_pct:.2f}% >= 2%")
    # secondary metric lines (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "hbm_accounting_overhead_pct",
        "value": round(overhead_pct, 4),
        "unit": "%",
        "ledger_ops_per_load": result["n_ops"],
        "noop_ledger_op_ns": round(result["noop_per_op_s"] * 1e9, 1),
        "audit_ok": result["audit_ok"],
        "gate_ok": True,
    }))
    print(json.dumps({
        "metric": "hbm_resident_bytes_cold_load",
        "value": result["resident_bytes"],
        "unit": "B",
        "by_kind": result["by_kind"],
        "commits": commits,
        "files_per_commit": files,
        "conditions": result["conditions"],
    }))


def tpcds_scan_metric(workdir: str) -> None:
    """TPC-DS-derived scan planning on a real table: partition pruning
    + stats skipping on a date-sorted store_sales slice, resident-index
    reuse across two scans of one snapshot version, and the Z-order
    payoff (clustering raises the box-predicate skip rate). Numbers on
    a CPU container are informational; the pruning/skip-rate asserts
    are platform-independent."""
    import shutil

    import pyarrow as pa
    import pyarrow.compute as pc

    import delta_tpu.api as dta
    from delta_tpu import obs
    from delta_tpu.expressions.tree import col, lit
    from delta_tpu.table import Table

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.tpcds_data import generate

    scale = int(os.environ.get("BENCH_TPCDS_SCALE", 40_000))
    root = os.path.join(workdir, "tpcds_scan")
    shutil.rmtree(root, ignore_errors=True)

    ss = generate(scale=scale, seed=7)["store_sales"]
    # date-sorted ingest: files inside each store partition get disjoint
    # ss_sold_date_sk ranges, the shape a daily batch load produces
    ss = ss.sort_by("ss_sold_date_sk")
    path = os.path.join(root, "store_sales")
    dta.write_table(path, ss, partition_by=["ss_store_sk"],
                    target_rows_per_file=500)

    snap = Table.for_path(path).latest_snapshot()
    total = snap.state.add_files_table.num_rows
    dates = ss.column("ss_sold_date_sk")
    d_lo = int(pc.quantile(dates, 0.25).to_pylist()[0])
    d_hi = int(pc.quantile(dates, 0.35).to_pylist()[0])
    flt = ((col("ss_store_sk") == lit(3))
           & (col("ss_sold_date_sk") >= lit(d_lo))
           & (col("ss_sold_date_sk") <= lit(d_hi)))

    builds = obs.counter("scan.stats_index_builds")
    plans = obs.counter("scan.device_plans")
    b0, p0 = builds.value, plans.value
    os.environ["DELTA_TPU_DEVICE_SKIP"] = "force"
    try:
        sc = snap.scan(filter=flt)
        surviving = sc.add_files_table()
        # second scan of the SAME snapshot version: index is reused
        sc2 = snap.scan(filter=flt)
        sc2.add_files_table()
    finally:
        del os.environ["DELTA_TPU_DEVICE_SKIP"]
    built, planned = builds.value - b0, plans.value - p0

    # correctness gate: no wrongly-skipped file — reading the surviving
    # files and filtering rows must reproduce the exact answer
    exact = int(pc.sum(pc.and_kleene(
        pc.equal(ss.column("ss_store_sk"), 3),
        pc.and_kleene(
            pc.greater_equal(dates, d_lo),
            pc.less_equal(dates, d_hi))).cast(pa.int64()),
        min_count=0).as_py())
    got_t = sc.to_arrow()
    got = int(pc.sum(pc.and_kleene(
        pc.equal(got_t.column("ss_store_sk"), 3),
        pc.and_kleene(
            pc.greater_equal(got_t.column("ss_sold_date_sk"), d_lo),
            pc.less_equal(got_t.column("ss_sold_date_sk"), d_hi))
        ).cast(pa.int64()), min_count=0).as_py())

    files_read = surviving.num_rows
    ok = (got == exact and files_read < total and built == 1
          and planned == 2 and sc.partition_pruned > 0
          and sc.skipped_by_stats > 0)
    print(f"tpcds store_sales scan @{scale} rows: {files_read}/{total} "
          f"files read (partition pruned {sc.partition_pruned}, stats "
          f"skipped {sc.skipped_by_stats}), rows {got}/{exact}, index "
          f"builds={built} over {planned} device plans, "
          f"{'OK' if ok else 'MISMATCH'}", file=sys.stderr)
    print(json.dumps({
        "metric": "tpcds_scan_files_read",
        "value": files_read if ok else -1,
        "unit": "files",
        "files_total": total,
        "partition_pruned": sc.partition_pruned,
        "stats_skipped": sc.skipped_by_stats,
        "rows": got,
        "stats_index_builds": built,
    }))

    # ---- Z-order payoff: clustering must raise the skip rate --------
    zpath = os.path.join(root, "zorder")
    rng = np.random.default_rng(23)
    n = scale
    zt = pa.table({
        "x": pa.array(rng.integers(0, 1 << 20, n).astype(np.int64)),
        "y": pa.array(rng.integers(0, 1 << 20, n).astype(np.int64)),
        "payload": pa.array(rng.integers(0, 1 << 30, n).astype(np.int64)),
    })
    dta.write_table(zpath, zt, target_rows_per_file=2000)
    tbl = Table.for_path(zpath)
    box = ((col("x") < lit(1 << 18)) & (col("y") < lit(1 << 18)))

    snap_pre = tbl.latest_snapshot()
    pre_total = snap_pre.state.add_files_table.num_rows
    pre_read = snap_pre.scan(filter=box).add_files_table().num_rows

    # keep the output file count comparable to the input's so the
    # before/after skip rates are apples to apples
    total_bytes = int(pc.sum(
        snap_pre.state.add_files_table.column("size")).as_py())
    tbl.optimize().execute_zorder_by(
        "x", "y", max_file_size=max(1, total_bytes // max(1, pre_total)))

    snap_post = tbl.latest_snapshot()
    post_files = snap_post.state.add_files_table
    post_total = post_files.num_rows
    post_read = snap_post.scan(filter=box).add_files_table().num_rows
    tags = [json.loads(t) if t else {}
            for t in post_files.column("tags").to_pylist()]
    tagged = sum(1 for t in tags if t.get("ZCUBE_ID"))

    zok = (post_read / max(1, post_total) < pre_read / max(1, pre_total)
           and tagged == post_total)
    print(f"zorder payoff: box predicate read {pre_read}/{pre_total} "
          f"files before, {post_read}/{post_total} after OPTIMIZE "
          f"ZORDER (x, y); {tagged} files ZCube-tagged, "
          f"{'OK' if zok else 'NO-IMPROVEMENT'}", file=sys.stderr)
    print(json.dumps({
        "metric": "zorder_box_files_read_frac",
        "value": round(post_read / max(1, post_total), 4) if zok else -1.0,
        "unit": "fraction",
        "before_frac": round(pre_read / max(1, pre_total), 4),
        "files_before": pre_total,
        "files_after": post_total,
        "zcube_tagged": tagged,
    }))


def tpcds_query_metric(workdir: str) -> None:
    """TPC-DS query execution through the device SQL spine: wall
    seconds to plan + execute a join/agg-heavy query slice with the
    sql gate forced to device, row-exact parity against the HostEngine
    executor, and the resident operand cache's warm payoff — the warm
    pass must show cache hits AND measurably fewer H2D bytes than the
    cold pass (the build sides stayed on device). Numbers on a CPU
    container are informational; the parity/cache asserts are
    platform-independent."""
    import shutil

    from delta_tpu import obs
    from delta_tpu.catalog import Catalog
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.sqlengine import execute_select

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.tpcds_data import load_delta
    from benchmarks.tpcds_queries import QUERIES

    scale = int(os.environ.get("BENCH_TPCDS_SCALE", 40_000))
    root = os.path.join(workdir, "tpcds_query")
    shutil.rmtree(root, ignore_errors=True)
    catalog = load_delta(root, scale=scale)
    host_catalog = Catalog(catalog.root, engine=HostEngine())

    # ORDER BY ties at a LIMIT cutoff are engine-dependent; comparing
    # the full result set is strictly stronger (same as test_tpcds)
    def _strip_limit(q: str) -> str:
        return re.sub(r"\blimit\s+\d+\s*$", "", q.strip(),
                      flags=re.IGNORECASE)

    names = [n for n in ("q3", "q7", "q19", "q42", "q52", "q55", "q68",
                         "q96") if n in QUERIES]
    texts = {n: _strip_limit(QUERIES[n]) for n in names}

    def _rows(tbl):
        out = list(zip(*(c.to_pylist() for c in tbl.columns))) \
            if tbl.num_columns else []
        if tbl.num_rows and not out:
            out = [()] * tbl.num_rows
        return sorted(out, key=repr)

    hits = obs.counter("sql.operand_cache_hits")
    misses = obs.counter("sql.operand_cache_misses")
    dev_q = obs.counter("sql.device_queries")
    h2d = obs.counter("device.h2d_bytes")

    os.environ["DELTA_TPU_DEVICE_SQL"] = "force"
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    try:
        q0, b0 = dev_q.value, h2d.value
        t0 = time.perf_counter()
        for n in names:
            execute_select(texts[n], catalog=catalog)
        cold_s = time.perf_counter() - t0
        cold_h2d = h2d.value - b0

        h0, m0, b1 = hits.value, misses.value, h2d.value
        warm = {}
        t0 = time.perf_counter()
        for n in names:
            warm[n] = execute_select(texts[n], catalog=catalog)
        warm_s = time.perf_counter() - t0
        warm_h2d = h2d.value - b1
        warm_hits = hits.value - h0
        warm_misses = misses.value - m0
        routed = dev_q.value - q0
    finally:
        del os.environ["DELTA_TPU_DEVICE_SQL"]
        obs.set_device_obs_mode(None)
        obs.reset_device_obs()

    mismatches = [n for n in names
                  if _rows(execute_select(texts[n], catalog=host_catalog))
                  != _rows(warm[n])]

    hit_pct = 100.0 * warm_hits / max(1, warm_hits + warm_misses)
    ok = (not mismatches and routed >= 2 * len(names)
          and warm_hits > 0 and warm_h2d < cold_h2d)
    print(f"tpcds queries @{scale} rows: {len(names)} queries, cold "
          f"{cold_s:.2f}s / warm {warm_s:.2f}s, H2D cold "
          f"{cold_h2d / 1e6:.2f}MB -> warm {warm_h2d / 1e6:.2f}MB, "
          f"operand cache {warm_hits} hits / {warm_misses} misses "
          f"({hit_pct:.0f}%), {routed} device-routed, parity "
          f"{'OK' if not mismatches else 'MISMATCH ' + str(mismatches)}",
          file=sys.stderr)
    print(json.dumps({
        "metric": "tpcds_query_seconds",
        "value": round(warm_s, 4) if ok else -1.0,
        "unit": "s",
        "queries": len(names),
        "cold_seconds": round(cold_s, 4),
        "h2d_bytes_cold": cold_h2d,
        "h2d_bytes_warm": warm_h2d,
        "device_routed": routed,
        "parity_mismatches": mismatches,
    }))
    print(json.dumps({
        "metric": "sql_operand_cache_hit_pct",
        "value": round(hit_pct, 2) if ok else -1.0,
        "unit": "%",
        "hits": warm_hits,
        "misses": warm_misses,
    }))


def main():
    commits = int(os.environ.get("BENCH_COMMITS", 100_000))
    workdir = os.environ.get("BENCH_WORKDIR", "/tmp/delta_tpu_bench")
    timeout_s = int(os.environ.get("BENCH_DEVICE_TIMEOUT", 1800))
    n_actions = commits * FILES_PER_COMMIT

    # capture-conditions stamp: rides into the bench artifact's metric
    # list so this run can be grouped with comparable history
    from delta_tpu import obs as _obs
    print(json.dumps({
        "metric": "capture_conditions",
        "value": 1,
        "unit": "schema",
        "conditions": _obs.capture_conditions(cache_state="warm"),
    }))

    analyzer_scan_metric()
    trace_overhead_metric(workdir)
    retry_overhead_metric(workdir)
    chaos_recovery_metric()
    device_chaos_soak_metric()
    contended_commits_metric()
    serve_metrics()
    checkpoint_read_metric(workdir)
    checkpoint_write_metric(workdir)
    device_parse_metric()
    scan_plan_metric()
    device_obs_metric(workdir)
    hbm_overhead_metric(workdir, min(timeout_s, 600))
    tpcds_scan_metric(workdir)
    tpcds_query_metric(workdir)
    if os.environ.get("BENCH_SHARDED", "1") != "0":
        sharded_metrics(timeout_s)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    # build the native scanner up front so neither side times a g++ run
    from delta_tpu import native
    native.available(allow_compile=True)

    path = ensure_log(workdir, commits)

    base_s, base_files, base_actions = baseline_load(path)
    print(f"baseline (host, vectorized replay): {base_s:.1f}s "
          f"({base_actions / base_s / 1e6:.2f}M actions/s, "
          f"{base_files} live files)", file=sys.stderr)

    # no fallback: a device load that fails fails the run
    dev = device_load_subprocess(path, timeout_s)
    if dev["files"] != base_files:
        print(f"LIVE-FILE MISMATCH: device {dev['files']} vs "
              f"baseline {base_files}", file=sys.stderr)
        print(json.dumps({"metric": "e2e_snapshot_load_actions_per_sec",
                          "value": 0.0, "unit": "actions/s",
                          "vs_baseline": 0.0}))
        return

    ours_s = dev["warm"]
    print(f"ours (TpuEngine product path): cold {dev['cold']:.1f}s, "
          f"warm {ours_s:.1f}s ({base_actions / ours_s / 1e6:.2f}M "
          f"actions/s)", file=sys.stderr)
    print(f"e2e speedup vs honest baseline: {base_s / ours_s:.2f}x "
          f"(cold: {base_s / dev['cold']:.2f}x)", file=sys.stderr)
    # secondary metric line (the driver reads the LAST line only)
    print(json.dumps({
        "metric": "cold_snapshot_load_seconds",
        "value": round(dev["cold"], 3),
        "unit": "s",
        "warm_seconds": round(ours_s, 3),
        "commits": commits,
    }))

    if os.environ.get("BENCH_KERNEL_DIAG", "1") != "0":
        kernel_diagnostics(min(n_actions, 10_000_000), timeout_s)

    if "update_s" in dev:
        upd_s = dev["update_s"]
        cold_s = dev["cold_after_append_s"]
        ok = dev["parity"]
        print(f"incremental update(): {upd_s * 1000:.0f}ms for "
              f"{dev['update_actions']} actions "
              f"({dev['update_actions'] / upd_s / 1e3:.0f}K actions/s), "
              f"{cold_s / upd_s:.0f}x faster than the {cold_s:.1f}s cold "
              f"reload, parity={'OK' if ok else 'MISMATCH'}",
              file=sys.stderr)
        # secondary metric line (the driver reads the LAST line only)
        print(json.dumps({
            "metric": "incremental_update_actions_per_sec",
            "value": round(dev["update_actions"] / upd_s, 1) if ok else 0.0,
            "unit": "actions/s",
            "vs_cold_full_load": round(cold_s / upd_s, 1) if ok else 0.0,
            "parity": ok,
        }))

    print(json.dumps({
        "metric": "e2e_snapshot_load_actions_per_sec",
        "value": round(base_actions / ours_s, 1),
        "unit": "actions/s",
        "vs_baseline": round(base_s / ours_s, 3),
    }))


if __name__ == "__main__":
    main()
