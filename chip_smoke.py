#!/usr/bin/env python3
"""chip_smoke.py — the product path on a TPU, one process, no children.

The quickest proof that delta-tpu still starts on the chip: it drives the
normal entry points (`Table.for_path(...).latest_snapshot()`, `update()`,
`checkpoint()`, `scan`, `write_table`, OPTIMIZE ZORDER, the SQL engine)
over a seeded 1M-action log and a 4M-row table, compares every answer
with the sequential `HostEngine` oracle, and prints one JSON object per
phase. It fails — naming the phase — on the first check that does not
hold, and it fails in phase `device` when JAX finds no TPU: there is no
CPU fallback here.

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --chips 4   # the mesh-sharded replay only

The last stdout line is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FILES_PER_COMMIT = 100   # BASELINE.json configs[1]: 10M adds / 100k commits
APPEND_COMMITS = 100
DATA_ROWS = 4_000_000
DATA_FILES = 64
DIM_ROWS = 1_000

# one override per gated route: every device kernel runs once
FORCE_ENV = {
    "DELTA_TPU_REPLAY_ROUTE": "single",
    "DELTA_TPU_DEVICE_PARSE": "force",
    "DELTA_TPU_DEVICE_DECODE": "force",
    "DELTA_TPU_DEVICE_SKIP": "force",
}

def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: check failed: {msg}")


def compiled_programs(records) -> list:
    """[dispatch, program, backend seconds, "cache" | "built"] of every
    program the compiler reported inside one of the dispatch `records`
    (`obs/device.py` joins JAX's compile events to the open dispatch)."""
    return [[r["kernel"], p["fun_name"], round(p["compile_s"], 3),
             "cache" if p["cache_hit"] else "built"]
            for r in records for p in r.get("programs", ())]


class Step:
    """Wall clock, gate decisions and device dispatches of one step,
    with the compile seconds its dispatch records carry, run under the
    route overrides `env` (the host oracles run outside a step, so they
    never see an override). Every API called inside returns
    numpy/Arrow, so the wall time has `block_until_ready` semantics."""

    def __init__(self, env=None):
        self._env = env or {}

    def __enter__(self):
        os.environ.update(self._env)
        self._ts = time.time_ns()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._t0
        self._te = time.time_ns()
        for k in self._env:
            del os.environ[k]
        return False

    def _mine(self, records):
        return [r for r in records
                if self._ts <= r["ts_unix_ns"] <= self._te]

    def report(self) -> dict:
        from delta_tpu import obs

        gates: dict = {}
        for rec in self._mine(obs.get_gate_records()):
            g = gates.setdefault(rec["gate"],
                                 {"decisions": collections.Counter()})
            g["decisions"][f"{rec['chosen']}/{rec['reason']}"] += 1
            if rec["predicted_s"]:
                g.setdefault("predicted_s", rec["predicted_s"])
                g.setdefault("inputs", rec["inputs"])
        mine = self._mine(obs.get_dispatch_records())
        dispatches = collections.Counter(r["kernel"] for r in mine)
        shapes = [f"{r['kernel']}{r['key']}" for r in mine if r["compile"]]
        compile_s = sum(r["compile_s"] for r in mine)
        return {
            "wall_s": round(self.wall_s, 3),
            "compile_s": round(compile_s, 3),
            "steady_s": round(self.wall_s - compile_s, 3),
            "programs": compiled_programs(mine),
            "gates": gates,
            "dispatches": dispatches,
            "new_shapes": shapes,
        }


def counters(names) -> dict:
    from delta_tpu import obs

    return {n: obs.counter(n).value for n in names}


def paths_digest(paths) -> dict:
    paths = sorted(paths)
    return {"n": len(paths),
            "sha256": hashlib.sha256("\n".join(paths).encode()).hexdigest()}


def snapshot_digest(snap) -> dict:
    live = snap.state.add_files_table.column("path").to_pylist()
    return {
        "version": snap.version,
        "num_files": snap.num_files,
        "size_in_bytes": snap.size_in_bytes,
        "sha256": paths_digest(live)["sha256"],
    }


def host_digest(path: str) -> dict:
    """Cold `HostEngine` load: the sequential oracle (`host_wall_s` is
    its wall time, for the reader; it is not part of the comparison)."""
    from delta_tpu import Table
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.replay.columnar import clear_parse_cache

    clear_parse_cache()
    t0 = time.perf_counter()
    digest = snapshot_digest(
        Table.for_path(path, HostEngine()).latest_snapshot())
    return {**digest, "host_wall_s": round(time.perf_counter() - t0, 3)}


def same(got: dict, want: dict) -> bool:
    return all(got[k] == want[k] for k in got)


# ------------------------------------------------------------- device --


def phase_device(chips: int) -> dict:
    import jax
    import numpy as np

    from delta_tpu import native, obs
    from delta_tpu.engine.tpu import configure_compilation_cache
    from delta_tpu.parallel import gate

    configure_compilation_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    check(device["platform"] == "tpu",
          f"JAX found no TPU (platform {device['platform']!r})")
    check(device["count"] == chips,
          f"{device['count']} devices visible, --chips {chips}")
    check(native.available(allow_compile=True),
          "native scanner did not build (no g++?): the generic parser "
          "is a different program")

    def h2d_seconds(nbytes: int):
        buf = np.random.default_rng(0).integers(
            0, 256, nbytes, dtype=np.uint8)
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.device_put(buf, devs[0]).block_until_ready()
            out.append(time.perf_counter() - t0)
        return sorted(out)

    jax.device_put(np.zeros(4, np.uint8), devs[0]).block_until_ready()
    rtt = []
    for _ in range(9):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(np.zeros(1, np.int32), devs[0]))
        rtt.append(time.perf_counter() - t0)
    h8, h64 = h2d_seconds(8 << 20), h2d_seconds(64 << 20)
    model = gate.link_model()
    emit("device", device=device,
         conditions=obs.capture_conditions(),
         compile_cache=jax.config.jax_compilation_cache_dir,
         link_measured={
             "h2d_8MiB_s": h8, "h2d_64MiB_s": h64,
             "h2d_8MiB_median_bytes_per_s": (8 << 20) / h8[2],
             "h2d_64MiB_median_bytes_per_s": (64 << 20) / h64[2],
             "roundtrip_4B_s": sorted(rtt),
         },
         link_assumed_by_gate={
             "h2d_bytes_per_s": model.h2d_bps, "rtt_s": model.rtt_s,
             "host_rows_per_s": model.host_rows_per_s,
             "device_rows_per_s": model.device_rows_per_s,
         })
    return device


# ---------------------------------------------------------------- log --


def make_log(workdir: str, commits: int, seed: int):
    """Seeded `commits` + APPEND_COMMITS commit log; the tail commits
    wait in a staging directory for the `update()` step."""
    from benchmarks.workloads import synth_delta_log

    path = os.path.join(workdir, "log_table")
    staged = os.path.join(workdir, "log_staged")
    for d in (path, staged):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(staged)
    t0 = time.perf_counter()
    synth_delta_log(path, commits + APPEND_COMMITS, FILES_PER_COMMIT,
                    seed=seed)
    reset_log(path, staged, commits)
    log = os.path.join(path, "_delta_log")
    nbytes = sum(os.path.getsize(os.path.join(log, f))
                 for f in os.listdir(log))
    emit("log.generate", commits=commits,
         actions=commits * FILES_PER_COMMIT, json_bytes=nbytes,
         wall_s=round(time.perf_counter() - t0, 3))
    return path, staged


def reset_log(path: str, staged: str, commits: int) -> None:
    """Back to the `commits`-commit JSON-only log: tail commits return
    to staging; checkpoints, their hint and checksums go."""
    log = os.path.join(path, "_delta_log")
    for name in os.listdir(log):
        full = os.path.join(log, name)
        if name.endswith(".json") and name[:20].isdigit():
            if int(name[:20]) >= commits:
                os.replace(full, os.path.join(staged, name))
        elif os.path.isdir(full):
            shutil.rmtree(full)
        else:
            os.unlink(full)


def append_staged(path: str, staged: str) -> None:
    for name in sorted(os.listdir(staged)):
        os.replace(os.path.join(staged, name),
                   os.path.join(path, "_delta_log", name))


def scan_predicate(commits: int):
    """Range on `x` that keeps ~1% of the files (file ids rise with the
    version; `x` stats are [fid*1000, (fid+1)*1000])."""
    from delta_tpu.expressions import col, lit

    n_adds = commits * int(FILES_PER_COMMIT * 0.8)
    lo = int(n_adds * 0.90) * 1000
    hi = lo + int(n_adds * 0.008) * 1000
    return (col("x") >= lit(lo)) & (col("x") < lit(hi))


def log_leg(leg: str, env: dict, path: str, staged: str, commits: int,
            oracle: dict) -> None:
    """Cold load, `update()`, checkpoint load and a filtered scan on the
    default engine, each compared with `HostEngine`. `oracle` carries
    the host answers for the JSON-only states from the first leg to the
    second (the checkpoint is rewritten by each leg, so its oracle and
    the scan's are taken again)."""
    from delta_tpu import Table
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.replay.columnar import clear_parse_cache

    reset_log(path, staged, commits)
    phase = f"log.{leg}"

    clear_parse_cache()
    with Step(env) as st:
        tbl = Table.for_path(path)
        got = snapshot_digest(tbl.latest_snapshot())
    check(isinstance(tbl.engine, TpuEngine), "default engine is TpuEngine")
    want = oracle["cold"] = oracle.get("cold") or host_digest(path)
    emit(phase, step="cold_load", digest=got, host=want, **st.report())
    check(same(got, want), f"{phase} cold load differs from HostEngine")

    append_staged(path, staged)
    with Step(env) as st:
        got = snapshot_digest(tbl.update())
    want = oracle["update"] = oracle.get("update") or host_digest(path)
    emit(phase, step="update", digest=got, host=want, **st.report())
    check(got["version"] == commits + APPEND_COMMITS - 1,
          f"{phase} update() did not advance to the appended commits")
    check(same(got, want), f"{phase} update() differs from HostEngine")

    with Step(env) as st:
        tbl.checkpoint()
    emit(phase, step="checkpoint_write", **st.report())
    clear_parse_cache()
    with Step(env) as st:
        tbl2 = Table.for_path(path)
        snap = tbl2.latest_snapshot()
        got = snapshot_digest(snap)
    check(snap.log_segment.checkpoint_version == got["version"],
          f"{phase} load did not start from the checkpoint")
    want = host_digest(path)
    emit(phase, step="checkpoint_load", digest=got, host=want,
         **st.report())
    check(same(got, want),
          f"{phase} checkpoint load differs from HostEngine")

    pred = scan_predicate(commits)
    with Step(env) as st:
        got = paths_digest(snap.scan(filter=pred).file_paths())
    host_snap = Table.for_path(path, HostEngine()).latest_snapshot()
    want = paths_digest(host_snap.scan(filter=pred).file_paths())
    emit(phase, step="scan", kept=got, host=want,
         total_files=snap.num_files, **st.report())
    check(0 < got["n"] < snap.num_files // 20,
          f"{phase} predicate kept {got['n']} files, not ~1%")
    check(got == want, f"{phase} scan differs from the HostEngine scan")


def phase_log(path: str, staged: str, commits: int) -> None:
    from delta_tpu import obs
    from delta_tpu.obs import hbm
    from delta_tpu.parallel import gate

    check(not any(k in os.environ for k in FORCE_ENV),
          "a route variable is set; the default leg needs none")
    oracle: dict = {}
    log_leg("default", {}, path, staged, commits, oracle)

    watched = ([r.fallback_counter for r in gate.ROUTES.values()]
               + ["gate.route_failures", "device.budget_violations"])
    progress = ["parse.device_windows", "decode.device_parts",
                "scan.device_plans"]
    before = counters(watched + progress)
    obs.set_device_obs_mode("strict")
    obs.set_hbm_obs_mode("strict")
    t_forced = time.time_ns()
    log_leg("forced", FORCE_ENV, path, staged, commits, oracle)
    after = counters(watched + progress)
    kernels = collections.Counter(
        r["kernel"] for r in obs.get_dispatch_records()
        if r["ts_unix_ns"] >= t_forced)
    audit = hbm.audit()
    emit("log.forced", step="non_vacuity", before=before, after=after,
         dispatches=kernels, hbm_audit_ok=audit["ok"])
    for name in watched:
        check(after[name] == before[name],
              f"log.forced counter {name} moved: "
              f"{before[name]} -> {after[name]}")
    check(after["device.budget_violations"] == 0, "budget violations")
    for name in progress:
        check(after[name] > before[name],
              f"log.forced {name} did not move: the device route "
              "did not run")
    check(any(k.startswith("replay.") for k in kernels),
          "log.forced saw no replay.* dispatch")
    check(audit["ok"], f"hbm audit: {audit}")
    obs.set_device_obs_mode("on")


# --------------------------------------------------------------- data --


def phase_data(workdir: str, seed: int) -> None:
    """The kernels the log does not reach: the Z-order interleave tile
    and the device SQL operators."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    import delta_tpu.api as dta
    from delta_tpu import Table, obs, sqlengine
    from delta_tpu.catalog import Catalog
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.expressions import col, lit
    from delta_tpu.obs import hbm

    root = os.path.join(workdir, "data")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    fact_path = os.path.join(root, "fact")
    dim_path = os.path.join(root, "dim")
    rng = np.random.default_rng(seed)
    a_np = rng.integers(0, 1 << 20, DATA_ROWS, dtype=np.int64)
    fact = pa.table({
        "a": a_np,
        "b": rng.integers(0, 1 << 20, DATA_ROWS, dtype=np.int64),
        "c": np.arange(DATA_ROWS, dtype=np.int64),
    })
    dim = pa.table({
        "k": np.arange(DIM_ROWS, dtype=np.int64),
        "grp": np.arange(DIM_ROWS, dtype=np.int64) % 16,
    })
    eng = TpuEngine()
    with Step() as st:
        dta.write_table(fact_path, fact, mode="error", engine=eng,
                        target_rows_per_file=DATA_ROWS // DATA_FILES)
        dta.write_table(dim_path, dim, mode="error", engine=eng)
    tbl = Table.for_path(fact_path, eng)
    n_files = tbl.latest_snapshot().num_files
    emit("data", step="write", rows=DATA_ROWS, files=n_files,
         **st.report())
    check(n_files >= DATA_FILES, f"write made {n_files} files")

    with Step() as st:
        tbl.optimize().execute_zorder_by("a", "b")
    rep = st.report()
    emit("data", step="zorder", **rep)
    check(rep["dispatches"].get("zorder.curve_perm", 0) >= 1,
          "OPTIMIZE ZORDER made no zorder.curve_perm dispatch")

    box = ((col("a") >= lit(100_000)) & (col("a") < lit(200_000))
           & (col("b") >= lit(500_000)) & (col("b") < lit(700_000)))
    with Step() as st:
        got = dta.read_table(fact_path, filter=box, engine=eng)
    a, b = fact.column("a"), fact.column("b")
    mask = pc.and_(
        pc.and_(pc.greater_equal(a, 100_000), pc.less(a, 200_000)),
        pc.and_(pc.greater_equal(b, 500_000), pc.less(b, 700_000)))
    want = fact.filter(mask).sort_by("c")
    got = got.select(["a", "b", "c"]).sort_by("c")
    emit("data", step="box_read", rows=got.num_rows,
         want_rows=want.num_rows, **st.report())
    check(want.num_rows > 0 and got.equals(want),
          "box read after ZORDER differs from Arrow filtering")

    query = ("SELECT d.grp, COUNT(*) AS n, SUM(f.b) AS sb, MAX(f.c) AS mc "
             "FROM dim d, fact f WHERE f.fk = d.k GROUP BY d.grp")
    fk_path = os.path.join(root, "fact_fk")
    star = fact.append_column("fk", pa.array(a_np % DIM_ROWS))
    dta.write_table(fk_path, star, mode="error", engine=eng,
                    target_rows_per_file=DATA_ROWS // 8)
    cat = Catalog(os.path.join(root, "catalog"), engine=eng)
    cat.register("dim", dim_path)
    cat.register("fact", fk_path)
    obs.set_device_obs_mode("strict")
    obs.set_hbm_obs_mode("strict")
    before = counters(["sql.device_queries", "sql.device_fallbacks",
                       "gate.route_failures", "device.budget_violations"])
    with Step({"DELTA_TPU_DEVICE_SQL": "force"}) as st:
        got = sqlengine.execute_select(query, catalog=cat)
    after = counters(list(before))
    host_cat = Catalog(os.path.join(root, "catalog"), engine=HostEngine())
    want = sqlengine.execute_select(query, catalog=host_cat)
    rows = sorted(got.to_pylist(), key=repr)
    audit = hbm.audit()
    emit("data", step="sql", rows=len(rows), before=before, after=after,
         hbm_audit_ok=audit["ok"], **st.report())
    check(len(rows) == 16 and rows == sorted(want.to_pylist(), key=repr),
          "device SQL rows differ from the HostEngine catalog's")
    check(after["sql.device_queries"] > before["sql.device_queries"],
          "sql.device_queries did not move")
    for name in ("sql.device_fallbacks", "gate.route_failures",
                 "device.budget_violations"):
        check(after[name] == before[name], f"sql counter {name} moved")
    check(audit["ok"], f"hbm audit: {audit}")
    obs.set_device_obs_mode("on")


# --------------------------------------------------------------- mesh --


def phase_mesh(path: str, chips: int) -> None:
    """The mesh-sharded replay, asked for the documented way
    (`TpuEngine(mesh=make_mesh())`), against the single-chip kernel and
    the host oracle."""
    from delta_tpu import Table
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.parallel.mesh import make_mesh
    from delta_tpu.replay.columnar import clear_parse_cache

    # the replay route is this phase's subject: the commit parse stays
    # on the host scanner (the one-chip run covers the device parse)
    host_parse = {"DELTA_TPU_DEVICE_PARSE": "off"}

    def load(engine, env):
        clear_parse_cache()
        with Step(env) as st:
            snap = Table.for_path(path, engine).latest_snapshot()
            digest = snapshot_digest(snap)
        return snap, digest, st.report()

    snap, sharded, rep = load(TpuEngine(mesh=make_mesh()), host_parse)
    decisions = rep["gates"].get("replay", {}).get("decisions", {})
    resident = snap.state.resident
    check(resident is not None, "sharded load left no resident key lane")
    shard_devices = sorted(
        str(s.device) for s in resident.key_sh.addressable_shards)
    emit("mesh", step="sharded_load", digest=sharded,
         shard_devices=shard_devices,
         shard_rows=[int(n) for n in resident.n_real], **rep)
    check("sharded/forced" in decisions,
          f"no forced sharded replay decision: {decisions}")
    check(len(set(shard_devices)) == chips,
          f"key lane shards sit on {shard_devices}")

    # the single-chip kernel, whatever the gate would have chosen
    _, single, rep = load(TpuEngine(replay_shards=1),
                          {**host_parse, "DELTA_TPU_REPLAY_ROUTE": "single"})
    emit("mesh", step="single_load", digest=single, **rep)
    check(rep["dispatches"].get("replay.single_fa", 0) >= 1,
          "single-chip load made no replay.single_fa dispatch")
    host = host_digest(path)
    emit("mesh", step="host_load", digest=host)
    check(sharded == single and same(single, host),
          "sharded, single-chip and host digests differ")

    _, default, rep = load(TpuEngine(), host_parse)
    emit("mesh", step="default_engine_load", digest=default, **rep)
    check(same(default, host), "default engine digest differs from host")


# --------------------------------------------------------------- main --


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--commits", type=int, default=10_000)
    ap.add_argument("--workdir",
                    default=os.path.join(ROOT, ".chip_smoke_work"))
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)

    os.makedirs(args.workdir, exist_ok=True)
    # the native scanner builds inside the work directory, not $HOME
    os.environ.setdefault("DELTA_TPU_NATIVE_CACHE",
                          os.path.join(args.workdir, "native"))
    import jax

    from delta_tpu import obs

    # on for the whole run: the dispatch records carry what the
    # compiler reports (compile seconds, program name, cache hit)
    obs.set_device_obs_mode("on")

    t0 = time.perf_counter()
    phase = "device"
    try:
        device = phase_device(args.chips)
        phase = "log.generate"
        path, staged = make_log(args.workdir, args.commits, args.seed)
        if args.chips == 1:
            phase = "log"
            phase_log(path, staged, args.commits)
            phase = "data"
            phase_data(args.workdir, args.seed)
        else:
            phase = "mesh"
            phase_mesh(path, args.chips)
    except BaseException:
        print(f"chip_smoke: FAILED in phase {phase}",
              file=sys.stderr, flush=True)
        raise
    stats = jax.devices()[0].memory_stats() or {}
    records = obs.get_dispatch_records()
    by_dispatch = collections.defaultdict(set)
    for kernel, program, _, _ in compiled_programs(records):
        by_dispatch[kernel].add(program)
    emit("total", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         compile_s=round(sum(r["compile_s"] for r in records), 3),
         programs={k: sorted(v) for k, v in sorted(by_dispatch.items())},
         wall_s=round(time.perf_counter() - t0, 3))
    launched = {p for programs in by_dispatch.values() for p in programs}
    check(not launched & {"jit(kernel)", "jit(fn)", "jit(<lambda>)"},
          f"a dispatch launched an unnamed program: {dict(by_dispatch)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
