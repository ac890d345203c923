"""The deployment `deltalog-10m-ckpt10` and its cell
`ckpt-cold-load-10m-v5e4`, at a test's size on the CPU, on four of
conftest's eight virtual devices: the generator's manifest against both
plain references (`oracle`, and `shard_oracle` at 1, 2 and 4 lists) and
against `HostEngine`, `TpuEngine` on one device and `TpuEngine` on the
mesh; the shares adding up; whole runs of the cell; the ten readers on
a run written by hand; the sharded route's spans, `attrs` and counters;
residency ending with its owner; two meshes broken on purpose.

A test's log is under the gate's 4M rows, so the engine is given its
shard count (`replay_shards=4`): intent the gate keeps (`forced`). On
the chip the cell passes nothing and the gate decides.

`python3 tests/chipbench/test_chipbench_mesh.py <broken mesh> --seed <n>
--seconds <s>` runs the cell itself, at its real size and on four chips,
on one of them: the last line is the harness's result."""

import contextlib
import gc
import hashlib
import importlib.util
import json
import os
import threading
import time
import types
from unittest import mock

import numpy as np
import pytest

from chipbench import harness, trace_reduce
from chipbench.gen import deltalog
from chipbench.reference import oracle, shard_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "mesh", "benchmark.json")
CELL = "ckpt-cold-load-10m-v5e4"
CONFIG = "deltalog-10m-ckpt10"
SHARDS = 4
SEEDS = [7, 2**31 + 17, 2**31 + 18]
SHAPE = dict(actions_per_commit=100, remove_fraction=0.2)
# every add and remove of 300 commits replayed, tombstones and all; and
# the deployment's form: a checkpoint of live adds and the commits since
LOGS = {"json": dict(SHAPE, commits=300),
        "ckpt10": dict(SHAPE, commits=300, checkpoint_interval=10,
                       retained_commits=40)}
MS = 1_000_000
MESH_METRICS = {"mesh_load_ms", "mesh_sharded_pct", "mesh_shard_route_ms",
                "mesh_replay_host_ms", "mesh_device_wait_ms",
                "mesh_h2d_mb_per_op", "mesh_collective_pct",
                "mesh_shard_skew_pct", "mesh_replay_roofline",
                "mesh_idle_pct"}
FROM_THE_TRACE = {"mesh_collective_pct", "mesh_shard_skew_pct",
                  "mesh_replay_roofline"}


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"mesh_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return module("layers", name).read


def engine(shards):
    from delta_tpu.engine.tpu import TpuEngine

    return TpuEngine(replay_shards=shards)


class OnTheMesh(DeltaTpu):
    """The system as the cell drives it, on four of the virtual devices."""

    def load(self, path):
        from delta_tpu import Table

        table = Table.for_path(path, engine=engine(SHARDS))
        return table, table.latest_snapshot()


def digest(paths) -> str:
    return hashlib.sha256("\n".join(sorted(paths)).encode()).hexdigest()


def of_summary(summary: dict) -> tuple:
    return (summary["num_live"], summary["live_bytes"],
            digest(key.split("|")[0] for key in summary["live_keys"]))


def of_engine(path, eng) -> tuple:
    from delta_tpu import Table

    snapshot = Table.for_path(path, engine=eng).latest_snapshot()
    return (snapshot.num_files, snapshot.size_in_bytes,
            digest(snapshot.state.add_files_table.column("path")
                   .to_pylist()))


# ---- manifest = both references = both engines = the mesh ----

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("log", sorted(LOGS))
def test_every_replay_finds_the_manifests_files(tmp_path, log, seed):
    from delta_tpu import obs
    from delta_tpu.engine.host import HostEngine

    m = deltalog.generate(str(tmp_path), LOGS[log], seed)
    want = (m.num_files(), m.size_in_bytes(), m.digest())
    plain = oracle.read_table_state(m.table_path).summary()
    assert of_summary(plain) == want
    if log == "json":   # a remove lies in a later commit than its add
        assert len(plain["tombstone_keys"]) > 1000
    for shards in (1, 2, SHARDS):
        dealt = shard_oracle.read_table_state(m.table_path, shards).summary()
        assert of_summary(dealt) == want
        assert dealt["tombstone_keys"] == plain["tombstone_keys"]
        assert dealt["live_keys"] == plain["live_keys"]
    launches = obs.counter("replay.sharded_launches")
    before = launches.value
    assert of_engine(m.table_path, HostEngine()) == want
    assert of_engine(m.table_path, engine(1)) == want
    assert launches.value == before      # neither went near the mesh
    assert of_engine(m.table_path, engine(SHARDS)) == want
    assert launches.value == before + 1


@pytest.mark.parametrize("seed", SEEDS)
def test_the_shares_add_up(tmp_path, seed):
    m = deltalog.generate(str(tmp_path), LOGS["json"], seed)
    plain = oracle.read_table_state(m.table_path)
    dealt = shard_oracle.read_table_state(m.table_path, SHARDS)
    live = [set(s.live) for s in dealt.shards]
    tombs = [s.tombstones for s in dealt.shards]
    assert all(live) and all(tombs)     # no list is idle
    for i in range(SHARDS):
        for j in range(i + 1, SHARDS):
            assert not (live[i] | tombs[i]) & (live[j] | tombs[j])
    assert set().union(*live) == set(plain.live)
    assert set().union(*tombs) == set(plain.tombstones)
    # every action of one path in one list
    home = {}
    for i, s in enumerate(dealt.shards):
        for _, path, _, _ in s.rows:
            assert home.setdefault(path, i) == i
    assert sum(len(s.rows) for s in dealt.shards) == m.load_actions - 2


def test_the_programs_routing_keeps_a_paths_rows_in_one_shard():
    from delta_tpu.ops.replay import derive_fa_flags
    from delta_tpu.parallel import sharded_replay

    rng = np.random.default_rng(5)
    raw = rng.integers(0, 4000, 20_000)
    _, first = np.unique(raw, return_index=True)   # dense codes in the
    order = np.argsort(first)                      # order of appearance
    codes = np.empty(len(order), np.uint32)
    codes[order] = np.arange(len(order))
    path = codes[np.searchsorted(np.sort(np.unique(raw)), raw)]
    fa = sharded_replay.route_to_shards_fa(
        path, np.zeros_like(path), derive_fa_flags(path),
        rng.random(len(path)) < 0.8, SHARDS)
    assert fa is not None
    rows = fa.scatter
    seen = np.sort(rows[rows >= 0])
    assert (seen == np.arange(len(path))).all()    # each row once
    for s in range(SHARDS):
        mine = rows[s][rows[s] >= 0]
        assert (path[mine] % SHARDS == s).all()
        assert (np.diff(mine) > 0).all()           # in the log's order


def test_a_rule_that_parts_a_files_actions_keeps_files_that_are_gone(
        tmp_path):
    m = deltalog.generate(str(tmp_path), LOGS["json"], SEEDS[0])
    by_row = shard_oracle.read_table_state(
        m.table_path, SHARDS, shard_of=lambda row, path, n: row % n)
    assert by_row.summary()["num_live"] > m.num_files()


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "shard_oracle.py")) as f:
        text = f.read()
    assert "import delta_tpu" not in text and "from delta_tpu" not in text
    assert "numpy" not in text and "from chipbench" not in text


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == CONFIG
    assert cell.entry["chips"] == 4 and cell.mix["driver"] == "cold_load"
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    # a lower bound, and no place in the file: a later PR may add to the
    # cell's metrics, and to the file before or behind its entries
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert MESH_METRICS <= mine
    for name in MESH_METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MESH_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["source"] == (
            "device_trace" if name in FROM_THE_TRACE | {"mesh_idle_pct"}
            else "program_counter" if name in ("mesh_sharded_pct",
                                               "mesh_h2d_mb_per_op")
            else "program_span")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)


def test_the_configuration_is_the_source_whole():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    assert config["reduced"] == {}
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-ckpt10.json")) as f:
        sibling = json.load(f)
    assert config["generator"] == dict(sibling["generator"], commits=100_000)
    assert config["guarantees"] == sibling["guarantees"]
    assert config["environment"] == sibling["environment"]
    assert set(sibling["assumed"]) | {"checkpoint_parts"} == set(
        config["assumed"])
    for word in ("checkpoint_writer", "tombstones", "checkpoints_in_window",
                 "storage", "schema", "allocator"):
        assert config["assumed"][word] == sibling["assumed"][word]
    assert "four chips" in config["deployment"]
    assert "jax.devices() = 4" in config["deployment"]


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5):
    return harness.run_cell("tiny-mesh-cold-load", seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system or OnTheMesh())


def test_a_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    result = run()
    assert result["correct"] and result["failed"] == 0
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    out = capsys.readouterr().out
    for compared in ("num_files", "size_in_bytes", "live_paths_sha256"):
        assert f"window {compared}: compared" in out
    assert "mismatches 0 (limit 0)" in out and "actions/s" in out


def test_a_traced_run_reads_the_cells_metrics(monkeypatch):
    # no device plane on the CPU, so the three readers of the device's
    # trace are handed the planes the run's own launches would have left
    seen = {}
    reduce_planes = trace_reduce.reduce_planes

    def with_four_planes(planes):
        from delta_tpu import obs

        reduced = reduce_planes(planes)
        offset = reduced.window[0] - seen["window_unix_ns"]
        mine = [r for r in obs.get_dispatch_records()
                if r["kernel"] == "replay.sharded_fa"
                and r["ts_unix_ns"] >= seen["window_unix_ns"]]
        events = [[] for _ in range(SHARDS)]
        for r in mine:
            at = r["ts_unix_ns"] - r["wall_ns"] // 2 + offset
            for p in range(SHARDS):
                events[p] += [
                    ("jit_replay_sharded_fa/%sort.3", at, at + 900 + 50 * p),
                    ("jit_replay_sharded_fa/%psum_invariant.7",
                     at + 900 + 50 * p, at + 1100)]
        seen["launches"] = len(mine)
        return trace_reduce.Reduced(reduced.window, events)

    monkeypatch.setattr(trace_reduce, "reduce_planes", with_four_planes)
    real = harness.per_layer

    def per_layer(cell, window, w_start, w_end, trace_dir, device):
        seen["window_unix_ns"] = w_start
        device = dict(device, kind="TPU v5 lite")
        return real(cell, window, w_start, w_end, trace_dir, device)

    monkeypatch.setattr(harness, "per_layer", per_layer)
    result = run(trace=True, seconds=0.6)
    assert result["correct"] and seen["launches"] == result["attempted"]
    assert set(result["metrics"]) == MESH_METRICS     # all ten, none null
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["mesh_sharded_pct"] == 100
    assert m["mesh_load_ms"] > m["mesh_replay_host_ms"] > 0
    assert m["mesh_replay_host_ms"] > m["mesh_shard_route_ms"] > 0
    assert m["mesh_device_wait_ms"] > 0
    # 72,380 rows: 18,095 a shard in a bucket of 32,768; two bit planes
    # of a bucket and three byte planes of at least 128 refs a shard
    assert m["mesh_h2d_mb_per_op"] == pytest.approx(
        SHARDS * (2 * 32768 / 8 + 3 * 128) / 1e6, rel=0.02)
    # chip 0 of the planes above: 200 of its 1,100 ns in the collective;
    # the planes' own work 900, 950, 1,000 and 1,050 ns
    assert m["mesh_collective_pct"] == pytest.approx(100 * 200 / 1100)
    assert m["mesh_shard_skew_pct"] == pytest.approx(100 * (1050 / 975 - 1))
    assert 0 < m["mesh_replay_roofline"] < 100
    assert 99 < m["mesh_idle_pct"] < 100
    # ten names at most, by idle time: the route's binning is among them
    assert "replay.shard_route" in dict(result["breakdown"]["idle_gaps"])


# ---- the ten readers on a run written by hand ----

def span(name, start_ms, dur_ms, parent=None, thread=None, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}",
            "parent_id": parent, "start_unix_ns": start_ms * MS,
            "duration_ns": dur_ms * MS,
            "thread_id": thread or threading.get_ident(), "attrs": attrs}


def a_load(at, load_ms, replay_ms, route_ms, wait_ms, gather_ms=200):
    """The spans of one load that begins `at` ms: the replay 1,000 ms in,
    its route 100 ms further, then transfer, reconcile and gather."""
    load, replay = f"snapshot.load@{at + 10}", f"snapshot.replay@{at + 1000}"
    route_end = at + 1100 + route_ms
    reconcile = f"replay.shard_reconcile@{route_end + 10}"
    return [
        span("snapshot.load", at + 10, load_ms),
        span("snapshot.replay", at + 1000, replay_ms, load),
        span("replay.shard_route", at + 1100, route_ms, replay),
        span("replay.shard_transfer", route_end, 10, replay),
        span("replay.shard_reconcile", route_end + 10, wait_ms + 10, replay),
        span("replay.wait", route_end + 20, wait_ms, reconcile),
        span("replay.shard_gather", route_end + 30 + wait_ms, gather_ms,
             replay)]


OPS = [{"kind": "load", "start_unix_ns": 0, "end_unix_ns": 3000 * MS},
       {"kind": "load", "start_unix_ns": 4000 * MS,
        "end_unix_ns": 7200 * MS}]
RECORDED = (a_load(0, 2490, 1400, 400, 60) + a_load(4000, 2700, 1600, 500, 70)
            # a worker's wait is on no operation's path, and under no replay
            + [span("replay.wait", 100, 900, thread=1)])
GATES = ([{"gate": "replay", "chosen": "sharded"}] * 2
         + [{"gate": "parse", "chosen": "host"}] * 4)
M = 1_572_864
ATTRS = {"shards": 4, "m": M, "ref_planes": 3, "want_key": True}
H2D = 4 * (2 * M // 8 + 3 * 128)


def launch(begin_ms, end_ms, **more):
    return dict({"kernel": "replay.sharded_fa", "h2d_bytes": H2D,
                 "ts_unix_ns": end_ms * MS, "wall_ns": (end_ms - begin_ms) * MS,
                 "attrs": dict(ATTRS)}, **more)


DISPATCHES = [launch(1500, 1580), launch(5600, 5690),
              {"kernel": "json_parse.window", "h2d_bytes": 5_000_000,
               "ts_unix_ns": 900 * MS, "wall_ns": 100 * MS}]
# a chip's own work ends when it ends; the psum ends on all four at once
ENDS = {1520: ([1546, 1548, 1544, 1545], 1549),
        5640: ([5668, 5666, 5670, 5667], 5671)}
SORT = {1520: 1530, 5640: 5652}


def device_plane(p):
    ops, modules = [], []
    for begin, (ends, psum_end) in ENDS.items():
        modules.append(("jit_replay_sharded_fa(7)", begin * MS,
                        (psum_end - begin) * MS))
        ops += [("%fusion.1 = u32[] fusion()", begin * MS,
                 (SORT[begin] - begin) * MS),
                ("%sort.3 = sort()", SORT[begin] * MS,
                 (ends[p] - SORT[begin]) * MS),
                ("%psum_invariant.7 = s32[] all-reduce()", ends[p] * MS,
                 (psum_end - ends[p]) * MS)]
    # another program's operation, on every chip, counted busy and no more
    modules.append(("jit_replay_single_fa(3)", 3000 * MS, 5 * MS))
    ops.append(("%sort.9 = sort()", 3000 * MS, 5 * MS))
    return (f"/device:TPU:{p}", [("XLA Modules", modules), ("XLA Ops", ops)])


PLANES = [("/host:CPU", [("python3", [("chipbench.window", 0, 8000 * MS)])])
          ] + [device_plane(p) for p in range(SHARDS)]
LEAST_S = (H2D // 4 + 3 * M * 4 + M // 8) / 819e9


def recorded(spans=RECORDED, gates=GATES, dispatches=DISPATCHES,
             planes=PLANES):
    return types.SimpleNamespace(
        ops=OPS, spans=spans, gates=list(gates), dispatches=list(dispatches),
        trace=trace_reduce.reduce_planes(planes), device_kind="TPU v5 lite",
        to_trace_ns=lambda unix_ns: unix_ns)


@pytest.mark.parametrize("name,want", [
    ("mesh_load_ms", (2490 + 2700) / 2),
    ("mesh_sharded_pct", 100),
    ("mesh_shard_route_ms", (400 + 200 + 500 + 200) / 2),
    ("mesh_replay_host_ms", (1400 - 60 + 1600 - 70) / 2),
    ("mesh_device_wait_ms", (60 + 70) / 2),
    ("mesh_h2d_mb_per_op", H2D / 1e6),              # 1.574: 2.1 bits a row
    # chip 0: 3 + 3 ms of its 29 + 31 in the psum
    ("mesh_collective_pct", 100 * 6 / 60),
    # own work: 26, 28, 24, 25 and 28, 26, 30, 27 ms
    ("mesh_shard_skew_pct", 100 * ((28 + 30) / (25.75 + 27.75) - 1)),
    # the slowest plane, psum and all: 29 and 31 ms
    ("mesh_replay_roofline", 100 * 2 * LEAST_S / 60e-3),
    # every plane busy 29 + 31 + 5 ms of the 8 s
    ("mesh_idle_pct", 100 * (1 - 65e-3 / 8)),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_the_least_bytes_of_a_shard_follow_the_launchs_record():
    count = module("layers", "mesh_replay_bytes").mesh_replay_bytes
    fa = launch(0, 1)
    # a shard's two bit planes and refs; the key lane written, sorted and
    # kept; the winner words
    assert count(fa) == (2 * M // 8 + 3 * 128) + 3 * M * 4 + M // 8
    unkept = launch(0, 1, attrs=dict(ATTRS, want_key=False))
    assert count(fa) - count(unkept) == M * 4
    raw = {"kernel": "replay.sharded_raw", "h2d_bytes": 4 * M * 9,
           "attrs": dict(ATTRS, ref_planes=0, want_key=False)}
    assert count(raw) == M * 9 + 2 * M
    # a bound under 100% of any time a sort of m keys can take
    assert 0 < reader("mesh_replay_roofline")(recorded()) < 1


def without(spans, *names):
    return [s for s in spans if s["name"] not in names]


# the parent's records: shapes in their `key` alone
PARENTS = [{k: v for k, v in d.items() if k != "attrs"} for d in DISPATCHES]


@pytest.mark.parametrize("name,changes,want", [
    # the parent: no wait and no gather on the sharded route, no attrs
    ("mesh_shard_route_ms", dict(spans=without(RECORDED, "replay.shard_gather")),
     None),
    ("mesh_replay_host_ms", dict(spans=without(RECORDED, "replay.wait")), None),
    ("mesh_device_wait_ms", dict(spans=without(RECORDED, "replay.wait")), None),
    ("mesh_replay_roofline", dict(dispatches=PARENTS), None),
    ("mesh_h2d_mb_per_op", dict(dispatches=PARENTS), H2D / 1e6),
    ("mesh_shard_skew_pct", dict(dispatches=PARENTS),
     100 * ((28 + 30) / (25.75 + 27.75) - 1)),
    # a load that fell to one chip
    ("mesh_sharded_pct", dict(gates=[{"gate": "replay", "chosen": "sharded"},
                                     {"gate": "replay", "chosen": "single"}]),
     50),
    ("mesh_sharded_pct", dict(gates=GATES[2:]), None),
    # nothing went to the mesh
    ("mesh_h2d_mb_per_op", dict(dispatches=DISPATCHES[2:]), None),
    ("mesh_shard_skew_pct", dict(dispatches=DISPATCHES[2:]), None),
    ("mesh_replay_roofline", dict(dispatches=DISPATCHES[2:]), None),
    # no device plane: a run on the CPU
    ("mesh_collective_pct", dict(planes=PLANES[:1]), None),
    ("mesh_shard_skew_pct", dict(planes=PLANES[:1]), None),
    ("mesh_replay_roofline", dict(planes=PLANES[:1]), None),
    ("mesh_idle_pct", dict(planes=PLANES[:1]), 100),
    ("mesh_load_ms", dict(spans=[]), None),
    ("mesh_shard_route_ms", dict(spans=[]), None),
])
def test_a_reader_on_a_program_without_what_it_reads(name, changes, want):
    got = reader(name)(recorded(**changes))
    assert got is None if want is None else got == pytest.approx(want)


def test_a_program_without_a_collective_reads_zero_not_nothing():
    planes = [PLANES[0]] + [
        (name, [(line, [e for e in events if "psum" not in e[0]])
                for line, events in lines]) for name, lines in PLANES[1:]]
    assert reader("mesh_collective_pct")(recorded(planes=planes)) == 0


# ---- the sharded route seen from inside ----

def test_the_sharded_route_names_its_phases_shapes_and_counts(tmp_path):
    from delta_tpu import obs

    m = deltalog.generate(str(tmp_path), dict(LOGS["ckpt10"], commits=1200,
                                              retained_commits=100), 11)
    counters = {n: obs.counter(n) for n in (
        "replay.sharded_launches", "replay.resident_established",
        "replay.resident_released")}
    before = {n: c.value for n, c in counters.items()}
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    obs.reset_trace_buffer()
    obs.reset_device_obs()
    try:
        got = of_engine(m.table_path, engine(SHARDS))
        spans = [s.to_dict() for s in obs.get_finished_spans()]
        records = obs.get_dispatch_records()
        gates = obs.get_gate_records()
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
    assert got == (m.num_files(), m.size_in_bytes(), m.digest())
    by_name = {s["name"]: s for s in spans}
    by_id = {s["span_id"]: s for s in spans}

    def parent(name):
        return by_id[by_name[name]["parent_id"]]["name"]

    rows = m.load_actions - 2
    route = by_name["replay.shard_route"]["attrs"]
    assert route["rows"] == rows and route["shards"] == SHARDS
    # dense codes in order of appearance, modulo the shards: a deal of
    # cards, but for the few paths that come twice (a commit's removes)
    assert route["rows_min"] <= rows // SHARDS <= route["rows_max"]
    assert route["rows_max"] - route["rows_min"] < rows // 1000
    assert route["m"] == 32768
    for name in ("replay.shard_route", "replay.shard_transfer",
                 "replay.shard_reconcile", "replay.shard_gather"):
        assert parent(name) == "snapshot.replay", name
    assert parent("replay.wait") == "replay.shard_reconcile"
    wait = by_name["replay.wait"]
    assert wait["attrs"] == {"rows": rows, "bytes": SHARDS * 32768 // 8}
    assert wait["duration_ns"] < by_name["replay.shard_reconcile"][
        "duration_ns"]
    assert by_name["replay.shard_gather"]["attrs"]["rows"] == rows
    [record] = [r for r in records if r["kernel"] == "replay.sharded_fa"]
    assert record["attrs"] == {"shards": SHARDS, "m": 32768,
                               "ref_planes": 2, "want_key": True}
    assert record["d2h_bytes"] == SHARDS * 32768 // 8
    [gate] = [g for g in gates if g["gate"] == "replay"]
    assert (gate["chosen"], gate["reason"]) == ("sharded", "forced")
    gc.collect()
    after = {n: c.value - before[n] for n, c in counters.items()}
    assert after == {"replay.sharded_launches": 1,
                     "replay.resident_established": 1,
                     "replay.resident_released": 1}


def test_a_phase_of_a_small_replay_is_a_span_under_verbose_alone(tmp_path):
    from delta_tpu import obs

    m = deltalog.generate(str(tmp_path), LOGS["ckpt10"], 3)
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        of_engine(m.table_path, engine(SHARDS))
        names = {s.to_dict()["name"] for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode(None)
    assert m.load_actions < obs.PHASE_SPAN_ROWS
    assert "replay.shard_gather" not in names
    assert {"replay.shard_route", "replay.wait"} <= names


def test_the_new_counters_are_in_the_catalogue_and_the_docs():
    with open(os.path.join(ROOT, "delta_tpu", "resources",
                           "metric_names.json")) as f:
        catalogue = json.load(f)
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        docs = f.read()
    for name in ("replay.sharded_launches", "replay.resident_established",
                 "replay.resident_released"):
        assert name in catalogue["counters"] and name in docs
    assert "replay.resident_hbm_bytes" in catalogue["gauges"]
    for name in ("replay.shard_gather", "replay.psum", "rows_min",
                 "ref_planes"):
        assert name in docs


def test_the_psum_has_a_name_in_the_compiled_program():
    import jax
    import jax.numpy as jnp
    from delta_tpu.parallel import sharded_replay
    from delta_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_devices=SHARDS)
    fn = sharded_replay.build_sharded_replay_fa_fn(mesh, 2, False, True)
    words = jax.ShapeDtypeStruct((SHARDS, 1024 // 32), jnp.uint32)
    plane = jax.ShapeDtypeStruct((SHARDS, 128), jnp.uint8)
    n_real = jax.ShapeDtypeStruct((SHARDS, 1), jnp.int32)
    text = fn.lower(words, plane, plane, n_real, words).as_text(
        debug_info=True)
    assert "replay.psum" in text and "replay.sort" in text


# ---- residency ends with its owner ----

def test_a_dropped_state_gives_its_lanes_back_every_time(tmp_path):
    from delta_tpu import Table, obs

    m = deltalog.generate(str(tmp_path), LOGS["ckpt10"], 5)
    held = obs.gauge("replay.resident_hbm_bytes")
    leaks = obs.counter("hbm.resident_leaks")
    released = obs.counter("replay.resident_released")
    gc.collect()
    base = held.read()      # what other tests of this process still hold
    leaks_before, released_before = leaks.value, released.value
    for k in range(10):
        snapshot = Table.for_path(
            m.table_path, engine=engine(SHARDS)).latest_snapshot()
        assert snapshot.num_files == m.num_files()
        resident = snapshot.state.resident
        assert resident is not None
        # four lanes of a bucket of uint32 keys, one a device
        assert held.read() == base + SHARDS * resident.m * 4
        assert len(resident.key_sh.sharding.device_set) == SHARDS
        del snapshot, resident
        gc.collect()
        assert held.read() == base, k
        assert released.value == released_before + k + 1
    # an owner that ends is a release, not a leak
    assert leaks.value == leaks_before


def test_a_release_by_hand_is_counted_once(tmp_path):
    from delta_tpu import Table, obs

    m = deltalog.generate(str(tmp_path), LOGS["ckpt10"], 6)
    released = obs.counter("replay.resident_released")
    snapshot = Table.for_path(
        m.table_path, engine=engine(SHARDS)).latest_snapshot()
    resident = snapshot.state.resident
    before = released.value
    resident.release()
    resident.release()
    assert released.value == before + 1 and resident.key_sh is None
    del snapshot, resident
    gc.collect()
    assert released.value == before + 1


def no_native_scanner(monkeypatch):
    """A fresh machine, a tail of commits under 4 MB: no scanner built,
    none worth building; the generic parser reads the commits."""
    from delta_tpu import native

    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)


def test_residency_does_not_hang_on_which_parser_read_the_commits(
        tmp_path, monkeypatch):
    """What the chip showed (PERF.md, PR 40): the generic parser hands a
    tail's adds before its removes, the rows are out of order, and until
    this PR such a replay kept no key lane: on a machine with no scanner
    built, which the chip's is, no default load was ever resident."""
    from delta_tpu import Table, obs
    from delta_tpu.ops.replay import chrono_ok

    m = deltalog.generate(str(tmp_path), dict(LOGS["ckpt10"],
                                              staged_commits=3), 9)
    no_native_scanner(monkeypatch)
    table = Table.for_path(m.table_path, engine=engine(SHARDS))
    snapshot = table.latest_snapshot()
    assert snapshot.num_files == m.num_files()
    held = snapshot.state.file_actions_raw
    assert not chrono_ok(np.asarray(held.column("version")),
                         np.asarray(held.column("order")))
    resident = snapshot.state.resident
    assert resident is not None
    # every row has its slot, and a slot names the caller's row
    rows = resident.scatter[resident.scatter >= 0]
    assert (np.sort(rows) == np.arange(held.num_rows)).all()
    # and the lanes serve the refresh: the landed rows alone cross over
    appends = obs.counter("replay.resident_appends")
    fallbacks = obs.counter("replay.resident_fallbacks")
    before = appends.value, fallbacks.value
    m.land(3)
    fresh = table.update()
    assert (appends.value, fallbacks.value) == (before[0] + 1, before[1])
    assert fresh.state.resident is resident
    got = (fresh.num_files, fresh.size_in_bytes,
           digest(fresh.state.add_files_table.column("path").to_pylist()))
    assert got == (m.num_files(), m.size_in_bytes(), m.digest())


def test_rows_out_of_order_keep_their_lane_and_their_places():
    from delta_tpu.parallel import sharded_replay
    from delta_tpu.parallel.mesh import make_mesh

    # six files; the removes of versions 1 and 2 stand behind every add
    path = np.array([0, 1, 2, 3, 4, 5, 0, 3], np.uint32)
    version = np.array([0, 0, 1, 1, 2, 2, 1, 2], np.int32)
    order = np.array([0, 1, 1, 2, 1, 2, 0, 0], np.int32)
    is_add = np.array([1, 1, 1, 1, 1, 1, 0, 0], bool)
    sink = []
    live, tomb, n_live, _ = sharded_replay.sharded_replay_select(
        path, np.zeros_like(path), version, order, is_add,
        mesh=make_mesh(n_devices=SHARDS), resident_sink=sink)
    assert live.tolist() == [0, 1, 1, 0, 1, 1, 0, 0] and n_live == 4
    assert tomb.tolist() == [0, 0, 0, 0, 0, 0, 1, 1]
    [payload] = sink
    for shard in range(SHARDS):
        mine = payload.scatter[shard][payload.scatter[shard] >= 0]
        assert (path[mine] % SHARDS == shard).all()
        # a shard's slots in chronological order, whatever the rows' was
        assert sorted(mine, key=lambda r: (version[r], order[r])) == list(
            mine)


# ---- two meshes broken on purpose, through the cell's own comparison ----

class BrokenMesh(DeltaTpu):
    """The program's sharded route with one piece replaced, for the
    length of a load and of its replay. `on`: the system that loads; on
    the chip, at the real size, the default one."""

    on = OnTheMesh

    def broken(self):
        raise NotImplementedError

    def load(self, path):
        with self.broken():
            table, snapshot = self.on.load(self, path)
            snapshot.state      # the replay, now
        return table, snapshot


class RoutesByRow(BrokenMesh):
    """Rows dealt to the shards by row number: an add and the remove
    that ends it part, each wins its own shard, and the file stays. The
    raw-key route, whose shards sort whole keys: under first-appearance
    coding a shard's codes are the path's code over the shards, and a
    deal by row would make nonsense of them before it made a wrong
    answer."""

    @contextlib.contextmanager
    def broken(self):
        from delta_tpu.parallel import sharded_replay

        coords = sharded_replay._shard_coords

        def by_row(shard_of, n_shards):
            return coords(np.arange(len(shard_of)) % n_shards, n_shards)

        with mock.patch.object(sharded_replay, "route_to_shards_fa",
                               lambda *a, **k: None), \
                mock.patch.object(sharded_replay, "_shard_coords", by_row):
            yield


class DropsAShard(BrokenMesh):
    """Shard 0's winners never come home."""

    @contextlib.contextmanager
    def broken(self):
        from delta_tpu.parallel import sharded_replay

        select = sharded_replay.sharded_replay_select

        def three_of_four(path_key, *args, **kwargs):
            live, tomb, n_live, nbytes = select(path_key, *args, **kwargs)
            lost = np.asarray(path_key) % SHARDS == 0
            return live & ~lost, tomb & ~lost, n_live, nbytes

        with mock.patch.object(sharded_replay, "sharded_replay_select",
                               three_of_four):
            yield


@pytest.mark.parametrize("system", [RoutesByRow, DropsAShard])
def test_a_broken_mesh_is_not_correct(system, capsys):
    result = run(system=system(), seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1
    out = capsys.readouterr().out
    assert "window num_files: compared" in out
    assert "first mismatch: got" in out


def test_the_sound_mesh_passes_where_the_broken_ones_fail():
    assert run(seconds=0.3)["correct"]


if __name__ == "__main__":      # the cell itself, on four chips, broken
    import argparse

    t0 = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("system", choices=["RoutesByRow", "DropsAShard"])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    asked = parser.parse_args()
    BrokenMesh.on = DeltaTpu    # the default engine: the gate's own mesh
    result = harness.run_cell(CELL, asked.seed, asked.seconds, False, t0,
                              system=globals()[asked.system]())
    print(json.dumps({"system": asked.system, "cell": CELL,
                      "seed": asked.seed, "correct": result["correct"],
                      "has_to_read": False,
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "device": result["device"]}), flush=True)
    raise SystemExit(result["correct"] is not False)
