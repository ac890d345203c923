"""The deployment `deltalog-4m-stream` and its cell
`ckpt-query-under-ingest`, at a test's size on the CPU: the generator
and its manifest against the plain reference and both engines, the
driver's reading of YCSB Workload E, the cell's files, the program's
spans under `snapshot.update` and `scan.plan`, and the five readers."""

import collections
import hashlib
import importlib.util
import json
import os
import threading
import time
import types

import pytest

from chipbench import control, harness, traffic
from chipbench.gen import deltalog, deltastream
from chipbench.reference import plan_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "stream", "benchmark.json")
CELL = "ckpt-query-under-ingest"
PARAMS = dict(commits=64, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=12)
W = deltastream.batch_width(80)
MS = 1_000_000


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"stream_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "scan_under_ingest")
with open(os.path.join(ROOT, "chipbench", "mixes", "ycsb-e-scans.json")) as f:
    MIX = json.load(f)


# ---- the generator, its manifest, the reference and both engines ----

def plan_by_engine(engine_name):
    def plan(path, lo, hi):
        from delta_tpu import Table
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.engine.tpu import TpuEngine
        from delta_tpu.expressions import col, lit
        from delta_tpu.replay.columnar import clear_parse_cache

        clear_parse_cache()
        engine = {"host": HostEngine, "tpu": TpuEngine}[engine_name]()
        snap = Table.for_path(path, engine).latest_snapshot()
        pred = (col("x") >= lit(lo)) & (col("x") < lit(hi))
        return sorted(snap.scan(filter=pred).file_paths())
    return plan


PLANNERS = {"reference": plan_oracle.plan,
            "HostEngine": plan_by_engine("host"),
            "TpuEngine": plan_by_engine("tpu"),
            "TpuEngine-skip-kernel": plan_by_engine("tpu")}
# commit v holds x in [(v + 1) W, (v + 2) W]: the commits each range meets
RANGES = {
    "on-both-edges": (11 * W, 14 * W, range(9, 13)),
    "starts-past-an-edge": (11 * W + 1, 14 * W, range(10, 13)),
    "ends-past-an-edge": (11 * W, 14 * W + 1, range(9, 14)),
    "inside-one-batch": (11 * W + 5, 11 * W + 6, range(10, 11)),
    "before-the-first-batch": (0, W, range(0)),
    "the-first-batch-alone": (0, W + 1, range(0, 1)),
    "from-the-newest-on": (65 * W, 10**12, range(63, 10**6)),
}


@pytest.mark.parametrize("planner", sorted(PLANNERS))
@pytest.mark.parametrize("case", sorted(RANGES))
def test_every_planner_finds_the_manifests_files(tmp_path, monkeypatch,
                                                 planner, case):
    if planner == "TpuEngine-skip-kernel":   # the jitted kernel, on the CPU
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    m = deltastream.generate(str(tmp_path), PARAMS, seed=2**31 + 11)
    lo, hi, commits = RANGES[case]
    for landed in (0, 3, 7):    # 7 more: past the next multiple of 10
        m.land(landed)
        want = m.scan_expected(lo, hi)
        assert {int(i) // 80 for i in want} == (
            set(commits) & set(range(m.version + 1))), landed
        assert PLANNERS[planner](m.table_path, lo, hi) == [
            deltalog.path_of(int(i)) for i in want], landed


def tree(root) -> dict:
    out = {}
    for base, _, files in os.walk(root):
        for name in files:
            with open(os.path.join(base, name), "rb") as f:
                out[os.path.relpath(os.path.join(base, name), root)] = (
                    hashlib.sha256(f.read()).hexdigest())
    return out


def test_the_generator_is_deterministic_in_the_seed(tmp_path):
    trees = []
    for d, seed in (("a", 2**31 + 5), ("b", 2**31 + 5), ("c", 2**31 + 6)):
        deltastream.generate(str(tmp_path / d), PARAMS, seed)
        trees.append(tree(str(tmp_path / d)))
    assert trees[0] == trees[1] and trees[0] != trees[2]


def test_the_log_is_deltalogs_but_for_the_stats(tmp_path):
    """Every file of a commit spans the batch's interval, in commits,
    checkpoint and staged commits alike; all else is `deltalog`'s."""
    import pyarrow.parquet as pq

    ours = deltastream.generate(str(tmp_path / "s"), PARAMS, seed=9)
    theirs = deltalog.generate(str(tmp_path / "d"), PARAMS, seed=9)
    assert ours.digest() == theirs.digest()
    assert deltalog.stats_of(5).endswith('"nullCount":{"x":0}}')  # untouched

    def lines(root, where, name):
        with open(os.path.join(root, where, name)) as f:
            return [json.loads(line) for line in f]

    seen = 0
    for where, v in (("table/_delta_log", 50), ("table/_delta_log", 63),
                     ("staged", 70)):
        name = deltalog.commit_name(v)
        mine = lines(str(tmp_path / "s"), where, name)
        for got, want in zip(mine, lines(str(tmp_path / "d"), where, name)):
            if "add" in got:
                stats = json.loads(got["add"].pop("stats"))
                want["add"].pop("stats")
                assert stats == {
                    "numRecords": 1000, "minValues": {"x": (v + 1) * W},
                    "maxValues": {"x": (v + 2) * W}, "nullCount": {"x": 0}}
                seen += 1
            assert got == want
    assert seen == 3 * 80
    rows = pq.read_table(os.path.join(
        ours.table_path, "_delta_log", f"{60:020d}.checkpoint.parquet"))
    for add in rows.column("add").to_pylist()[2:]:
        v = int(add["path"][5:15]) // 80
        assert json.loads(add["stats"])["minValues"]["x"] == (v + 1) * W


# ---- the driver's reading of YCSB Workload E ----

def block_of(seed):
    schedule = traffic.schedule(MIX, seed)
    return [next(schedule) for _ in range(MIX["block"])]


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 2**31 + 18])
def test_a_block_is_workload_e(seed):
    block = block_of(seed)
    assert len(block) == 100
    assert [i for i, p in enumerate(block) if p["refresh"]] == [
        19, 39, 59, 79, 99]                 # 5 inserts in 100, one commit each
    assert {p["refresh"] for p in block} == {0, 1}
    lengths = sorted(DRIVER.scan_length(p["length"]) for p in block)
    assert lengths == list(range(1, 101))   # uniform in 1..100


def test_every_seed_starts_at_the_same_commits_in_another_order():
    chooser = DRIVER.ScrambledZipfian(42_000)
    starts = [[chooser.item(p["start"], 39_999) for p in block_of(seed)]
              for seed in (1, 2**31 + 17)]
    assert starts[0] != starts[1]
    assert sorted(starts[0]) == sorted(starts[1])
    top = collections.Counter(starts[0]).most_common(2)
    # Zipfian 0.99 over 42,000: the first rank holds 8.5% and the second
    # 4.3%, which is 8 and 5 of the block's 100 evenly spaced quantiles
    assert [n for _, n in top] == [8, 5]
    assert abs(top[0][0] - top[1][0]) > 1000        # scattered, no neighbours
    assert all(0 <= c <= 39_999 for c in starts[0])


def test_the_popular_commits_stay_as_the_table_grows():
    chooser = DRIVER.ScrambledZipfian(42_000)
    us = [(i + 0.5) / 100 for i in range(100)]
    before = [chooser.item(u, 39_999) for u in us]
    after = [chooser.item(u, 40_500) for u in us]
    assert sum(a == b for a, b in zip(before, after)) >= 95
    assert max(after) <= 40_500


def test_fnv_is_ycsbs():
    # FNV-1a, 64 bits, over the eight octets of the number, lowest first
    h = 0xCBF29CE484222325
    for octet in (1, 0, 0, 0, 0, 0, 0, 0):
        h = ((h ^ octet) * 1099511628211) % 2**64
    assert DRIVER.fnv1a_64(1) == h
    assert DRIVER.fnv1a_64(1) != DRIVER.fnv1a_64(256)


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == "deltalog-4m-stream"
    assert cell.entry["chips"] == 1 and cell.mix["driver"] == (
        "scan_under_ingest")
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert mine == {"scan_plan_ms", "refresh_ms", "index_rebuild_ms",
                    "state_advance_ms", "skip_roofline"}
    for name in mine:
        assert cell.module("layers", name).read
    assert {m["name"] for m in cell.metrics_of("end_to_end")} == {
        "op_p50_ms", "ops_per_s", "setup_s"}


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-stream.json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text
    assert list(config["reduced"]) == ["commits"]
    assert len(config["guarantees"]) == 3
    assert {"record_is_a_micro_batch", "events_in_order", "indexed_columns",
            "route", "checkpoints_in_window", "storage", "layout"} <= set(
                config["assumed"])
    same = dict(config["generator"], kind="deltalog")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-ckpt10.json")) as f:
        assert same == json.load(f)["generator"]    # cut as that one is
    assert MIX["fixture"] == {"staged_commits": 2000}


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5):
    return harness.run_cell("tiny-query-under-ingest", seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system)


def test_a_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    result = run()
    assert result["correct"] and result["failed"] == 0
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    out = capsys.readouterr().out
    for compared in ("planned_files", "planned_paths_sha256", "version"):
        assert f"window {compared}: compared" in out
    assert "mismatches 0 (limit 0)" in out and " refresh (median" in out


def test_a_traced_run_reads_the_cells_metrics():
    result = run(trace=True, seconds=1.0)
    assert result["correct"]
    # no plan reaches a chip here, so the kernel's share has nothing to read
    assert set(result["metrics"]) == {
        "scan_plan_ms", "refresh_ms", "index_rebuild_ms", "state_advance_ms",
        "device_route_pct", "h2d_mb_per_op", "device_idle_pct"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["refresh_ms"] > m["index_rebuild_ms"] > 0
    assert m["refresh_ms"] > m["state_advance_ms"] > 0
    assert m["refresh_ms"] > m["scan_plan_ms"] > 0


class KeepsMore(DeltaTpu):
    """A plan one micro-batch too wide: no file is lost, some are extra."""

    def plan(self, snapshot, lo, hi):
        return super().plan(snapshot, lo, hi + W)


class DropsTheEdge(DeltaTpu):
    """Compares the file's maximum with `>` where the range needs `>=`:
    the batch that ends where the range begins is lost."""

    def plan(self, snapshot, lo, hi):
        from delta_tpu.expressions import col, lit

        pred = (col("x") > lit(lo)) & (col("x") < lit(hi))
        return snapshot.scan(filter=pred).file_paths()


class NeverRefreshes(DeltaTpu):
    def refresh(self, table):
        return table._cached_snapshot


@pytest.mark.parametrize("system", [control.StaleReader, KeepsMore,
                                    DropsTheEdge, NeverRefreshes])
def test_a_broken_guarantee_is_not_correct(system, capsys):
    result = run(system=system())
    assert result["correct"] is False
    assert "first mismatch: got" in capsys.readouterr().out


# ---- the program's spans, once per operation ----

@pytest.fixture
def traced_ops(tmp_path, monkeypatch):
    """One plan, then one refresh with its plan, on the kernel's route;
    the spans and dispatch records of each."""
    from delta_tpu import Table, obs

    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    m = deltastream.generate(str(tmp_path), PARAMS, seed=5)
    system = DeltaTpu()
    table, snapshot = system.load(m.table_path)
    system.plan(snapshot, 11 * W, 14 * W)        # the index's first build
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    out = {}
    try:
        for kind in ("plan", "refresh"):
            obs.reset_trace_buffer()
            obs.reset_device_obs()
            if kind == "refresh":
                m.land(1)
                snapshot = system.refresh(table)
            paths = system.plan(snapshot, 11 * W, 14 * W)
            assert len(paths) == len(m.scan_expected(11 * W, 14 * W))
            out[kind] = ([s.to_dict() for s in obs.get_finished_spans()],
                         obs.get_dispatch_records())
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
        obs.reset_trace_buffer()
        obs.reset_device_obs()
    return out


SPAN_TREE = [          # kind of operation, span, the span it sits under
    ("plan", "plan.skip", "scan.plan"),
    ("plan", "skip.wait", "plan.skip"),
    ("plan", "plan.filter", "scan.plan"),
    ("refresh", "log.list_incremental", "snapshot.update"),
    ("refresh", "update.advance", "snapshot.update"),
    ("refresh", "plan.skip", "scan.plan"),
    ("refresh", "stats.index_build", "plan.skip"),
    ("refresh", "stats.index_upload", "plan.skip"),
    ("refresh", "skip.wait", "plan.skip"),
    ("refresh", "plan.filter", "scan.plan"),
]


@pytest.mark.parametrize("kind,name,parent", SPAN_TREE)
def test_a_span_appears_once_an_operation_under_its_parent(
        traced_ops, kind, name, parent):
    spans, _ = traced_ops[kind]
    by_id = {s["span_id"]: s for s in spans}
    mine = [s for s in spans if s["name"] == name]
    assert len(mine) == 1
    assert by_id[mine[0]["parent_id"]]["name"] == parent


def test_the_spans_say_what_happened(traced_ops):
    def attrs(kind, name):
        [s] = [s for s in traced_ops[kind][0] if s["name"] == name]
        return s["attrs"]

    advance = attrs("refresh", "update.advance")
    assert advance["route"] == "host" and advance["delta_rows"] == 100
    assert advance["prev_rows"] > 3000
    assert advance["stats_index"] == "released"   # a landed commit drops it
    assert not [s for s in traced_ops["plan"][0]
                if s["name"].startswith(("stats.index", "update."))]
    for kind in ("plan", "refresh"):
        skip = attrs(kind, "plan.skip")
        assert skip["skip_route"] == "device" and skip["skip_atoms"] == 2
        assert skip["skip_fallback_conjuncts"] == 0 and skip["conjuncts"] == 2
        assert attrs(kind, "plan.filter")["surviving"] == attrs(
            kind, "scan.plan")["surviving"] > 0
        [launch] = [r for r in traced_ops[kind][1]
                    if r["kernel"] == "skipping.mask_block"]
        assert launch["wait_ns"] > 0 and launch["d2h_bytes"] == 4096
        assert launch["attrs"] == {"lanes": 4, "n_pad": 4096}


# ---- the readers, on a recorded run ----

def reader(name):
    return module("layers", name).read


def span(name, start_ms, dur_ms):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "thread_id": threading.get_ident()}


def op(kind, start_ms, end_ms):
    return {"kind": kind, "start_unix_ns": start_ms * MS,
            "end_unix_ns": end_ms * MS}


# plans at 0, 100 and 200 ms; refreshes at 1,000 and 2,000 ms
OPS = [op("plan", 0, 50), op("plan", 100, 130), op("plan", 200, 290),
       op("refresh", 1000, 1900), op("refresh", 2000, 2700)]
RECORDED = [
    span("scan.plan", 1, 40), span("scan.plan", 101, 20),
    span("scan.plan", 201, 80),
    span("snapshot.update", 1000, 300), span("update.advance", 1100, 180),
    span("scan.plan", 1300, 590), span("stats.index_build", 1310, 400),
    span("stats.index_upload", 1720, 100),
    span("snapshot.update", 2000, 200), span("update.advance", 2050, 140),
    span("scan.plan", 2200, 490), span("stats.index_build", 2210, 300),
    span("stats.index_upload", 2520, 100),
    span("scan.plan", 5000, 7),     # outside every operation
]
PARENT = [s for s in RECORDED if s["name"] != "update.advance"]


def recorded(spans=RECORDED, dispatches=(), events=()):
    trace = types.SimpleNamespace(events=[list(events)] if events else [])
    return types.SimpleNamespace(ops=OPS, spans=spans, trace=trace,
                                 dispatches=list(dispatches),
                                 device_kind="TPU v5 lite")


@pytest.mark.parametrize("name,want", [
    ("scan_plan_ms", 40),                       # of 40, 20, 80: no refresh's
    ("refresh_ms", (890 + 690) / 2),            # update + the plan after it
    ("index_rebuild_ms", (500 + 400) / 2),      # build + upload
    ("state_advance_ms", (180 + 140) / 2),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


@pytest.mark.parametrize("name,spans,want", [
    ("state_advance_ms", PARENT, None),         # the parent has no such span
    ("scan_plan_ms", PARENT, 40),
    ("refresh_ms", PARENT, (890 + 690) / 2),
    ("index_rebuild_ms", PARENT, (500 + 400) / 2),
    ("scan_plan_ms", [], None), ("refresh_ms", [], None),
    ("index_rebuild_ms", [], None), ("state_advance_ms", [], None),
])
def test_a_reader_on_a_program_without_its_spans(name, spans, want):
    got = reader(name)(recorded(spans))
    assert got is None if want is None else got == pytest.approx(want)


def launch(**attrs):
    record = {"kernel": "skipping.mask_block", "key": "(16, 17)"}
    return dict(record, attrs=attrs) if attrs else record


def test_skip_roofline_is_least_time_over_device_time():
    n_pad = 2_621_440
    nbytes = module("layers", "skip_mask_bytes").skip_mask_bytes(4, n_pad)
    assert nbytes == 4 * n_pad * 9 + n_pad      # 4 lanes and flags in, 1 out
    events = [("jit_skipping_mask_block/while.1", 0, 600_000),
              ("jit_skipping_mask_block/fusion.3", 100_000, 500_000),  # its body
              ("jit_skipping_mask_block/copy.2", 700_000, 1_100_000),
              ("jit_stats_index_upload/fusion", 0, 9_000_000)]
    run_ = recorded(dispatches=[launch(lanes=4, n_pad=n_pad)] * 2
                    + [{"kernel": "stats.index_upload"}], events=events)
    least = 2 * nbytes / 819e9
    assert reader("skip_roofline")(run_) == pytest.approx(
        100 * least / 1e-3)
    assert reader("skip_roofline")(run_) < 100


@pytest.mark.parametrize("dispatches,events", [
    ([], [("jit_skipping_mask_block/fusion", 0, 5)]),   # no plan on the chip
    ([launch()], [("jit_skipping_mask_block/fusion", 0, 5)]),   # the parent
    ([launch(lanes=4, n_pad=4096)], []),                # no device plane
], ids=["host-route", "no-shape-on-the-record", "no-device-events"])
def test_skip_roofline_finds_nothing_to_read(dispatches, events):
    assert reader("skip_roofline")(recorded(dispatches=dispatches,
                                            events=events)) is None
