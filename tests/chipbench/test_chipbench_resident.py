"""The deployment `deltalog-10m-stream` and its cell
`ckpt-query-under-ingest-10m-v5e4`, at a test's size on the CPU, on four
of conftest's eight virtual devices: after every landed commit the
snapshot `update()` hands out against the generator's manifest, both
plain references (`oracle`, and `resident_oracle` advanced commit by
commit at 1, 2 and 4 dicts) and a cold load on `HostEngine`; the route
each refresh took and what it shipped; the shares adding up; the three
ways residency ends, each keeping the answer; whole runs of the cell;
the ten readers on a run written by hand; the append's spans and
`attrs`; two systems broken on purpose.

A test's log is under the gate's 4M rows, so the engine is given its
shard count (`replay_shards=4`): intent the gate keeps (`forced`). On
the chip the cell passes nothing and the gate decides.

`python3 tests/chipbench/test_chipbench_resident.py <broken system>
--seed <n> --seconds <s>` runs the cell itself, at its real size and on
four chips, on one of them: the last line is the harness's result."""

import gc
import hashlib
import importlib.util
import json
import os
import threading
import time
import types
from unittest import mock

import pytest

from chipbench import harness, trace_reduce
from chipbench.gen import deltalog, deltastream
from chipbench.reference import oracle, plan_oracle, resident_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "resident", "benchmark.json")
CELL = "ckpt-query-under-ingest-10m-v5e4"
TINY_CELL = "tiny-resident-query-under-ingest"
CONFIG = "deltalog-10m-stream"
MIX = "ycsb-e-scans-whole-state"
SHARDS = 4
SEEDS = [7, 2**31 + 17, 2**31 + 18]
# a checkpoint at version 60, nine commits after it, 14 staged: 1,155
# rows a shard in a bucket of 2,048, room for every landing of a test
PARAMS = dict(commits=70, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=40, staged_commits=14)
# over the 65,536 rows from which a phase of a refresh is a span
PHASES = dict(PARAMS, commits=1200, retained_commits=100, staged_commits=3)
W = deltastream.batch_width(80)
MS = 1_000_000
# one landed commit on four shards: 100 rows in 128 slots a shard, a slot
# index and a key each; the record counts the four fill levels too
OPERAND_BYTES = SHARDS * 128 * 8
RECORD_BYTES = OPERAND_BYTES + SHARDS * 4
RESIDENT_METRICS = {
    "resident_plan_ms", "resident_refresh_ms", "resident_route_pct",
    "resident_append_ms", "resident_append_host_ms",
    "resident_append_wait_ms", "resident_h2d_kb_per_op",
    "resident_index_rebuild_ms", "resident_append_roofline",
    "resident_idle_pct"}
SOURCES = {"resident_h2d_kb_per_op": "program_counter",
           "resident_append_roofline": "device_trace",
           "resident_idle_pct": "device_trace"}
PHASE_SPANS = ("resident.index_build", "resident.code_paths",
               "resident.place", "resident.wait", "resident.masks")


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"resident_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return module("layers", name).read


def engine(shards=SHARDS):
    from delta_tpu.engine.tpu import TpuEngine

    return TpuEngine(replay_shards=shards)


class OnTheMesh(DeltaTpu):
    """The system as the cell drives it, on four of the virtual devices."""

    def load(self, path):
        from delta_tpu import Table

        table = Table.for_path(path, engine=engine())
        return table, table.latest_snapshot()


def digest(paths) -> str:
    return hashlib.sha256("\n".join(sorted(paths)).encode()).hexdigest()


def of_manifest(m) -> tuple:
    return m.num_files(), m.size_in_bytes(), m.digest()


def of_summary(summary: dict) -> tuple:
    return (summary["num_live"], summary["live_bytes"],
            digest(key.split("|")[0] for key in summary["live_keys"]))


def of_snapshot(snapshot) -> tuple:
    return (snapshot.num_files, snapshot.size_in_bytes,
            digest(snapshot.state.add_files_table.column("path")
                   .to_pylist()))


def of_host_engine(path) -> tuple:
    from delta_tpu import Table
    from delta_tpu.engine.host import HostEngine

    return of_snapshot(Table.for_path(path, engine=HostEngine())
                       .latest_snapshot())


class Traced:
    """The program's spans and dispatch records of one call."""

    def __init__(self, call):
        from delta_tpu import obs

        obs.set_trace_mode("on")
        obs.set_device_obs_mode("on")
        obs.reset_trace_buffer()
        obs.reset_device_obs()
        try:
            self.value = call()
            self.spans = [s.to_dict() for s in obs.get_finished_spans()]
            self.records = obs.get_dispatch_records()
        finally:
            obs.set_trace_mode(None)
            obs.set_device_obs_mode(None)
            obs.reset_trace_buffer()
            obs.reset_device_obs()
        self.by_id = {s["span_id"]: s for s in self.spans}

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def one(self, name):
        [span] = self.named(name)
        return span

    def parent(self, span):
        return self.by_id[span["parent_id"]]["name"]


def refresh(table):
    """`update()` and the state behind it (the advance is lazy), traced."""
    def call():
        snapshot = table.update()
        snapshot.state
        return snapshot
    return Traced(call)


class Counters:
    NAMES = ("replay.resident_appends", "replay.resident_fallbacks",
             "replay.resident_released", "replay.h2d_bytes")

    def __init__(self):
        from delta_tpu import obs

        self.counters = [obs.counter(n) for n in self.NAMES]
        self.mark()

    def mark(self):
        self.before = [c.value for c in self.counters]

    def since(self) -> tuple:
        """(appends, fallbacks, released, H2D bytes) since the mark."""
        return tuple(c.value - b
                     for c, b in zip(self.counters, self.before))


def held_table(m):
    """The table loaded on the mesh, its snapshot resident."""
    from delta_tpu import Table

    table = Table.for_path(m.table_path, engine=engine())
    snapshot = table.latest_snapshot()
    assert snapshot.state.resident is not None
    return table, snapshot


# commit v holds x in [(v + 1) W, (v + 2) W]
RANGES = [(61 * W, 64 * W), (W, 40 * W), (70 * W + 1, 10**12),
          (30 * W + 5, 30 * W + 6), (0, W), (50 * W, 85 * W)]


# ---- update() = both references = the manifest = a cold load ----

@pytest.mark.parametrize("seed", SEEDS)
def test_every_refresh_equals_every_reference(tmp_path, seed):
    m = deltastream.generate(str(tmp_path), PARAMS, seed)
    table, snapshot = held_table(m)
    kept = {s: resident_oracle.load(m.table_path, s)
            for s in (1, 2, SHARDS)}
    assert of_snapshot(snapshot) == of_manifest(m)
    counted = Counters()
    for landing in range(12):
        m.land(1)
        counted.mark()
        got = refresh(table)
        snapshot, want = got.value, of_manifest(m)
        assert snapshot.version == m.version == 70 + landing
        assert of_snapshot(snapshot) == want
        assert of_summary(
            oracle.read_table_state(m.table_path).summary()) == want
        for state in kept.values():     # this commit's actions alone
            assert state.advance(m.table_path) == 1
            assert state.summary() == want
        assert of_host_engine(m.table_path) == want
        # the route, and what crossed the link
        advance = got.one("update.advance")["attrs"]
        assert advance["route"] == "resident"
        assert advance["delta_rows"] == 100
        appends, fallbacks, _, shipped = counted.since()
        assert (appends, fallbacks, shipped) == (1, 0, OPERAND_BYTES)
        [record] = [r for r in got.records
                    if r["kernel"] == "replay.resident_append"]
        assert record["h2d_bytes"] == RECORD_BYTES
        assert snapshot.state.resident is not None
        # the plans on it
        lo, hi = RANGES[landing % len(RANGES)]
        planned = sorted(DeltaTpu().plan(snapshot, lo, hi))
        assert planned == plan_oracle.plan(m.table_path, lo, hi)
        assert planned == kept[SHARDS].plan(lo, hi)
        assert planned == [deltalog.path_of(int(i))
                           for i in m.scan_expected(lo, hi)]
    # the reference read the checkpoint once and each commit once
    assert kept[SHARDS].commits_read == list(range(61, 82))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_shares_add_up(tmp_path, seed):
    m = deltastream.generate(str(tmp_path), PARAMS, seed)
    table, _ = held_table(m)
    kept = resident_oracle.load(m.table_path, SHARDS)
    for _ in range(5):
        m.land(1)
        kept.advance(m.table_path)
    snapshot = table.update()
    live = [set(kept.live_of(s)) for s in range(SHARDS)]
    assert all(live)        # no dict is idle
    for i in range(SHARDS):
        for j in range(i + 1, SHARDS):
            assert not live[i] & live[j]
    plain = oracle.read_table_state(m.table_path)
    assert set().union(*live) == set(plain.live)
    for s, mine in enumerate(live):     # the path alone decides the dict
        assert all(kept.shard_of(path) == s for path, _ in mine)
    # the program's own lanes: every row held has one slot, and every
    # row of one path lies in one shard, the landed rows included
    resident = snapshot.state.resident
    held = snapshot.state.file_actions_raw
    paths = held.column("path").to_pylist()
    rows = resident.scatter[resident.scatter >= 0]
    assert sorted(rows.tolist()) == list(range(held.num_rows))
    home = {}
    for s in range(SHARDS):
        for row in resident.scatter[s][resident.scatter[s] >= 0]:
            assert home.setdefault(paths[row], s) == s
    assert set(home.values()) == set(range(SHARDS))


def test_a_commit_that_is_missing_is_not_skipped(tmp_path):
    m = deltastream.generate(str(tmp_path), PARAMS, SEEDS[0])
    kept = resident_oracle.load(m.table_path, SHARDS)
    m.land(2)
    os.remove(os.path.join(m.table_path, "_delta_log",
                           deltalog.commit_name(70)))
    with pytest.raises(ValueError, match="70"):
        kept.advance(m.table_path)


def test_the_references_share_no_code_with_the_program():
    for name in ("resident_oracle", "shard_oracle", "plan_oracle",
                 "oracle"):
        with open(os.path.join(ROOT, "chipbench", "reference",
                               name + ".py")) as f:
            text = f.read()
        assert "import delta_tpu" not in text, name
        assert "from delta_tpu" not in text, name
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "resident_oracle.py")) as f:
        text = f.read()
    assert "numpy" not in text and "from chipbench" not in text
    assert "import chipbench" not in text


# ---- the three ways residency ends: each keeps the answer ----

DV = ('"deletionVector":{"storageType":"u","pathOrInlineDv":'
      '"ab^-aqEH.-t@S}K{vb[*k^","offset":4,"sizeInBytes":40,'
      '"cardinality":6}')


def lands_a_deletion_vector(m, table, snapshot):
    """Another writer's commit, by hand: a live file gone and back with
    a deletion vector. Nothing staged lands after it."""
    version = m.version + 1
    fid = int(m.live_ids()[5])
    add = deltalog.add_line(fid, version)
    lines = [deltalog.remove_line(fid, version), add[:-2] + "," + DV + "}}"]
    with open(os.path.join(m.table_path, "_delta_log",
                           deltalog.commit_name(version)), "w") as f:
        f.write("\n".join(lines) + "\n")
    return False


def the_tail_is_newer(m, table, snapshot):
    """The lanes hold a version past the one that lands next."""
    snapshot.state.resident._max_version = 10**9
    m.land(1)
    return True


def a_shard_is_full(m, table, snapshot):
    m.land(1)
    return True


@pytest.mark.parametrize("ends,commits,first", [
    (lands_a_deletion_vector, 70, 1),
    (the_tail_is_newer, 70, 1),
    # 1,995 rows a shard in a bucket of 2,048: the capacity the load
    # itself left, ~53 free slots a shard, ~25 rows a shard a commit
    (a_shard_is_full, 128, None),
], ids=["deletion-vector", "older-than-the-tail", "full-shard"])
def test_a_batch_the_lanes_cannot_take_ends_residency_and_keeps_the_answer(
        tmp_path, ends, commits, first):
    from delta_tpu import obs

    m = deltastream.generate(str(tmp_path), dict(PARAMS, commits=commits),
                             SEEDS[0])
    held = obs.gauge("replay.resident_hbm_bytes")
    gc.collect()
    base = held.read()      # what other tests of this process still hold
    table, snapshot = held_table(m)
    lanes = SHARDS * snapshot.state.resident.m * 4
    assert held.read() == base + lanes
    counted = Counters()
    routes, can_go_on = [], True
    while can_go_on and len(routes) < 6:
        resident = snapshot.state.resident
        free = (None if resident is None
                else int(resident.m - resident.n_real.max()))
        if len(routes) == first:        # after one resident refresh
            can_go_on = ends(m, table, snapshot)
        else:
            m.land(1)
        counted.mark()
        got = refresh(table)
        snapshot = got.value
        route = got.one("update.advance")["attrs"]["route"]
        routes.append(route)
        if route == "resident":
            assert counted.since()[:2] == (1, 0)
            assert snapshot.state.resident is resident
        elif resident is not None:      # the refresh that ended it
            assert counted.since() == (0, 1, 1, 0)
            assert first is not None or free < 100
        else:                           # and every one after it
            assert counted.since() == (0, 0, 0, 0)
        if route == "host":
            assert snapshot.state.resident is None
            assert held.read() == base      # the lanes, in the ledger
        assert of_snapshot(snapshot) == of_summary(
            oracle.read_table_state(m.table_path).summary())
        assert snapshot.version == len(routes) + commits - 1
    ended = routes.index("host")
    assert routes == ["resident"] * ended + ["host"] * (len(routes) - ended)
    assert ended >= 1 and (first is None or ended == first)
    if can_go_on:
        assert len(routes) > ended + 1      # the host route went on


# ---- the cell's files ----

def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == CONFIG and cell.entry["traffic"] == MIX
    assert cell.entry["chips"] == 4
    assert cell.mix["driver"] == "scan_under_ingest_whole_state"
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    # a lower bound, and no place in the file: a later PR may add to the
    # cell's metrics, and to the file before or behind its entries
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert RESIDENT_METRICS <= mine
    for name in RESIDENT_METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    bench = load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in RESIDENT_METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["source"] == SOURCES.get(name, "program_span")
        assert by_name[name]["moves"] == (
            "op_p50_ms" if name == "resident_plan_ms" else "ops_per_s")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_configuration_is_its_siblings_table_under_their_ingest():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    assert config["reduced"] == {}
    whole = load_json("chipbench", "configs", "deltalog-10m-ckpt10.json")
    stream = load_json("chipbench", "configs", "deltalog-4m-stream.json")
    assert config["generator"] == dict(whole["generator"],
                                       kind="deltastream")
    assert config["generator"] == dict(stream["generator"], commits=100_000)
    assert config["environment"] == whole["environment"] == stream[
        "environment"]
    assert config["guarantees"][:3] == stream["guarantees"]
    assert len(config["guarantees"]) == 4
    assert "whichever route advanced it" in config["guarantees"][3]
    assert set(config["assumed"]) == (
        set(whole["assumed"]) | set(stream["assumed"]))
    for word in ("log_cleanup", "checkpoint_writer", "checkpoint_parts",
                 "tombstones", "storage", "allocator"):
        assert config["assumed"][word] == whole["assumed"][word], word
    for word in ("layout", "events_in_order", "client", "schema",
                 "storage"):
        assert config["assumed"][word] == stream["assumed"][word], word
    # the table's own sizes where the siblings' differ
    assert "6,291,456 padded rows" in config["assumed"]["indexed_columns"]
    assert "6.0M live files" in config["assumed"]["record_is_a_micro_batch"]
    assert config["assumed"]["checkpoints_in_window"].startswith(
        "none lands during a run")
    for said in ("DEFAULT_SHARDED_MIN_ROWS 4M", "m = 1,572,864",
                 "route=resident", "never set by the benchmark"):
        assert said in config["assumed"]["route"], said
    assert "four chips" in config["deployment"]
    assert "jax.devices() = 4" in config["deployment"]


def test_the_mix_is_its_siblings_but_for_the_driver():
    mix = load_json("chipbench", "mixes", MIX + ".json")
    sibling = load_json("chipbench", "mixes", "ycsb-e-scans.json")
    assert mix["driver"] == "scan_under_ingest_whole_state"
    assert mix["about"] != sibling["about"]
    assert {k: v for k, v in mix.items() if k not in ("driver", "about")} \
        == {k: v for k, v in sibling.items() if k not in ("driver", "about")}
    assert mix["fixture"] == {"staged_commits": 2000}
    tiny = load_json("tests", "chipbench", "resident", "benchmark.json")
    real = {m["name"]: m for m in load_json("BENCHMARK.json")["per_layer"]}
    assert {m["name"] for m in tiny["per_layer"]} == RESIDENT_METRICS
    for m in tiny["per_layer"]:     # the real entries, on the tiny cell
        assert dict(m, workloads=[CELL]) == real[m["name"]]
    assert tiny["workloads"][0]["traffic"] == MIX
    assert tiny["workloads"][0]["chips"] == 4


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5):
    return harness.run_cell(TINY_CELL, seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system or OnTheMesh())


def test_a_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    counted = Counters()
    result = run()
    assert result["correct"] and result["failed"] == 0
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    appends, fallbacks, _, _ = counted.since()
    assert appends >= 2 and fallbacks == 0
    out = capsys.readouterr().out
    for compared in ("planned_files", "planned_paths_sha256", "version"):
        assert f"window {compared}: compared" in out
    # the whole state: on the warm-up's refresh and the closing operation,
    # and on no other (the warm-up's plans are checked in full too)
    for compared in ("num_files", "size_in_bytes", "live_paths_sha256"):
        assert f"warm-up {compared}: compared 1, mismatches 0" in out
        assert f"window {compared}: compared 1, mismatches 0" in out
    assert "mismatches 0 (limit 0)" in out and " refresh (median" in out


def test_a_traced_run_reads_the_cells_metrics(monkeypatch):
    # no device plane on the CPU, so the reader of the device's trace is
    # handed the planes the run's own launches would have left
    seen = {}
    reduce_planes = trace_reduce.reduce_planes

    def with_four_planes(planes):
        from delta_tpu import obs

        reduced = reduce_planes(planes)
        offset = reduced.window[0] - seen["window_unix_ns"]
        mine = [r for r in obs.get_dispatch_records()
                if r["kernel"] == "replay.resident_append"
                and r["ts_unix_ns"] >= seen["window_unix_ns"]]
        events = [[] for _ in range(SHARDS)]
        for r in mine:
            at = r["ts_unix_ns"] - r["wall_ns"] // 2 + offset
            for p in range(SHARDS):
                events[p] += [
                    ("jit_replay_resident_append/%scatter.2", at, at + 100),
                    ("jit_replay_resident_append/%sort.4", at + 100,
                     at + 1000 + 50 * p)]
        seen["launches"] = len(mine)
        return trace_reduce.Reduced(reduced.window, events)

    monkeypatch.setattr(trace_reduce, "reduce_planes", with_four_planes)
    real = harness.per_layer

    def per_layer(cell, window, w_start, w_end, trace_dir, device):
        seen["window_unix_ns"] = w_start
        device = dict(device, kind="TPU v5 lite")
        return real(cell, window, w_start, w_end, trace_dir, device)

    monkeypatch.setattr(harness, "per_layer", per_layer)
    result = run(trace=True, seconds=0.8)
    assert result["correct"] and seen["launches"] >= 2
    assert set(result["metrics"]) == RESIDENT_METRICS   # all ten, none null
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["resident_route_pct"] == 100
    assert m["resident_h2d_kb_per_op"] == RECORD_BYTES / 1e3
    assert m["resident_refresh_ms"] > m["resident_append_ms"] > 0
    assert m["resident_append_ms"] > m["resident_append_host_ms"] > 0
    assert m["resident_append_ms"] == pytest.approx(
        m["resident_append_host_ms"] + m["resident_append_wait_ms"], rel=0.5)
    assert m["resident_append_wait_ms"] > 0
    assert m["resident_refresh_ms"] > m["resident_index_rebuild_ms"] > 0
    assert m["resident_refresh_ms"] > m["resident_plan_ms"] > 0
    # 1,150 ns on the slowest plane against a shard's 267,780 bytes:
    # 72,380 rows are 18,095 a shard in a bucket of 32,768
    least = (RECORD_BYTES // 4 + 128 * 4 + 2 * 32768 * 4 + 32768 // 8) / 819e9
    assert m["resident_append_roofline"] == pytest.approx(
        100 * least / 1150e-9)
    assert 0 < m["resident_append_roofline"] < 100
    assert 99 < m["resident_idle_pct"] < 100
    # ten names at most, by idle time: the append is among them
    gaps = dict(result["breakdown"]["idle_gaps"])
    assert set(gaps) & {"replay.resident_append", "resident.masks",
                        "resident.wait"}


# ---- the ten readers on a run written by hand ----

def span(name, start_ms, dur_ms, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "thread_id": threading.get_ident(), "attrs": attrs}


def op(kind, start_ms, end_ms):
    return {"kind": kind, "start_unix_ns": start_ms * MS,
            "end_unix_ns": end_ms * MS}


def a_refresh(at, update_ms, advance_ms, append_ms, wait_ms, masks_ms,
              build_ms, route="resident"):
    """The spans of one refresh that begins `at` ms: `update()`, then
    the plan 300 ms in with the index brought forward under it."""
    plan = at + 300
    return [
        span("snapshot.update", at, update_ms),
        span("update.advance", at + 50, advance_ms, route=route),
        span("advance.resident_append", at + 55, append_ms),
        span("replay.resident_append", at + 56, append_ms - 2),
        span("resident.code_paths", at + 57, 4),
        span("resident.place", at + 62, 2),
        span("resident.wait", at + 70, wait_ms),
        span("resident.masks", at + 71 + wait_ms, masks_ms),
        span("scan.plan", plan, build_ms + 190),
        span("stats.index_build", plan + 10, build_ms),
        span("stats.index_upload", plan + 20 + build_ms, 100)]


# plans at 0, 100 and 200 ms; refreshes at 1,000 and 2,000 ms
OPS = [op("plan", 0, 50), op("plan", 100, 130), op("plan", 200, 290),
       op("refresh", 1000, 1900), op("refresh", 2000, 2700)]
PLANS = [span("scan.plan", 1, 40), span("scan.plan", 101, 20),
         span("scan.plan", 201, 80),
         span("scan.plan", 5000, 7)]            # outside every operation
FIRST = a_refresh(1000, 300, 180, 150, 30, 90, 400)
RECORDED = PLANS + FIRST + a_refresh(2000, 200, 140, 120, 20, 70, 300)
M = 1_572_864
ATTRS = {"shards": 4, "m": M, "d_pad": 128}


def launch(begin_ms, end_ms, **more):
    return dict({"kernel": "replay.resident_append",
                 "h2d_bytes": RECORD_BYTES, "ts_unix_ns": end_ms * MS,
                 "wall_ns": (end_ms - begin_ms) * MS,
                 "attrs": dict(ATTRS)}, **more)


DISPATCHES = [launch(1064, 1069), launch(2064, 2068),
              {"kernel": "skipping.mask_block", "h2d_bytes": 64,
               "ts_unix_ns": 150 * MS, "wall_ns": 2 * MS,
               "attrs": {"lanes": 4, "n_pad": 4 * M}}]
# a launch's scatter begins 2 ms after the host's; each chip sorts its
# own lane and ends when it ends
SORT_ENDS = {1066: [1084, 1086, 1083, 1085], 2066: [2082, 2081, 2084, 2083]}


def device_plane(p):
    ops, modules = [], []
    for begin, ends in SORT_ENDS.items():
        modules.append(("jit_replay_resident_append(5)", begin * MS,
                        (ends[p] + 3 - begin) * MS))
        ops += [("%scatter.2 = u32[] scatter()", begin * MS, 1 * MS),
                ("%sort.4 = sort()", (begin + 1) * MS,
                 (ends[p] - begin - 1) * MS),
                ("%fusion.6 = u32[] fusion()", ends[p] * MS, 3 * MS)]
    if p == 0:      # the index and the plans' kernel lie on chip 0 alone
        modules.append(("jit_skipping_mask_block(3)", 3000 * MS, 5 * MS))
        ops.append(("%fusion.9 = fusion()", 3000 * MS, 5 * MS))
    return (f"/device:TPU:{p}", [("XLA Modules", modules), ("XLA Ops", ops)])


PLANES = [("/host:CPU", [("python3", [("chipbench.window", 0, 8000 * MS)])])
          ] + [device_plane(p) for p in range(SHARDS)]
LEAST_S = (RECORD_BYTES // 4 + 128 * 4 + 2 * M * 4 + M // 8) / 819e9
# per plane: 21 + 19 (+ 5 on chip 0), 23 + 18, 20 + 21, 22 + 20 ms
BUSY_S = (45 + 41 + 41 + 42) / 4 / 1e3


def recorded(spans=RECORDED, dispatches=DISPATCHES, planes=PLANES, ops=OPS):
    return types.SimpleNamespace(
        ops=ops, spans=spans, gates=[], dispatches=list(dispatches),
        trace=trace_reduce.reduce_planes(planes), device_kind="TPU v5 lite",
        to_trace_ns=lambda unix_ns: unix_ns)


@pytest.mark.parametrize("name,want", [
    ("resident_plan_ms", 40),                       # of 40, 20, 80
    ("resident_refresh_ms", (890 + 690) / 2),       # update + the plan
    ("resident_route_pct", 100),
    ("resident_append_ms", (150 + 120) / 2),
    ("resident_append_host_ms", (150 - 30 + 120 - 20) / 2),
    ("resident_append_wait_ms", (30 + 20) / 2),
    ("resident_h2d_kb_per_op", 4.112),              # 8 bytes a padded row
    ("resident_index_rebuild_ms", (500 + 400) / 2),  # build + upload
    # the slowest plane: 23 and 21 ms
    ("resident_append_roofline", 100 * 2 * LEAST_S / 44e-3),
    ("resident_idle_pct", 100 * (1 - BUSY_S / 8)),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_the_least_bytes_of_a_shard_follow_the_launchs_record():
    count = module("layers", "resident_append_bytes").resident_append_bytes
    one = launch(0, 1)
    # a shard's operands; 128 keys scattered in place; the lane read and
    # written by the sort; the winner words
    assert count(one) == 1028 + 512 + 2 * M * 4 + M // 8 == 12_781_060
    wider = launch(0, 1, attrs=dict(ATTRS, d_pad=256),
                   h2d_bytes=SHARDS * 256 * 8 + 16)
    assert count(wider) - count(one) == 128 * 8 + 128 * 4
    # a bound under 100% of any time a sort of m keys can take
    assert 0 < reader("resident_append_roofline")(recorded()) < 1


def without(spans, *names):
    return [s for s in spans if s["name"] not in names]


# the parent's records: shapes in their `key` alone
PARENTS = [{k: v for k, v in d.items() if k != "attrs"} for d in DISPATCHES]
# the second refresh fell to the host route: no append, a probe
FELL = (PLANS + FIRST
        + [s for s in a_refresh(2000, 200, 140, 120, 20, 70, 300,
                                route="host")
           if not s["name"].startswith(("advance.resident", "resident.",
                                        "replay.resident"))]
        + [span("advance.probe", 2060, 110)])


@pytest.mark.parametrize("name,changes,want", [
    # the parent: no phase under the append, no attrs on the record
    ("resident_append_host_ms",
     dict(spans=without(RECORDED, *PHASE_SPANS)), None),
    ("resident_append_wait_ms",
     dict(spans=without(RECORDED, *PHASE_SPANS)), None),
    ("resident_append_ms", dict(spans=without(RECORDED, *PHASE_SPANS)), 135),
    ("resident_refresh_ms", dict(spans=without(RECORDED, *PHASE_SPANS)),
     790),
    ("resident_route_pct", dict(spans=without(RECORDED, *PHASE_SPANS)), 100),
    ("resident_append_roofline", dict(dispatches=PARENTS), None),
    ("resident_h2d_kb_per_op", dict(dispatches=PARENTS), 4.112),
    # a refresh that left the lanes, and the window after it
    ("resident_route_pct", dict(spans=FELL), 50),
    ("resident_append_ms", dict(spans=FELL), 150),
    ("resident_append_host_ms", dict(spans=FELL), 120),
    ("resident_append_wait_ms", dict(spans=FELL), 30),
    ("resident_h2d_kb_per_op", dict(dispatches=DISPATCHES[:1]), 4.112 / 2),
    # a program whose span does not name its route; nothing advanced
    ("resident_route_pct", dict(spans=[
        dict(s, attrs={}) if s["name"] == "update.advance" else s
        for s in RECORDED]), None),
    ("resident_route_pct", dict(spans=without(RECORDED, "update.advance")),
     None),
    # the host route from the load on (`DELTA_TPU_RESIDENT=0`)
    ("resident_append_ms",
     dict(spans=without(RECORDED, "advance.resident_append")), None),
    ("resident_h2d_kb_per_op", dict(dispatches=DISPATCHES[2:]), None),
    ("resident_append_roofline", dict(dispatches=DISPATCHES[2:]), None),
    # no device plane: a run on the CPU
    ("resident_append_roofline", dict(planes=PLANES[:1]), None),
    ("resident_idle_pct", dict(planes=PLANES[:1]), 100),
    # no refresh in the window
    ("resident_h2d_kb_per_op", dict(ops=OPS[:3]), None),
    ("resident_refresh_ms", dict(spans=[]), None),
    ("resident_plan_ms", dict(spans=[]), None),
    ("resident_index_rebuild_ms", dict(spans=[]), None),
    ("resident_append_host_ms", dict(spans=[]), None),
])
def test_a_reader_on_a_program_without_what_it_reads(name, changes, want):
    got = reader(name)(recorded(**changes))
    assert got is None if want is None else got == pytest.approx(want)


# ---- the append seen from inside ----

def test_the_append_names_its_phases_shapes_and_counts(tmp_path):
    m = deltastream.generate(str(tmp_path), PHASES, 11)
    table, snapshot = held_table(m)
    rows = snapshot.state.file_actions_raw.num_rows
    assert rows == m.load_actions - 2 == 72_380
    for landing in range(2):
        m.land(1)
        got = refresh(table)
        assert got.value.version == m.version
        whole = got.one("replay.resident_append")
        assert got.parent(whole) == "advance.resident_append"
        assert whole["attrs"]["rows"] == 100
        if landing == 0:    # the path dictionary, once a load
            build = got.one("resident.index_build")
            assert got.parent(build) == "replay.resident_append"
            assert build["attrs"] == {"rows": rows}
        else:
            assert not got.named("resident.index_build")
        want = {"resident.code_paths": {"rows": 100, "new_paths": 80},
                "resident.place": {"d_pad": 128},
                "resident.wait": {"rows": rows + 100 * (landing + 1),
                                  "bytes": SHARDS * 32768 // 8},
                "resident.masks": {"slots": SHARDS * 32768}}
        for name, attrs in want.items():
            phase = got.one(name)       # once a refresh
            assert got.parent(phase) == "replay.resident_append", name
            assert phase["attrs"] == attrs, name
        inside = sum(got.one(n)["duration_ns"] for n in want)
        assert inside <= whole["duration_ns"]
        [record] = [r for r in got.records
                    if r["kernel"] == "replay.resident_append"]
        assert record["attrs"] == {"shards": SHARDS, "m": 32768,
                                   "d_pad": 128}
        assert record["h2d_bytes"] == RECORD_BYTES
        assert record["violations"] == []
        assert of_snapshot(got.value) == of_manifest(m)


def test_a_phase_of_a_small_append_is_a_span_under_verbose_alone(tmp_path):
    from delta_tpu import obs

    m = deltastream.generate(str(tmp_path), PARAMS, 3)
    table, snapshot = held_table(m)
    assert snapshot.state.file_actions_raw.num_rows < obs.PHASE_SPAN_ROWS
    m.land(1)
    names = {s["name"] for s in refresh(table).spans}
    assert {"update.advance", "replay.resident_append",
            "resident.wait"} <= names
    assert not names & (set(PHASE_SPANS) - {"resident.wait"})


def test_the_new_spans_are_in_the_docs():
    for doc in ("observability.md", "incremental_update.md"):
        with open(os.path.join(ROOT, "docs", doc)) as f:
            text = f.read()
        for name in PHASE_SPANS:
            assert name in text, (doc, name)
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        assert "d_pad" in f.read()


# ---- two systems broken on purpose, through the cell's own comparison ----

class Broken(DeltaTpu):
    """`on`: the system that loads; on the chip, at the real size, the
    default one."""

    on = OnTheMesh

    def load(self, path):
        return self.on.load(self, path)


class AddsOnly:
    """A landed batch with its removes left out."""

    def __init__(self, delta):
        self._delta = delta

    def __getattr__(self, name):
        return getattr(self._delta, name)

    def file_actions_complete(self):
        rows = self._delta.file_actions_complete()
        return rows.filter(rows.column("is_add"))


class DropsTheRemoves(Broken):
    """A refresh that lands a commit's adds and not its removes: 20
    files a commit stay live that are gone, anywhere in the table."""

    def refresh(self, table):
        from delta_tpu.replay import state

        advance = state._advance_state

        def adds_only(engine, prev, delta, new_segment, sp):
            return advance(engine, prev, AddsOnly(delta), new_segment, sp)

        with mock.patch.object(state, "_advance_state", adds_only):
            fresh = super().refresh(table)
            fresh.state     # the advance, now
        return fresh


class PlansOnTheSnapshotBefore(Broken):
    """Refreshes, and answers from the snapshot it held before."""

    def load(self, path):
        table, self._held = super().load(path)
        return table, self._held

    def refresh(self, table):
        fresh = super().refresh(table)
        fresh.state
        stale, self._held = self._held, fresh
        return stale


BROKEN = {"DropsTheRemoves": ("num_files", DropsTheRemoves),
          "PlansOnTheSnapshotBefore": ("version", PlansOnTheSnapshotBefore)}


@pytest.mark.parametrize("name", sorted(BROKEN))
def test_a_broken_system_is_not_correct(name, capsys):
    caught_by, system = BROKEN[name]
    result = run(system=system(), seconds=0.3)
    assert result["correct"] is False and result["failed"] >= 1
    out = capsys.readouterr().out
    assert "first mismatch: got" in out
    [line] = [ln for ln in out.splitlines()
              if ln.startswith(f"window {caught_by}: compared")]
    assert "mismatches 0 " not in line


def test_the_sound_system_passes_where_the_broken_ones_fail():
    assert run(seconds=0.3)["correct"]


def test_a_remove_that_no_plan_meets_is_seen_in_the_whole_state_alone(
        tmp_path):
    """What the sibling's check cannot see: the refresh keeps 20 files
    that are gone, the plan asks for a range none of them lies in, and
    every comparison of `scan_under_ingest` passes."""
    m = deltastream.generate(str(tmp_path), PARAMS, SEEDS[1])
    gone = {int(i) // 80 for i in m.staged[0].removed}
    start = next(c for c in range(1, 60)
                 if not gone & set(range(c - 1, c + 4)))
    answers = {}
    for kind in ("scan_under_ingest", "scan_under_ingest_whole_state"):
        fresh = deltastream.generate(str(tmp_path / kind), PARAMS, SEEDS[1])
        driver = module("drivers", kind).Driver(DropsTheRemoves(), fresh)
        driver.table, driver.snapshot = driver.system.load(fresh.table_path)
        driver.snapshot.state   # held, as after the warm-up's plans
        fresh.land(1)
        prep = (1, (start + 1) * W, (start + 3) * W)
        answer = driver.timed(prep)
        answers[kind] = driver.check(prep, answer, True)
    kind, compared = answers["scan_under_ingest"]
    assert kind == "refresh" and len(compared) == 3
    assert all(got == want for _, got, want in compared)
    kind, compared = answers["scan_under_ingest_whole_state"]
    assert kind == "refresh" and compared[:3] == answers[
        "scan_under_ingest"][1]
    wrong = {name: got - want for name, got, want in compared[3:5]}
    assert wrong == {"num_files": 20, "size_in_bytes": 20 * deltalog.FILE_SIZE}
    name, got, want = compared[5]
    assert name == "live_paths_sha256" and got != want


def test_the_whole_state_is_read_where_the_harness_asks_and_nowhere_else(
        tmp_path):
    m = deltastream.generate(str(tmp_path), PARAMS, SEEDS[2])
    driver = module("drivers", "scan_under_ingest_whole_state").Driver(
        OnTheMesh(), m)
    driver.table, driver.snapshot = driver.system.load(m.table_path)
    plan, landing = (0, 11 * W, 14 * W), (1, 11 * W, 14 * W)

    def compared(prep, full):
        if prep[0]:
            m.land(1)
        return [name for name, got, want in
                driver.check(prep, driver.timed(prep), full)[1]
                if got == want]

    three = ["planned_files", "planned_paths_sha256", "version"]
    whole = three + ["num_files", "size_in_bytes", "live_paths_sha256"]
    driver.warming = True       # every warm-up operation is `full`
    assert compared(plan, True) == three
    assert compared(landing, True) == whole
    driver.warming = False      # the window: its closing operation alone
    assert compared(plan, False) == three
    assert compared(landing, False) == three
    assert compared(plan, True) == whole
    assert compared(landing, True) == whole


if __name__ == "__main__":      # the cell itself, on four chips, broken
    import argparse

    t0 = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("system", choices=sorted(BROKEN))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    asked = parser.parse_args()
    Broken.on = DeltaTpu    # the default engine: the gate's own mesh
    result = harness.run_cell(CELL, asked.seed, asked.seconds, False, t0,
                              system=BROKEN[asked.system][1]())
    print(json.dumps({"system": asked.system, "cell": CELL,
                      "seed": asked.seed, "correct": result["correct"],
                      "has_to_read": False,
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "device": result["device"]}), flush=True)
    raise SystemExit(result["correct"] is not False)
