"""The deployment `deltalog-4m-stream-ckpt10` and its cell
`ckpt-landing-under-ingest`, at a test's size on the CPU: a table whose
writer lands a checkpoint with every tenth commit while readers hold
their state through `update()`. The generator and its manifest against
the plain reference, both engines and a cold reader after every
landing; the driver's kinds of operation; whole runs and four broken
systems; why an update fell back, on the program's spans and counters;
and the seven readers."""

import hashlib
import importlib.util
import json
import os
import threading
import time
import types

import pyarrow.parquet as pq
import pytest

from chipbench import harness, traffic
from chipbench.gen import deltalog, deltastream, deltastream_ckpt
from chipbench.reference import oracle, plan_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "stream_ckpt", "benchmark.json")
CELL = "ckpt-landing-under-ingest"
PARAMS = dict(commits=64, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=40,
              staged_checkpoints=4)
W = deltastream.batch_width(80)
MS = 1_000_000
SEED = 2**31 + 23


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"ckpt_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "scan_under_ingest_ckpt")
with open(os.path.join(ROOT, "chipbench", "mixes",
                       "ycsb-e-scans-ckpt10.json")) as f:
    MIX = json.load(f)


def sha(paths) -> str:
    return hashlib.sha256("\n".join(sorted(paths)).encode()).hexdigest()


# ---- the readers of the table against the manifest, landing by landing ----

# commit v holds x in [(v + 1) W, (v + 2) W]; seven kinds of range, some
# of them over the commits that land and over the checkpoints' versions
RANGES = {
    "on-both-edges": (11 * W, 14 * W),
    "starts-past-an-edge": (11 * W + 1, 14 * W),
    "inside-one-batch": (71 * W + 5, 71 * W + 6),
    "before-the-first-batch": (0, W),
    "across-the-first-checkpoint": (68 * W, 74 * W + 1),
    "the-landed-commits": (65 * W, 105 * W),
    "from-the-newest-on": (100 * W, 10**12),
}


class Held:
    """A reader that holds its table and snapshot through `update()`."""

    def __init__(self, engine_name, path):
        from delta_tpu import Table
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.engine.tpu import TpuEngine

        engine = {"host": HostEngine, "tpu": TpuEngine}[engine_name]()
        self.table = Table.for_path(path, engine)
        self.snapshot = self.table.latest_snapshot()

    def refresh(self):
        self.snapshot = self.table.update()

    def plan(self, lo, hi):
        from delta_tpu.expressions import col, lit

        pred = (col("x") >= lit(lo)) & (col("x") < lit(hi))
        return sorted(self.snapshot.scan(filter=pred).file_paths())

    def state(self):
        snap = self.snapshot
        return (snap.version, snap.num_files, sha(
            snap.state.add_files_table.column("path").to_pylist()))


class Cold(Held):
    """A process that has never seen the table, at every landing."""

    def __init__(self, engine_name, path):
        self.args = engine_name, path
        self.refresh()

    def refresh(self):
        from delta_tpu.replay.columnar import clear_parse_cache

        clear_parse_cache()
        super().__init__(*self.args)


class Reference:
    """`reference/plan_oracle.py` and `reference/oracle.py`: no code of
    `delta_tpu`; the newest landed checkpoint is theirs to find."""

    def __init__(self, path):
        self.path = path

    def refresh(self):
        pass

    def plan(self, lo, hi):
        return plan_oracle.plan(self.path, lo, hi)

    def state(self):
        paths = [path for path, _ in oracle.read_table_state(self.path).live]
        newest = max(int(name[:20]) for name in os.listdir(
            os.path.join(self.path, "_delta_log")) if name[20:] == ".json")
        return newest, len(paths), sha(paths)


READERS = {
    "reference": Reference,
    "HostEngine-held": lambda path: Held("host", path),
    "TpuEngine-held": lambda path: Held("tpu", path),
    "TpuEngine-held-skip-kernel": lambda path: Held("tpu", path),
    "TpuEngine-cold": lambda path: Cold("tpu", path),
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_every_reader_follows_the_manifest_across_the_checkpoints(
        tmp_path, monkeypatch, reader):
    if reader.endswith("skip-kernel"):      # the jitted kernel, on the CPU
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    mine = READERS[reader](m.table_path)
    crossed = []
    for landing in range(38):               # 64 .. 101: 70, 80, 90 and 100
        m.land(1)
        mine.refresh()
        if m.version % 10 == 0:
            crossed.append(m.version)
            assert m.checkpoint_version == m.version
        assert mine.state() == (m.version, m.num_files(), m.digest())
        for case, (lo, hi) in RANGES.items():
            want = [deltalog.path_of(int(i)) for i in m.scan_expected(lo, hi)]
            assert mine.plan(lo, hi) == want, (landing, case)
    assert crossed == [70, 80, 90, 100]
    assert len(m.scan_expected(*RANGES["the-landed-commits"])) > 2000


# ---- the generator: what it stages, how it lands, that it repeats ----

def test_a_staged_checkpoint_is_the_base_writers_of_the_live_set(tmp_path):
    """Schema, row order, stats strings and writer settings as the base
    checkpoint's: equal to `_checkpoint_table` of what the manifest has
    live at that version."""
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    assert [c.version for c in m.staged_checkpoints] == [70, 80, 90, 100]
    # `deltalog` with the streaming sink's stats, as `deltastream` runs it
    spec = importlib.util.find_spec("chipbench.gen.deltalog")
    private = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(private)
    private.stats_of = lambda fid: deltastream.stats_of(fid, m.adds_per_commit)
    log = os.path.join(m.table_path, "_delta_log")
    base = pq.ParquetFile(os.path.join(
        log, deltastream_ckpt.checkpoint_name(60)))
    seen = 0
    for c in list(m.staged):
        staged = os.path.join(m.staged_dir,
                              deltastream_ckpt.checkpoint_name(c.version))
        due = c.version % 10 == 0 and c.version <= 100
        assert os.path.exists(staged) == due
        m.land(1)
        if not due:
            continue
        seen += 1
        assert not os.path.exists(staged)       # moved, not copied
        landed = os.path.join(log, deltastream_ckpt.checkpoint_name(c.version))
        live = deltalog._writer_order(m.live_ids())
        got = pq.read_table(landed)
        assert got.equals(private._checkpoint_table(live, m.adds_per_commit))
        assert got.num_rows == m.num_files() + 2
        mine = pq.ParquetFile(landed)
        assert mine.schema_arrow.equals(base.schema_arrow)
        column = mine.metadata.row_group(0).column(0)
        assert column.compression == base.metadata.row_group(
            0).column(0).compression == "SNAPPY"
        with open(os.path.join(log, "_last_checkpoint")) as f:
            assert json.load(f) == {
                "version": c.version, "size": m.num_files() + 2,
                "sizeInBytes": os.path.getsize(landed),
                "numOfAddFiles": m.num_files()}
        if c.version == 100:
            break
    assert seen == 4
    # nothing is cleaned up: older checkpoints and commits stay
    assert {name for name in os.listdir(log) if "checkpoint" in name} == {
        "_last_checkpoint", *(deltastream_ckpt.checkpoint_name(v)
                              for v in (60, 70, 80, 90, 100))}
    assert deltalog.commit_name(41) in os.listdir(log)


def test_the_last_checkpoint_hint_names_the_newest_landed_one(tmp_path):
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    hint = os.path.join(m.table_path, "_delta_log", "_last_checkpoint")

    def hinted():
        with open(hint) as f:
            return json.load(f)["version"]

    assert hinted() == 60 == m.checkpoint_version
    m.land(5)
    assert (m.version, hinted(), m.checkpoint_version) == (68, 60, 60)
    m.land(2)                               # 69, then 70 with its checkpoint
    assert (m.version, hinted(), m.checkpoint_version) == (70, 70, 70)
    m.land(13)                              # one call over 80
    assert (m.version, hinted(), m.checkpoint_version) == (83, 80, 80)


def test_used_up_checkpoints_raise_before_anything_moves(tmp_path):
    m = deltastream_ckpt.generate(
        str(tmp_path), dict(PARAMS, staged_checkpoints=1), SEED)
    m.land(15)                              # through 78; 70 had the one
    assert m.version == 78 and m.checkpoint_version == 70
    m.land(1)
    with pytest.raises(RuntimeError, match="staged_checkpoints"):
        m.land(1)                           # 80 is due and has none
    assert m.version == 79 and len(m.staged) == 40 - 16
    assert not os.path.exists(os.path.join(
        m.table_path, "_delta_log", deltalog.commit_name(80)))


def tree(root) -> dict:
    out = {}
    for base, _, files in os.walk(root):
        for name in files:
            with open(os.path.join(base, name), "rb") as f:
                out[os.path.relpath(os.path.join(base, name), root)] = (
                    hashlib.sha256(f.read()).hexdigest())
    return out


def test_the_generator_is_deterministic_in_the_seed(tmp_path, capsys):
    trees = []
    for d, seed in (("a", SEED), ("b", SEED), ("c", SEED + 1)):
        deltastream_ckpt.generate(str(tmp_path / d), PARAMS, seed)
        trees.append(tree(str(tmp_path / d)))
    assert trees[0] == trees[1] and trees[0] != trees[2]
    assert sum("checkpoint.parquet" in name for name in trees[0]) == 5
    plain = tree(deltastream.generate(
        str(tmp_path / "plain"), PARAMS, SEED).table_path + "/..")
    assert {k: v for k, v in trees[0].items()
            if "staged/" not in k or k.endswith(".json")} == plain
    assert "staged checkpoints: 4 written in" in capsys.readouterr().out


# ---- the driver: kinds by landing, the warm-up, the mix ----

def test_the_mix_is_the_siblings_draw_for_draw():
    with open(os.path.join(ROOT, "chipbench", "mixes",
                           "ycsb-e-scans.json")) as f:
        sibling = json.load(f)
    assert {k: MIX[k] for k in ("block", "draws", "loop")} == {
        k: sibling[k] for k in ("block", "draws", "loop")}
    assert MIX["driver"] == "scan_under_ingest_ckpt"
    assert MIX["fixture"] == {"staged_commits": 2000,
                              "staged_checkpoints": 16}
    for seed in (1, SEED):
        a, b = traffic.schedule(MIX, seed), traffic.schedule(sibling, seed)
        assert [next(a) for _ in range(300)] == [next(b) for _ in range(300)]


class Counting(DeltaTpu):
    """The system, counting what the driver asks of it."""

    def __init__(self):
        self.calls = []

    def refresh(self, table):
        self.calls.append("refresh")
        return super().refresh(table)

    def state(self, snapshot):
        self.calls.append("state")
        return super().state(snapshot)


def drive(tmp_path, n_ops, system=None):
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    driver = DRIVER.Driver(system or DeltaTpu(), m)
    ops = []

    def run_op(params, full=True):
        prep = driver.prepare(params)
        kind, compared = driver.check(prep, driver.timed(prep), full)
        ops.append((kind, m.version, compared))
        return kind

    schedule = traffic.schedule(MIX, SEED)
    driver.warm_up(run_op, schedule)
    warm = len(ops)
    for _ in range(n_ops):
        run_op(next(schedule), full=False)
    return m, driver, ops, warm


def test_the_kinds_follow_the_landings_and_the_warm_up_sees_each(tmp_path):
    system = Counting()
    m, driver, ops, warm = drive(tmp_path, 260, system)
    # 64 .. 69 land as refreshes, 70 brings its checkpoint: the warm-up
    # ends there, 140 operations in, having seen a refresh long before
    assert warm == 140 and ops[warm - 1][:2] == ("crossing", 70)
    kinds = [kind for kind, _, _ in ops]
    assert kinds[:warm].count("refresh") == 6
    assert kinds[:warm].count("crossing") == 1
    landed = [(kind, v) for kind, v, _ in ops if kind != "plan"]
    assert landed == [("crossing" if v % 10 == 0 else "refresh", v)
                      for v in range(64, 64 + len(landed))]
    assert [i for i, k in enumerate(kinds) if k != "plan"] == list(
        range(19, len(ops), 20))
    assert ("crossing", 80) in landed
    assert driver.commits.n == 64 + 40      # the key space: loaded + staged
    for kind, _, compared in ops:
        names = [name for name, _, _ in compared]
        assert names[:3] == ["planned_files", "planned_paths_sha256",
                             "version"]
        assert all(got == want for _, got, want in compared)
    # in the window a crossing also compares the count and the size
    [(crossing, extra)] = [(k, [n for n, _, _ in c][3:])
                           for k, v, c in ops[warm:] if v == 80 and k != "plan"]
    assert crossing == "crossing" and extra == ["num_files", "size_in_bytes"]
    assert all(len(c) == 3 for k, _, c in ops[warm:] if k != "crossing")
    # in the warm-up the landings are compared in full, the plans are not
    for kind, _, compared in ops[:warm]:
        assert len(compared) == (3 if kind == "plan" else 6)
        assert kind == "plan" or compared[-1][0] == "live_paths_sha256"
    assert system.calls.count("refresh") == len(landed)
    assert system.calls.count("state") == 7 + 1


def test_the_windows_closing_operation_compares_every_live_path(tmp_path):
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    driver = DRIVER.Driver(DeltaTpu(), m)
    driver.table, driver.snapshot = driver.system.load(m.table_path)
    prep = driver.prepare({"refresh": 0, "start": 0.5, "length": 0.5})
    kind, compared = driver.check(prep, driver.timed(prep), True)
    assert kind == "plan"
    assert compared[-1] == ("live_paths_sha256", m.digest(), m.digest())
    assert compared[3] == ("num_files", m.num_files(), m.num_files())


def test_the_driver_raises_when_the_checkpoints_are_used_up(tmp_path):
    m = deltastream_ckpt.generate(
        str(tmp_path), dict(PARAMS, staged_checkpoints=1), SEED)
    driver = DRIVER.Driver(DeltaTpu(), m)
    driver.table, driver.snapshot = driver.system.load(m.table_path)
    land = {"refresh": 1, "start": 0.5, "length": 0.5}
    for _ in range(16):                     # 64 .. 79
        driver.prepare(land)
    with pytest.raises(RuntimeError, match="more `staged_checkpoints`"):
        driver.prepare(land)


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == "deltalog-4m-stream-ckpt10"
    assert cell.entry["chips"] == 1
    assert cell.entry["traffic"] == "ycsb-e-scans-ckpt10"
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert mine == {"crossing_refresh_ms", "plain_refresh_ms",
                    "update_fallback_pct", "crossing_load_ms",
                    "crossing_index_ms", "plain_plan_ms",
                    "landing_skip_roofline"}
    for m in cell.metrics_of("per_layer"):
        assert m["workloads"] == [CELL]
        assert cell.module("layers", m["name"]).read
    assert {m["name"] for m in cell.metrics_of("end_to_end")} == {
        "op_p50_ms", "ops_per_s", "setup_s"}


def test_the_configuration_is_the_siblings_but_for_the_writer():
    def config(name):
        with open(os.path.join(ROOT, "chipbench", "configs",
                               name + ".json")) as f:
            text = f.read()
        assert "DELTA_TPU_" not in text
        return json.loads(text)

    mine, sibling = config("deltalog-4m-stream-ckpt10"), config(
        "deltalog-4m-stream")
    assert list(mine["reduced"]) == ["commits"]
    assert mine["reduced"] == sibling["reduced"]
    assert mine["environment"] == sibling["environment"]
    assert mine["generator"] == dict(sibling["generator"],
                                     kind="deltastream_ckpt")
    assert mine["generator"]["checkpoint_interval"] == 10
    assert mine["guarantees"][:3] == sibling["guarantees"]
    assert len(mine["guarantees"]) == 4 and "crossed a checkpoint" in (
        mine["guarantees"][3])
    assert set(mine["assumed"]) - set(sibling["assumed"]) == {
        "writer_checkpoints", "staged_checkpoints"}
    assert set(sibling["assumed"]) - set(mine["assumed"]) == {
        "checkpoints_in_window"}
    assert all(mine["assumed"][k] == v for k, v in sibling["assumed"].items()
               if k != "checkpoints_in_window")
    assert mine["source"] != sibling["source"]
    assert "every tenth" in mine["deployment"]


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=SEED, seconds=0.6):
    return harness.run_cell("tiny-landing-under-ingest", seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system)


def test_a_run_is_correct_and_names_its_kinds(capsys):
    result = run()
    assert result["correct"] and result["failed"] == 0
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    out = capsys.readouterr().out
    assert "staged checkpoints: 16 written in" in out
    for compared in ("planned_files", "planned_paths_sha256", "version",
                     "num_files", "size_in_bytes", "live_paths_sha256"):
        assert f"warm-up {compared}: compared" in out
        assert f"window {compared}: compared" in out or compared in (
            "num_files", "size_in_bytes")   # no crossing in a short window
    assert "window live_paths_sha256: compared 1," in out
    assert "mismatches 0 (limit 0)" in out and " refresh (median" in out


def test_a_traced_run_reads_the_cells_metrics():
    # long enough for a crossing inside the window: 180 operations
    result = run(trace=True, seconds=2.5)
    assert result["correct"]
    # no plan reaches a chip here, so the kernel's share has nothing to read
    assert set(result["metrics"]) == {
        "crossing_refresh_ms", "plain_refresh_ms", "update_fallback_pct",
        "crossing_load_ms", "crossing_index_ms", "plain_plan_ms",
        "device_idle_pct"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["crossing_refresh_ms"] > m["crossing_load_ms"] > 0
    assert m["crossing_refresh_ms"] > m["crossing_index_ms"] > 0
    assert m["plain_refresh_ms"] > m["plain_plan_ms"] > 0
    assert 0 < m["update_fallback_pct"] <= 20   # one landing in ten


class NeverCrosses(DeltaTpu):
    """Stays on the state it can advance: where a checkpoint has
    appeared it hands out the snapshot it held (a stale version)."""

    def refresh(self, table):
        held = table._cached_snapshot
        return held.update() or held


class KeepsTheOldIndex(DeltaTpu):
    """Crosses, and then plans on what it held before the crossing (its
    index among it) until the next refresh: the version is right, the
    crossing's commit is missing from the plans."""

    before = None

    def refresh(self, table):
        held = table._cached_snapshot
        fresh = super().refresh(table)
        crossed = (fresh._segment.checkpoint_version
                   != held._segment.checkpoint_version)
        self.before = held if crossed else None
        return fresh

    def plan(self, snapshot, lo, hi):
        return super().plan(self.before or snapshot, lo, hi)


class DropsTheCommitsAfter(DeltaTpu):
    """Plans on the newest checkpoint alone: right on a crossing, and
    without every commit that landed since on any other operation."""

    def plan(self, snapshot, lo, hi):
        at = snapshot._segment.checkpoint_version
        return super().plan(snapshot._table.snapshot_at(at), lo, hi)


class KeepsMore(DeltaTpu):
    """A plan one micro-batch too wide: no file is lost, some are extra."""

    def plan(self, snapshot, lo, hi):
        return super().plan(snapshot, lo, hi + W)


@pytest.mark.parametrize("system,compared", [
    (NeverCrosses, "version"), (KeepsTheOldIndex, "planned_files"),
    (DropsTheCommitsAfter, "planned_files"), (KeepsMore, "planned_files")])
def test_a_broken_guarantee_is_not_correct(system, compared, capsys):
    result = run(system=system())
    assert result["correct"] is False
    out = capsys.readouterr().out
    assert "first mismatch: got" in out
    [line] = [x for x in out.splitlines()
              if x.startswith(f"warm-up {compared}:")]
    assert "mismatches 0 " not in line


# ---- the program says why an update fell back ----

@pytest.fixture
def traced(tmp_path):
    """A table, a reader, and the spans and counters of what is done
    under `with traced.recording():`."""
    import contextlib

    from delta_tpu import obs

    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    out = types.SimpleNamespace(manifest=m, spans=[], counters={})
    log = os.path.join(m.table_path, "_delta_log")

    def counters():
        return {k: v for k, v in obs.metrics_snapshot()["counters"].items()
                if k.startswith("snapshot.update_fallbacks.")}

    @contextlib.contextmanager
    def recording():
        obs.set_trace_mode("on")
        obs.reset_trace_buffer()
        before = counters()
        try:
            yield
        finally:
            out.spans = [s.to_dict() for s in obs.get_finished_spans()]
            out.counters = {k.rsplit(".", 1)[1]: v - before[k]
                            for k, v in counters().items() if v != before[k]}
            obs.set_trace_mode(None)
            obs.reset_trace_buffer()

    def attrs(name):
        [s] = [s for s in out.spans if s["name"] == name]
        return s["attrs"]

    def write(name, *lines):
        with open(os.path.join(log, name), "w") as f:
            f.write("".join(line + "\n" for line in lines))

    out.recording, out.attrs, out.write, out.log = (
        recording, attrs, write, log)
    return out


def held_reader(m):
    from delta_tpu import Table

    table = Table.for_path(m.table_path)
    snapshot = table.latest_snapshot()
    snapshot.state                          # replayed, so it can be advanced
    return table, snapshot


def cause_checkpoint(t):
    t.manifest.land(7)                      # 64 .. 70, with 70's checkpoint


def cause_compacted_delta(t):
    t.manifest.land(2)
    lines = []
    for v in (64, 65):
        with open(os.path.join(t.log, deltalog.commit_name(v))) as f:
            lines += f.read().splitlines()
    t.write(f"{64:020d}.{65:020d}.compacted.json", *lines)


def cause_gap(t):
    staged = os.path.join(t.manifest.staged_dir, deltalog.commit_name(65))
    os.replace(staged, os.path.join(t.log, deltalog.commit_name(65)))


def cause_protocol(t):
    t.write(deltalog.commit_name(64), deltalog.PROTOCOL,
            deltalog.add_line(10**6, 64))


@pytest.mark.parametrize("reason,cause", [
    ("checkpoint", cause_checkpoint),
    ("compacted_delta", cause_compacted_delta),
    ("gap", cause_gap), ("protocol", cause_protocol)])
def test_a_snapshot_update_that_falls_back_says_why_once(traced, reason,
                                                         cause):
    _, snapshot = held_reader(traced.manifest)
    cause(traced)
    with traced.recording():
        assert snapshot.update() is None
    got = traced.attrs("snapshot.update")
    assert (got["outcome"], got["reason"]) == ("fallback_full_load", reason)
    assert traced.counters == {reason: 1}


def test_an_update_with_no_state_to_advance_says_so(traced):
    from delta_tpu import Table

    lazy = Table.for_path(traced.manifest.table_path).latest_snapshot()
    traced.manifest.land(1)
    with traced.recording():
        fresh = lazy.update()
    assert fresh is not lazy and fresh.version == 64
    got = traced.attrs("snapshot.update")
    assert (got["outcome"], got["reason"]) == ("fallback_full_load",
                                               "no_state")
    assert traced.counters == {"no_state": 1}


def test_updates_that_advance_or_find_nothing_give_no_reason(traced):
    table, _ = held_reader(traced.manifest)
    with traced.recording():
        table.update()
    assert traced.attrs("table.update")["outcome"] == "unchanged"
    assert "reason" not in traced.attrs("table.update")
    traced.manifest.land(1)
    with traced.recording():
        assert table.update().version == 64
    assert traced.attrs("table.update")["outcome"] == "advanced"
    assert traced.attrs("snapshot.update")["outcome"] == "advanced"
    assert "reason" not in traced.attrs("table.update")
    assert "reason" not in traced.attrs("snapshot.update")
    assert traced.counters == {}


def test_a_crossing_names_its_cause_on_every_span_of_its_tree(traced,
                                                              monkeypatch):
    """`table.update` > `snapshot.update` (why) and
    `table.latest_snapshot`; then the plan loads the state and builds
    the index from nothing."""
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    system = DeltaTpu()
    table, snapshot = system.load(traced.manifest.table_path)
    system.plan(snapshot, 11 * W, 14 * W)
    cause_checkpoint(traced)
    with traced.recording():
        snapshot = system.refresh(table)
        paths = system.plan(snapshot, 60 * W, 80 * W)
    assert len(paths) == len(traced.manifest.scan_expected(60 * W, 80 * W))
    assert snapshot.version == 70
    assert snapshot._segment.checkpoint_version == 70
    update = traced.attrs("table.update")
    assert (update["outcome"], update["reason"]) == ("full_load",
                                                     "checkpoint")
    by_id = {s["span_id"]: s for s in traced.spans}
    for name, parent in (("snapshot.update", "table.update"),
                         ("table.latest_snapshot", "table.update"),
                         ("stats.index_build", "plan.skip"),
                         ("stats.index_upload", "plan.skip")):
        [s] = [s for s in traced.spans if s["name"] == name]
        assert by_id[s["parent_id"]]["name"] == parent
    assert [s["name"] for s in traced.spans].count("snapshot.load") == 1
    build = traced.attrs("stats.index_build")
    assert (build["mode"], build["reason"]) == ("full", "no_seed")
    assert build["rows"] == traced.manifest.num_files()
    assert "append_fallback" not in build
    assert traced.counters == {"checkpoint": 1}
    # the refresh after it has a seed again and says nothing of the kind
    traced.manifest.land(1)
    with traced.recording():
        system.plan(system.refresh(table), 60 * W, 80 * W)
    assert traced.attrs("table.update")["outcome"] == "advanced"
    build = traced.attrs("stats.index_build")
    assert build["mode"] == "append" and "reason" not in build


def test_a_table_with_nothing_cached_loads_in_full_and_says_so(traced):
    from delta_tpu import Table

    with traced.recording():
        Table.for_path(traced.manifest.table_path).update()
    update = traced.attrs("table.update")
    assert (update["outcome"], update["reason"]) == ("full_load", "no_state")
    assert traced.counters == {}            # no snapshot.update ran


def test_a_crossing_leaves_one_state_and_one_index_behind(tmp_path,
                                                          monkeypatch):
    """What the chip run watches as bytes, counted as objects: after
    every crossing the process holds the new state, snapshot and index
    and none of those before it, and Arrow's pool holds no more a live
    file than after the first (600 files land between two crossings)."""
    import gc

    import pyarrow as pa

    from delta_tpu.replay.state import SnapshotState
    from delta_tpu.snapshot import Snapshot
    from delta_tpu.stats.device_index import ResidentStatsIndex

    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    m = deltastream_ckpt.generate(str(tmp_path), PARAMS, SEED)
    system = DeltaTpu()
    table, snapshot = system.load(m.table_path)
    held = []
    for _ in range(38):
        m.land(1)
        snapshot = system.refresh(table)
        system.plan(snapshot, 11 * W, 14 * W)
        if m.version % 10:
            continue
        gc.collect()
        alive = gc.get_objects()
        counts = [sum(isinstance(o, kind) for o in alive)
                  for kind in (SnapshotState, Snapshot, ResidentStatsIndex)]
        del alive
        assert counts == [1, 1, 1], m.version
        held.append(pa.total_allocated_bytes() / m.num_files())
    assert len(held) == 4 and max(held) < 1.1 * held[0]


# ---- the readers, on a recorded run ----

def reader(name):
    return module("layers", name).read


def span(name, start_ms, dur_ms, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "thread_id": threading.get_ident(), "attrs": attrs}


def op(kind, start_ms, end_ms):
    return {"kind": kind, "start_unix_ns": start_ms * MS,
            "end_unix_ns": end_ms * MS}


# plans at 0, 100, 200; refreshes at 1,000 and 2,000; crossings at 3,000
# and 8,000 ms
OPS = [op("plan", 0, 50), op("plan", 100, 130), op("plan", 200, 290),
       op("refresh", 1000, 1900), op("refresh", 2000, 2700),
       op("crossing", 3000, 7500), op("crossing", 8000, 11900)]
RECORDED = [
    span("scan.plan", 1, 40), span("scan.plan", 101, 20),
    span("scan.plan", 201, 80),
    span("table.update", 1000, 300, outcome="advanced"),
    span("snapshot.update", 1001, 290, outcome="advanced"),
    span("scan.plan", 1300, 590), span("stats.index_build", 1310, 60),
    span("stats.index_upload", 1380, 2),
    span("table.update", 2000, 200, outcome="advanced"),
    span("scan.plan", 2200, 490),
    span("table.update", 3000, 10, outcome="full_load", reason="checkpoint"),
    span("scan.plan", 3010, 4480), span("snapshot.load", 3020, 1100),
    span("stats.index_build", 4200, 2900),
    span("stats.index_upload", 7100, 100),
    span("table.update", 8000, 12, outcome="full_load", reason="checkpoint"),
    span("scan.plan", 8012, 3880), span("snapshot.load", 8020, 900),
    span("stats.index_build", 9000, 2700),
    span("stats.index_upload", 11700, 100),
    span("scan.plan", 20000, 7),    # outside every operation
    span("table.update", 20010, 1, outcome="unchanged"),
]


def without_outcome(spans):
    return [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k not in ("outcome", "reason")})
            for s in spans]


def recorded(spans=RECORDED, dispatches=(), events=()):
    trace = types.SimpleNamespace(events=[list(events)] if events else [])
    return types.SimpleNamespace(ops=OPS, spans=spans, trace=trace,
                                 dispatches=list(dispatches),
                                 device_kind="TPU v5 lite")


@pytest.mark.parametrize("name,want", [
    ("crossing_refresh_ms", (10 + 4480 + 12 + 3880) / 2),
    ("plain_refresh_ms", (300 + 590 + 200 + 490) / 2),
    ("update_fallback_pct", 100 * 2 / 5),       # of five `table.update`
    ("crossing_load_ms", (1100 + 900) / 2),
    ("crossing_index_ms", (3000 + 2800) / 2),   # build + upload
    ("plain_plan_ms", 40),                      # of 40, 20, 80
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


@pytest.mark.parametrize("name,spans,want", [
    # the parent of the PR that added them: the spans, no `outcome`
    ("update_fallback_pct", without_outcome(RECORDED), None),
    ("crossing_refresh_ms", without_outcome(RECORDED), 4191),
    ("plain_refresh_ms", without_outcome(RECORDED), 790),
    ("crossing_load_ms", without_outcome(RECORDED), 1000),
    ("crossing_index_ms", without_outcome(RECORDED), 2900),
    ("plain_plan_ms", without_outcome(RECORDED), 40),
    ("crossing_refresh_ms", [], None), ("plain_refresh_ms", [], None),
    ("update_fallback_pct", [], None), ("crossing_load_ms", [], None),
    ("crossing_index_ms", [], None), ("plain_plan_ms", [], None),
])
def test_a_reader_on_a_program_without_its_spans(name, spans, want):
    got = reader(name)(recorded(spans))
    assert got is None if want is None else got == pytest.approx(want)


def test_a_window_without_a_crossing_reads_no_crossing():
    calm = types.SimpleNamespace(
        ops=OPS[:5], spans=RECORDED, trace=None, dispatches=[],
        device_kind="TPU v5 lite")
    for name in ("crossing_refresh_ms", "crossing_load_ms",
                 "crossing_index_ms"):
        assert reader(name)(calm) is None
    assert reader("plain_refresh_ms")(calm) == pytest.approx(790)


def launch(**attrs):
    record = {"kernel": "skipping.mask_block", "key": "2"}
    return dict(record, attrs=attrs) if attrs else record


def test_landing_skip_roofline_is_skip_rooflines_reckoning():
    n_pad = 2_621_440
    events = [("jit_skipping_mask_block/fusion.3", 0, 500_000),
              ("jit_skipping_mask_block/fusion.4", 400_000, 1_000_000),
              ("jit_stats_index_upload/fusion", 0, 9_000_000)]
    run_ = recorded(dispatches=[launch(lanes=4, n_pad=n_pad)] * 2
                    + [{"kernel": "stats.index_upload"}], events=events)
    least = 2 * (4 * n_pad * 9 + n_pad) / 819e9
    got = reader("landing_skip_roofline")(run_)
    assert got == pytest.approx(100 * least / 1e-3)
    assert got == reader("skip_roofline")(run_) and got < 100


@pytest.mark.parametrize("dispatches,events", [
    ([], [("jit_skipping_mask_block/fusion", 0, 5)]),   # no plan on the chip
    ([launch()], [("jit_skipping_mask_block/fusion", 0, 5)]),
    ([launch(lanes=4, n_pad=4096)], []),                # no device plane
], ids=["host-route", "no-shape-on-the-record", "no-device-events"])
def test_landing_skip_roofline_finds_nothing_to_read(dispatches, events):
    assert reader("landing_skip_roofline")(recorded(
        dispatches=dispatches, events=events)) is None
