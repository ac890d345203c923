"""The deployment `nexmark-bids-4m-stream` and its cell
`bids-query-under-ingest`, at a test's size on the CPU: the generator
and its manifest against the plain reference, readers of both engines
that hold their state through `update()`, and a cold reader, on every
kind of predicate after every landing; the typed index; the driver's
reading of the mix; whole runs; the seven readers; four broken systems."""

import collections
import datetime
import hashlib
import importlib.util
import json
import os
import threading
import time
import types

import numpy as np
import pytest

from chipbench import bid_queries, harness, traffic
from chipbench.gen import deltalog, deltastream, nexmark_bids
from chipbench.reference import bid_plan_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "bids", "benchmark.json")
CELL = "bids-query-under-ingest"
PARAMS = dict(commits=64, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=24)
LANDINGS = 20
B = nexmark_bids.Batch(80)
MS = 1_000_000
UTC = datetime.timezone.utc


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bids_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "bid_windows_under_ingest")
with open(os.path.join(ROOT, "chipbench", "mixes",
                       "ycsb-e-bid-windows.json")) as f:
    MIX = json.load(f)


# ---- manifest = reference = both engines' held readers = a cold reader ----

def t(v, plus_us=0):
    """The start of batch `v`, microseconds since 1970."""
    return B.start_us(v) + plus_us


def auctions_of(*batches):
    """One auction opened in the middle of each of `batches`."""
    return tuple(B.first_auction(v) + B.auctions // 2 for v in batches)


def a_live_file(m, v):
    return int(np.flatnonzero(m.alive[v * 80:(v + 1) * 80])[0]) + v * 80


WINDOWS = {   # name: manifest -> (t0, t1, auctions), microseconds
    "window-alone": lambda m: (t(11), t(14), ()),
    "window-and-selection": lambda m: (t(5), t(40), auctions_of(7, 20, 33)),
    "selection-of-a-neighbour-in-flight": lambda m: (
        t(5), t(40), (B.first_auction(21) - 50,)),
    "before-all-data": lambda m: (t(-9), t(-5), ()),
    "after-all-data": lambda m: (t(200), t(300), ()),
    "over-what-lands": lambda m: (t(60), t(500), auctions_of(66, 70, 83)),
    # batch 30's files reach back up to 3 s: some begin before, some after
    "ends-inside-the-late-reach": lambda m: (t(27), t(30, -2_970_000), ()),
    # one stored max M: an instant within its millisecond keeps the file
    "inside-the-truncated-millisecond": lambda m: (
        int(m.stats.time_max[a_live_file(m, 20)]) + 500, t(300), ()),
    "at-the-end-of-the-millisecond": lambda m: (
        int(m.stats.time_max[a_live_file(m, 20)]) + 1000, t(300), ()),
    "past-the-truncated-millisecond": lambda m: (
        int(m.stats.time_max[a_live_file(m, 20)]) + 1001, t(300), ()),
}


class HeldReader:
    """A reader process: loads once, then `update()` after each landing."""

    def __init__(self, path, engine, route):
        from delta_tpu import Table

        self.route = route
        self.table = Table.for_path(path, engine)
        self.snapshot = self.table.latest_snapshot()

    def refresh(self):
        self.snapshot = self.table.update()

    def plan(self, monkeypatch, t0, t1, auctions, zone=UTC):
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", self.route)
        return sorted(bid_queries.plan_bids(
            self.snapshot, nexmark_bids.instant(t0).astimezone(zone),
            nexmark_bids.instant(t1), auctions))


def cold_plan(path, t0, t1, auctions):
    from delta_tpu import Table
    from delta_tpu.replay.columnar import clear_parse_cache

    clear_parse_cache()
    snapshot = Table.for_path(path).latest_snapshot()
    return sorted(bid_queries.plan_bids(
        snapshot, nexmark_bids.instant(t0), nexmark_bids.instant(t1),
        auctions))


def held_readers(path):
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine

    return {"HostEngine": HeldReader(path, HostEngine(), "off"),
            "TpuEngine-twin": HeldReader(path, TpuEngine(), "off"),
            "TpuEngine-skip-kernel": HeldReader(path, TpuEngine(), "force")}


def paths(ids):
    return [deltalog.path_of(int(i)) for i in ids]


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_every_reader_finds_the_manifests_files_after_every_landing(
        tmp_path, monkeypatch, case):
    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=2**31 + 11)
    readers = held_readers(m.table_path)
    seen = set()
    for landed in range(LANDINGS + 1):
        if landed:
            m.land(1)
            for reader in readers.values():
                reader.refresh()
        t0, t1, auctions = WINDOWS[case](m)
        want = paths(m.scan_expected(t0, t1, auctions))
        seen.add(len(want))
        assert bid_plan_oracle.plan(
            m.table_path, nexmark_bids.instant(t0),
            nexmark_bids.instant(t1), auctions) == want, landed
        for name, reader in readers.items():
            assert reader.snapshot.version == m.version
            assert reader.plan(monkeypatch, t0, t1, auctions) == want, (
                name, landed)
        if landed % 5 == 0:
            monkeypatch.delenv("DELTA_TPU_DEVICE_SKIP")
            assert cold_plan(m.table_path, t0, t1, auctions) == want, landed
    if case in ("before-all-data", "after-all-data"):
        assert seen == {0}
    elif case == "over-what-lands":
        assert len(seen) > 10       # the answer grows with the table
    else:
        assert 0 not in seen


def test_the_truncated_millisecond_decides_one_files_fate(tmp_path):
    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=3)
    fid = a_live_file(m, 20)
    got = {case: fid in m.scan_expected(*WINDOWS[case](m))
           for case in WINDOWS if "millisecond" in case}
    assert got == {"inside-the-truncated-millisecond": True,
                   "at-the-end-of-the-millisecond": True,
                   "past-the-truncated-millisecond": False}
    # late events: batch 30's files split on a window that ends 2.97 s
    # before it begins, and the whole of batch 13 meets [t_11, t_13)
    kept = m.scan_expected(*WINDOWS["ends-inside-the-late-reach"](m)) // 80
    assert 0 < (kept == 30).sum() < m.alive[30 * 80:31 * 80].sum()
    assert set(m.scan_expected(t(11), t(13)) // 80) == {10, 11, 12, 13}


def test_a_literal_in_another_zone_is_the_same_instant(tmp_path,
                                                       monkeypatch):
    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=4)
    want = paths(m.scan_expected(t(11), t(14)))
    for name, reader in held_readers(m.table_path).items():
        for hours in (-7, 5.5):
            zone = datetime.timezone(datetime.timedelta(hours=hours))
            assert reader.plan(monkeypatch, t(11), t(14), (),
                               zone) == want, name


def test_a_zone_less_literal_keeps_every_file_and_is_counted(tmp_path,
                                                             monkeypatch):
    from delta_tpu import obs
    from delta_tpu.expressions import col, lit

    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=5)
    counted = obs.counter("scan.skip_uncompared_conjuncts")
    naive = nexmark_bids.instant(t(11)).replace(tzinfo=None)
    for name, reader in held_readers(m.table_path).items():
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", reader.route)
        before = counted.value
        got = reader.snapshot.scan(
            filter=col("dateTime") >= lit(naive)).file_paths()
        assert sorted(got) == paths(m.live_ids()), name
        assert counted.value == before + 1, name


def test_text_columns_stay_on_the_ladder_and_conservative(tmp_path,
                                                          monkeypatch):
    """`channel`'s max is short, so it is exact; `url`'s is 32
    characters, so it may be a cut prefix and rules nothing out."""
    from delta_tpu.expressions import col, lit

    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=6)
    ids = m.scan_expected(t(11), t(14))
    stats = [json.loads(s) for s in m.stats.strings(ids).to_pylist()]
    window = (col("dateTime") >= lit(nexmark_bids.instant(t(11)))) & (
        col("dateTime") < lit(nexmark_bids.instant(t(14))))
    by_channel = [i for i, s in zip(ids, stats)
                  if s["maxValues"]["channel"] >= "channel-9990"]
    assert 0 < len(by_channel) < len(ids)
    for name, reader in held_readers(m.table_path).items():
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", reader.route)
        got = reader.snapshot.scan(filter=window & (
            col("channel") >= lit("channel-9990"))).file_paths()
        assert sorted(got) == paths(by_channel), name
        got = reader.snapshot.scan(filter=window & (
            col("url") > lit("https://www.nexmark.com/zzzzzzzzzz"))
        ).file_paths()
        assert sorted(got) == paths(ids), name


# ---- the typed index ----

def test_the_index_has_lanes_for_the_four_numbers_and_counts_the_text(
        tmp_path):
    from delta_tpu import Table
    from delta_tpu.stats.device_index import build_index

    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=7)
    table = Table.for_path(m.table_path)
    snapshot = table.latest_snapshot()
    bid_queries.plan_bids(snapshot, nexmark_bids.instant(t(11)),
                          nexmark_bids.instant(t(14)))
    idx = snapshot.state.stats_index
    assert idx.cols == {("auction",): (0, "int"), ("bidder",): (3, "int"),
                        ("price",): (6, "int"), ("dateTime",): (9, "tstz")}
    assert idx.vals.shape[0] == 13 and idx.unindexed == {"string": 3}
    # the lanes hold the manifest's arrays, the max a millisecond on
    files = snapshot.state.add_files_table
    ids = np.array([int(p[5:15]) for p in files.column("path").to_pylist()])
    n = len(ids)
    assert np.array_equal(idx.vals[0, :n], m.stats.auction_min[ids])
    assert np.array_equal(idx.vals[1, :n], m.stats.auction_max[ids])
    assert np.array_equal(idx.vals[9, :n], m.stats.time_min[ids])
    assert np.array_equal(idx.vals[10, :n], m.stats.time_max[ids] + 1000)
    # carried over landings, it equals one built from every stats string
    for _ in range(3):
        m.land(1)
        snapshot = table.update()
        bid_queries.plan_bids(snapshot, nexmark_bids.instant(t(11)),
                              nexmark_bids.instant(t(14)))
    carried = snapshot.state.stats_index
    full = build_index(snapshot.state.add_files_table,
                       metadata=snapshot.metadata)
    n = full.n
    assert carried.n == n and carried.cols == full.cols
    assert carried.unindexed == full.unindexed
    assert np.array_equal(carried.vals[:, :n], full.vals[:, :n])
    assert np.array_equal(carried.valid[:, :n], full.valid[:, :n])
    assert carried.arrow_index._table.equals(full.arrow_index._table)


def test_a_one_long_column_table_builds_the_index_it_built_before(tmp_path):
    from delta_tpu import Table
    from delta_tpu.stats.device_index import build_index

    m = deltastream.generate(str(tmp_path), PARAMS, seed=8)
    snapshot = Table.for_path(m.table_path).latest_snapshot()
    files = snapshot.state.add_files_table
    typed = build_index(files, metadata=snapshot.metadata)
    before = build_index(files)
    assert typed.cols == before.cols == {("x",): (0, "int")}
    assert typed.unindexed == before.unindexed == {}
    assert np.array_equal(typed.vals, before.vals)
    assert np.array_equal(typed.valid, before.valid)
    assert typed.arrow_index._table.equals(before.arrow_index._table)


# ---- the generator ----

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
        nexmark_bids.generate(str(tmp_path / d), PARAMS, seed)
        trees.append(tree(str(tmp_path / d)))
    assert trees[0] == trees[1] and trees[0] != trees[2]


def test_a_batch_is_nexmarks():
    assert (B.events, B.width_us, B.auctions, B.persons) == (
        86_957, 8_695_700, 5_217, 1_739)
    assert nexmark_bids.instant(t(0)) == datetime.datetime(
        2015, 7, 15, tzinfo=UTC)
    assert B.first_auction(2) == 1000 + 2 * 5_217


def test_the_log_is_deltalogs_but_for_stats_and_schema(tmp_path):
    """The stats of commits, checkpoint and staged commits parse to the
    manifest's arrays; all else is `deltalog`'s, line for line."""
    import pyarrow.parquet as pq

    ours = nexmark_bids.generate(str(tmp_path / "s"), PARAMS, seed=9)
    theirs = deltalog.generate(str(tmp_path / "d"), PARAMS, seed=9)
    assert ours.digest() == theirs.digest()
    s = ours.stats

    def stored(text):       # a stats timestamp, microseconds since 1970
        assert text.endswith("Z") and len(text) == 24
        return round(datetime.datetime.fromisoformat(text).timestamp() * 1e6)

    def holds(stats, fid):
        least, most = stats["minValues"], stats["maxValues"]
        assert list(least) == list(most) == list(stats["nullCount"]) == [
            "auction", "bidder", "price", "channel", "url", "dateTime",
            "extra"]
        assert stats["numRecords"] == 1000
        assert set(stats["nullCount"].values()) == {0}
        assert (least["auction"], most["auction"]) == (
            s.auction_min[fid], s.auction_max[fid])
        assert (stored(least["dateTime"]), stored(most["dateTime"])) == (
            s.time_min[fid], s.time_max[fid])
        v = fid // 80
        assert t(v) - 3_001_000 < s.time_min[fid] <= t(v)
        assert t(v + 1) - 10_000 <= s.time_max[fid] < t(v + 1)
        assert max(1000, B.first_auction(v) - 100) <= least["auction"]
        assert most["auction"] < B.first_auction(v + 1)
        assert 100 <= least["price"] < 200 and most["price"] > 5 * 10**7
        assert least["channel"] == "Apple"
        assert most["channel"].startswith("channel-9")
        for key in ("url", "extra"):
            assert len(least[key]) == len(most[key]) == 32
        assert least["url"].startswith("https://www.nexmark.com/")

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
                holds(json.loads(got["add"].pop("stats")),
                      int(got["add"]["path"][5:15]))
                want["add"].pop("stats")
                seen += 1
            assert got == want
    assert seen == 3 * 80
    rows = pq.read_table(os.path.join(
        ours.table_path, "_delta_log", f"{60:020d}.checkpoint.parquet"))
    theirs_rows = pq.read_table(os.path.join(
        theirs.table_path, "_delta_log", f"{60:020d}.checkpoint.parquet"))
    assert rows.schema == theirs_rows.schema
    assert rows.column("add").combine_chunks().field("path").equals(
        theirs_rows.column("add").combine_chunks().field("path"))   # order
    schema = json.loads(rows.column("metaData")[1].as_py()["schemaString"])
    assert [(f["name"], f["type"]) for f in schema["fields"]] == [
        ("auction", "long"), ("bidder", "long"), ("price", "long"),
        ("channel", "string"), ("url", "string"),
        ("dateTime", "timestamp"), ("extra", "string")]
    for add in rows.column("add").to_pylist()[2:40]:
        holds(json.loads(add["stats"]), int(add["path"][5:15]))
    with open(os.path.join(ours.table_path, "_delta_log",
                           deltalog.commit_name(63))) as f:
        size = len(f.read())
    assert 55_000 < size < 70_000       # a commit ~60 KB


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "bid_plan_oracle.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any("delta_tpu" in line for line in imports)
    assert "chipbench.gen" not in source


# ---- the driver's reading of the mix ----

def block_of(seed):
    schedule = traffic.schedule(MIX, seed)
    return [next(schedule) for _ in range(MIX["block"])]


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 2**31 + 18])
def test_a_block_is_workload_e_with_three_selections_in_four(seed):
    block = block_of(seed)
    assert [i for i, p in enumerate(block) if p["refresh"]] == [
        19, 39, 59, 79, 99]
    scans = [p for p in block if not p["refresh"]]
    assert sorted(DRIVER.scan_length(p["length"]) for p in block) == list(
        range(1, 101))
    alone = [p for p in scans if p["selection"] < DRIVER.ALONE_BELOW]
    assert (len(scans), len(alone)) == (95, 24)
    assert all(p["selection"] == 1.0 for p in block if p["refresh"])
    assert all(0 <= p[f"id{k}"] < 1 for p in block for k in range(5))


def test_the_driver_draws_windows_and_auctions_inside_them(tmp_path):
    m = nexmark_bids.generate(str(tmp_path), PARAMS, seed=10)
    driver = DRIVER.Driver(DeltaTpu(), m)
    assert driver.commits.n == 64 + 24
    shapes = collections.Counter()
    for params in block_of(2**31 + 17):
        landed, t0, t1, auctions = driver.prepare(params)
        c, rest = divmod(t0 - t(0), B.width_us)
        length = (t1 - t0) // B.width_us
        assert rest == 0 and 0 <= c <= m.version and 1 <= length <= 100
        assert len(auctions) in (0, 5)
        assert all(B.first_auction(c) <= a < B.first_auction(c + length)
                   for a in auctions)
        shapes[(landed, len(auctions))] += 1
    assert shapes == {(0, 5): 71, (0, 0): 24, (1, 5): 5}
    assert m.version == 63 + 5 and driver.shapes == {
        "refresh", "alone", "selection"}
    # the sibling's Zipfian, scramble and scan length, not copies of them
    sibling = module("drivers", "scan_under_ingest")
    assert DRIVER.ScrambledZipfian.__module__ == (
        "chipbench.drivers.scan_under_ingest")
    assert DRIVER.scan_length(0.5) == sibling.scan_length(0.5)


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == "nexmark-bids-4m-stream"
    assert cell.entry["chips"] == 1 and cell.mix["driver"] == (
        "bid_windows_under_ingest")
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert mine == {"bids_plan_ms", "bids_refresh_ms",
                    "bids_index_rebuild_ms", "bids_live_filter_ms",
                    "bids_index_upload_mb", "bids_host_conjuncts_pct",
                    "bids_skip_roofline"}
    for name in mine:
        assert cell.module("layers", name).read
    assert {m["name"] for m in cell.metrics_of("end_to_end")} == {
        "op_p50_ms", "ops_per_s", "setup_s"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL     # appended, last
    assert bench["configs"][-1]["name"] == "nexmark-bids-4m-stream"
    assert [m["name"] for m in bench["per_layer"][-7:]] == [
        "bids_plan_ms", "bids_refresh_ms", "bids_index_rebuild_ms",
        "bids_live_filter_ms", "bids_index_upload_mb",
        "bids_host_conjuncts_pct", "bids_skip_roofline"]


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "nexmark-bids-4m-stream.json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    assert list(config["reduced"]) == ["commits"]
    assert len(config["guarantees"]) == 3
    assert "millisecond" in config["guarantees"][2]
    assert {"nexmark", "micro_batch", "late_events", "stats_form", "layout",
            "record_is_a_micro_batch", "route", "client", "storage",
            "checkpoint_writer", "log_cleanup", "allocator"} <= set(
                config["assumed"])
    for name in ("firstEventRate", "probDelayedEvent", "occasionalDelaySec",
                 "numInFlightAuctions", "numActivePeople", "avgBidByteSize",
                 "BID_PROPORTION", "FIRST_AUCTION_ID", "FIRST_PERSON_ID"):
        assert name in config["assumed"]["nexmark"], name
    same = dict(config["generator"], kind="deltastream")
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-stream.json")) as f:
        sibling = json.load(f)
    assert same == sibling["generator"]     # the log's shape is the sibling's
    assert config["environment"] == sibling["environment"]
    assert MIX["fixture"] == {"staged_commits": 2000}


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5):
    return harness.run_cell("tiny-bids-under-ingest", seed, seconds, trace,
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
    assert "after the refresh to version" in out and "process RSS" in out


def test_a_traced_run_reads_the_cells_metrics(monkeypatch):
    # the kernel's route, so that the plans' records and spans are the
    # chip's; no device plane here, so its roofline has nothing to read.
    # (2,420 files and 60 more a landing stay in one bucket of 4,096
    # padded rows: a second bucket would compile inside the window.)
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    result = run(trace=True, seconds=0.6)
    assert result["correct"]
    assert set(result["metrics"]) == {
        "bids_plan_ms", "bids_refresh_ms", "bids_index_rebuild_ms",
        "bids_live_filter_ms", "bids_index_upload_mb",
        "bids_host_conjuncts_pct"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["bids_refresh_ms"] > m["bids_index_rebuild_ms"] > 0
    assert m["bids_refresh_ms"] > m["bids_live_filter_ms"] > 0
    assert m["bids_refresh_ms"] > m["bids_plan_ms"] > 0
    assert m["bids_host_conjuncts_pct"] == 0    # event time is on the lanes
    # 13 lanes of 4,096 padded rows and their validity words, a refresh
    assert m["bids_index_upload_mb"] == pytest.approx(
        13 * 4096 * (8 + 1 / 8) / 1e6)


class PlansByCommitOrder(DeltaTpu):
    """Takes a window for the files of the batches it names, as a table
    without late data would allow: omits the late reach."""

    def plan_bids(self, snapshot, t0, t1, auctions=()):
        def us(instant):
            return round(instant.timestamp() * 1e6)

        first, last = ((us(x) - t(0)) // B.width_us for x in (t0, t1))
        return [p for p in bid_queries.plan_bids(snapshot, t0, t1, auctions)
                if first <= int(p[5:15]) // 80 < last]


class NoMillisecond(DeltaTpu):
    """Reads a stored max as exact: drops the file whose last events
    fell inside the millisecond its writer truncated away."""

    def plan_bids(self, snapshot, t0, t1, auctions=()):
        later = t0 + datetime.timedelta(milliseconds=1)
        return bid_queries.plan_bids(snapshot, later, t1, auctions)


class TimeAsText(DeltaTpu):
    """The program before this deployment: event time is text to it, so
    a window skips nothing."""

    def plan_bids(self, snapshot, t0, t1, auctions=()):
        from delta_tpu.expressions import col

        if not auctions:
            return snapshot.scan().file_paths()
        return snapshot.scan(
            filter=col("auction").is_in(*auctions)).file_paths()


class KeepsMore(DeltaTpu):
    """A plan one micro-batch too wide: no file is lost, some are extra."""

    def plan_bids(self, snapshot, t0, t1, auctions=()):
        wider = t1 + datetime.timedelta(microseconds=B.width_us)
        return bid_queries.plan_bids(snapshot, t0, wider, auctions)


CONTROLS = [PlansByCommitOrder, NoMillisecond, TimeAsText, KeepsMore]


@pytest.mark.parametrize("system", CONTROLS)
def test_a_broken_guarantee_is_not_correct(system, capsys):
    result = run(system=system())
    assert result["correct"] is False
    assert "first mismatch: got" in capsys.readouterr().out


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


DEVICE = dict(skip_route="device", skip_fallback_conjuncts=0, uncompared=0)
# plans at 0, 100 and 200 ms; refreshes at 1,000 and 2,000 ms
OPS = [op("plan", 0, 50), op("plan", 100, 130), op("plan", 200, 290),
       op("refresh", 1000, 1900), op("refresh", 2000, 2700)]
RECORDED = [
    span("scan.plan", 1, 40), span("plan.skip", 2, 10, **DEVICE),
    span("skip.wait", 3, 5, rows_read=7),
    span("scan.plan", 101, 20), span("plan.skip", 102, 10, **DEVICE),
    span("skip.wait", 103, 5, rows_read=4),
    span("scan.plan", 201, 80), span("plan.skip", 202, 10, **DEVICE),
    span("skip.wait", 203, 5, rows_read=7),
    span("table.update", 1000, 300), span("state.filter_live", 1100, 150),
    span("scan.plan", 1300, 590), span("plan.skip", 1301, 580, **DEVICE),
    span("stats.index_build", 1310, 400),
    span("stats.index_upload", 1720, 100),
    span("skip.wait", 1850, 20, rows_read=7),
    span("table.update", 2000, 200), span("state.filter_live", 2050, 110),
    span("scan.plan", 2200, 490), span("plan.skip", 2201, 480, **DEVICE),
    span("stats.index_build", 2210, 300),
    span("stats.index_upload", 2520, 100),
    span("skip.wait", 2650, 20, rows_read=7),
    span("scan.plan", 5000, 7),     # outside every operation
]
N_PAD = 2_621_440
UPLOAD = {"kernel": "stats.index_upload", "h2d_bytes": 13 * N_PAD * 8}
LAUNCH = {"kernel": "skipping.mask_block", "h2d_bytes": 0,
          "attrs": {"lanes": 13, "n_pad": N_PAD}}
DISPATCHES = [LAUNCH] * 3 + [UPLOAD, LAUNCH, UPLOAD, LAUNCH]
EVENTS = [("jit_skipping_mask_block/fusion.1", 0, 3_000_000),
          ("jit_skipping_mask_block/fusion.2", 2_000_000, 4_000_000),
          ("jit_stats_index_upload/fusion", 0, 9_000_000)]


def recorded(spans=RECORDED, dispatches=DISPATCHES, events=EVENTS):
    trace = types.SimpleNamespace(events=[list(events)] if events else [])
    return types.SimpleNamespace(ops=OPS, spans=spans, trace=trace,
                                 dispatches=list(dispatches),
                                 device_kind="TPU v5 lite")


def least_s(rows_read):
    return (rows_read * N_PAD * 9 + N_PAD) / 819e9


@pytest.mark.parametrize("name,want", [
    ("bids_plan_ms", 40),                       # of 40, 20, 80: no refresh's
    ("bids_refresh_ms", (890 + 690) / 2),       # update + the plan after it
    ("bids_index_rebuild_ms", (500 + 400) / 2),  # build + upload
    ("bids_live_filter_ms", (150 + 110) / 2),
    ("bids_index_upload_mb", 13 * N_PAD * 8 / 1e6),     # a refresh
    ("bids_host_conjuncts_pct", 0),
    # four launches read 7 rows and one 4, in 4 ms of device time
    ("bids_skip_roofline", 100 * (4 * least_s(7) + least_s(4)) / 4e-3),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_the_roofline_charges_the_rows_a_launch_reads_not_the_index():
    mine = module("layers", "bid_skip_mask_bytes").bid_skip_mask_bytes
    theirs = module("layers", "skip_mask_bytes").skip_mask_bytes
    assert mine(7, N_PAD) == 7 * N_PAD * 9 + N_PAD
    assert mine(4, N_PAD) == theirs(4, N_PAD)   # the sibling's whole index
    assert mine(7, N_PAD) < theirs(13, N_PAD)
    assert 0 < reader("bids_skip_roofline")(recorded()) < 100


def without(spans, *names, drop_attr=None):
    out = [s for s in spans if s["name"] not in names]
    if drop_attr:
        out = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                              if k != drop_attr}) for s in out]
    return out


def plan_skips(**attrs):
    return [span("plan.skip", 2, 10, **dict(DEVICE, **attrs)),
            span("plan.skip", 102, 10, **DEVICE),
            span("plan.skip", 202, 10, **DEVICE),
            span("plan.skip", 302, 10, **DEVICE)]


@pytest.mark.parametrize("name,spans,dispatches,want", [
    # the parent: no rows_read on skip.wait, no uncompared on plan.skip,
    # and a window's plan compiles to nothing, so it names no route
    ("bids_skip_roofline", without(RECORDED, drop_attr="rows_read"),
     DISPATCHES, None),
    ("bids_skip_roofline", RECORDED, [UPLOAD], None),   # no plan on the chip
    ("bids_skip_roofline", without(RECORDED, "skip.wait"), DISPATCHES, None),
    ("bids_host_conjuncts_pct", plan_skips(skip_fallback_conjuncts=2),
     [], 25),
    ("bids_host_conjuncts_pct", plan_skips(uncompared=1), [], 25),
    ("bids_host_conjuncts_pct", plan_skips(skip_route="host"), [], 25),
    ("bids_host_conjuncts_pct",
     [span("plan.skip", 2, 10, rows=5, conjuncts=2)], [], 100),
    ("bids_host_conjuncts_pct", without(RECORDED, drop_attr="uncompared"),
     [], 0),
    ("bids_host_conjuncts_pct", [], [], None),
    ("bids_index_upload_mb", RECORDED, [LAUNCH], None),
    ("bids_live_filter_ms", without(RECORDED, "state.filter_live"), [], None),
    ("bids_plan_ms", [], [], None), ("bids_refresh_ms", [], [], None),
    ("bids_index_rebuild_ms", [], [], None),
])
def test_a_reader_on_a_program_without_its_spans(name, spans, dispatches,
                                                 want):
    got = reader(name)(recorded(spans, dispatches))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_roofline_finds_nothing_without_a_device_plane():
    assert reader("bids_skip_roofline")(recorded(events=())) is None
