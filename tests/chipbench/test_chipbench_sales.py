"""The deployment `tpcds-store-sales-4m-stream` and its cell
`sales-query-under-ingest`, at a test's size on the CPU (8 rows a file,
and the small files of 1, 2 and 4, so that Query 28's buckets prune
much): the generator and its manifest against the plain reference,
readers of both engines that hold their state through `update()`, and a
cold reader, on every kind of predicate after every landing; the 70-lane
index; the driver's reading of the mix; whole runs; the seven readers;
six broken systems.

`python3 tests/chipbench/test_chipbench_sales.py <broken system> --seed
<n> --seconds <s>` runs the cell itself, at its real size and on the
chip, on one of them: the last line is the harness's result."""

import collections
import decimal
import hashlib
import importlib.util
import json
import os
import threading
import time
import types

import numpy as np
import pytest

from chipbench import harness, sales_queries, traffic
from chipbench.gen import deltalog, tpcds_store_sales
from chipbench.reference import sales_plan_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "sales", "benchmark.json")
CELL = "sales-query-under-ingest"
PARAMS = dict(commits=64, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=24,
              rows_per_file=8)
# a table of decimal(18,2) whose every money value lies where a double
# is a quarter wide
BASE = 1_234_567_890_123_400
WIDE = dict(PARAMS, money_precision=18, money_base=BASE)
LANDINGS = 20
B = tpcds_store_sales.Batch(80, 8)
MS = 1_000_000
D = decimal.Decimal
SALES_METRICS = {"sales_plan_ms", "sales_refresh_ms", "sales_index_rebuild_ms",
                 "sales_index_upload_mb", "sales_host_conjuncts_pct",
                 "sales_decimal_atoms_pct", "sales_skip_roofline"}


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"sales_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "sales_buckets_under_ingest")
with open(os.path.join(ROOT, "chipbench", "mixes",
                       "ycsb-e-sales-buckets.json")) as f:
    MIX = json.load(f)


# ---- manifest = reference = both engines' held readers = a cold reader ----

def a_live_file(m, v):
    return int(np.flatnonzero(m.alive[v * 80:(v + 1) * 80])[0]) + v * 80


def just_past(m, column, fid):
    """The whole number of currency, over the table's `money_base`,
    just under file `fid`'s least `column`: a range that ends there
    misses the file by its cents."""
    least = int(m.stats.values(np.array([fid]))[column][0][0])
    assert least % 100, "the file's least has no cents to miss it by"
    return least // 100 - m.stats.money_base


def on_base(bucket, base):
    """`bucket`'s three amounts as the table has them: over `base`."""
    if bucket is None or not base:
        return bucket
    if isinstance(bucket, list):
        return [on_base(one, base) for one in bucket]
    return bucket[:2] + tuple(base + amount for amount in bucket[2:])


NOWHERE = 10**6     # over any coupon amount and any wholesale cost


def by_the_cents(m):
    """A bucket whose list-price range ends a few cents under one live
    file's least, its other two ranges nowhere: only the cents, 16
    digits down, keep that file out."""
    fid = a_live_file(m, 20)
    q = int(m.stats.values(np.array([fid]))["ss_quantity"][0][0])
    return (B.day(0), B.day(63),
            (q - 4, q, just_past(m, "ss_list_price", fid) - 10,
             NOWHERE, NOWHERE))


# the three amounts of the tests' six buckets: towards the ends of their
# ranges, where at 8 rows a file the OR of the three prunes as well
AMOUNTS = [(190, 18_000, 80), (0, 18_000, 0), (180, 17_000, 80),
           (5, 16_000, 0), (190, 15_000, 75), (100, 18_000, 80)]
SIX = [q + amounts for q, amounts in zip(
    ((0, 5), (6, 10), (11, 15), (16, 20), (21, 25), (26, 30)), AMOUNTS)]

CASES = {   # name: (generator's parameters, manifest -> (lo, hi, bucket))
    "window-alone": (PARAMS, lambda m: (B.day(11), B.day(31), None)),
    "before-all-data": (PARAMS, lambda m: (B.day(0) - 9, B.day(0) - 1, None)),
    "over-what-lands": (PARAMS, lambda m: (B.day(60), B.day(500),
                                           (6, 10, 40, 5000, 30))),
    **{f"bucket-from-{bucket[0]}": (PARAMS, lambda m, bucket=bucket: (
        B.day(5), B.day(50), bucket)) for bucket in SIX},
    # Query 28 asks its six buckets side by side: the OR of them passes
    # the atom limit many times over and is the ladder's
    "six-buckets-wider-than-the-atom-limit": (PARAMS, lambda m: (
        B.day(5), B.day(50), SIX)),
    "decimal-18-2-by-the-cents": (WIDE, by_the_cents),
    "decimal-18-2-bucket": (WIDE, lambda m: (
        B.day(5), B.day(50), (11, 15, 180, 17_000, 80))),
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

    def plan(self, monkeypatch, lo, hi, bucket):
        """`bucket` as the table has it: its amounts on the base."""
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", self.route)
        return sorted(sales_queries.plan_sales(self.snapshot, lo, hi, bucket))


def cold_plan(path, lo, hi, bucket):
    from delta_tpu import Table
    from delta_tpu.replay.columnar import clear_parse_cache

    clear_parse_cache()
    snapshot = Table.for_path(path).latest_snapshot()
    return sorted(sales_queries.plan_sales(snapshot, lo, hi, bucket))


def held_readers(path):
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine

    return {"HostEngine": HeldReader(path, HostEngine(), "off"),
            "TpuEngine-twin": HeldReader(path, TpuEngine(), "off"),
            "TpuEngine-skip-kernel": HeldReader(path, TpuEngine(), "force")}


def paths(ids):
    return [deltalog.path_of(int(i)) for i in ids]


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_reader_finds_the_manifests_files_after_every_landing(
        tmp_path, monkeypatch, case):
    from delta_tpu import obs

    params, window = CASES[case]
    m = tpcds_store_sales.generate(str(tmp_path), params, seed=2**31 + 11)
    readers = held_readers(m.table_path)
    too_wide = obs.counter("scan.skip_disjunctions_too_wide")
    distributed = obs.counter("scan.skip_disjunctions_distributed")
    unindexed = obs.counter("scan.stats_index_unindexed_leaves.decimal")
    before = too_wide.value, distributed.value, unindexed.value
    seen = set()
    for landed in range(LANDINGS + 1):
        if landed:
            m.land(1)
            for reader in readers.values():
                reader.refresh()
        lo, hi, bucket = window(m)
        want = paths(m.scan_expected(lo, hi, bucket))
        seen.add(len(want))
        asked = on_base(bucket, m.stats.money_base)
        assert sales_plan_oracle.plan(m.table_path, lo, hi, asked) == want, \
            landed
        for name, reader in readers.items():
            assert reader.snapshot.version == m.version
            assert reader.plan(monkeypatch, lo, hi, asked) == want, (
                name, landed)
        if landed % 5 == 0:
            monkeypatch.delenv("DELTA_TPU_DEVICE_SKIP")
            assert cold_plan(m.table_path, lo, hi, asked) == want, landed
    live = int(m.alive.sum())
    if case == "before-all-data":
        assert seen == {0}
    elif case == "over-what-lands":
        assert len(seen) > 10       # the answer grows with the table
    else:
        assert 0 not in seen and max(seen) < live
    # which way the plans went: every money column has its lane, a
    # bucket's OR is distributed, the six side by side are the ladder's
    plans = 3 * (LANDINGS + 1) + 5
    assert unindexed.value == before[2]
    if isinstance(window(m)[2], list):
        assert too_wide.value == before[0] + plans
    elif window(m)[2] is not None:
        assert too_wide.value == before[0]
        assert distributed.value == before[1] + plans


def test_a_bucket_prunes_and_the_cents_decide_one_files_fate(tmp_path):
    m = tpcds_store_sales.generate(str(tmp_path), WIDE, seed=3)
    lo, hi, (q_lo, q_hi, p, c, w) = by_the_cents(m)
    fid = a_live_file(m, 20)
    window = m.scan_expected(lo, hi)
    assert fid in window
    assert fid not in m.scan_expected(lo, hi, (q_lo, q_hi, p, c, w))
    assert fid in m.scan_expected(lo, hi, (q_lo, q_hi, p + 1, c, w))
    # the reference reads the digits: a range that ends on the file's
    # own least takes it, a cent under misses it
    stats = json.loads(m.stats.strings(np.array([fid]))[0].as_py(),
                       parse_float=D)
    least = stats["minValues"]["ss_list_price"]
    assert isinstance(least, D) and float(least) != least
    c, w = BASE + c, BASE + w
    assert sales_plan_oracle.admits(stats, lo, hi,
                                    (q_lo, q_hi, least - 10, c, w))
    assert not sales_plan_oracle.admits(
        stats, lo, hi, (q_lo, q_hi, least - 10 - D("0.01"), c, w))
    # and at 8 rows a file every part of a bucket prunes some files
    m = tpcds_store_sales.generate(str(tmp_path / "narrow"), PARAMS, seed=3)
    lo, hi, bucket = CASES["bucket-from-11"][1](m)
    window, kept = m.scan_expected(lo, hi), m.scan_expected(lo, hi, bucket)
    assert 0 < len(kept) < len(window) < m.alive.sum()
    found = m.stats.values(window)
    by_quantity = ((found["ss_quantity"][1] >= bucket[0])
                   & (found["ss_quantity"][0] <= bucket[1]))
    assert len(kept) < by_quantity.sum() < len(window)


# ---- the 70-lane index ----

def test_the_index_has_a_lane_for_every_column_twelve_of_them_decimal(
        tmp_path):
    from delta_tpu import Table, obs
    from delta_tpu.stats.device_index import build_index

    m = tpcds_store_sales.generate(str(tmp_path), PARAMS, seed=7)
    table = Table.for_path(m.table_path)
    snapshot = table.latest_snapshot()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        sales_queries.plan_sales(snapshot, B.day(11), B.day(31),
                                 (6, 10, 40, 5000, 30))
        spans = {s.name: s.to_dict()["attrs"]
                 for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode("off")
    assert spans["stats.index_build"]["lane_kinds"] == "int:11,decimal:12"
    assert spans["stats.index_build"]["lanes"] == 70
    assert spans["stats.index_build"]["unindexed"] == 0
    skip = spans["plan.skip"]
    assert (skip["atoms"], skip["groups"], skip["decimal_atoms"],
            skip["distributed"]) == (28, 12, 24, 1)
    assert skip["skip_fallback_conjuncts"] == 0 and skip["uncompared"] == 0
    idx = snapshot.state.stats_index
    assert idx.vals.shape[0] == 70 and idx.unindexed == {}
    kinds = {path[0]: kind for path, (_, kind) in idx.cols.items()}
    assert kinds == {name: "decimal:2" if "decimal" in kind else "int"
                     for name, kind in tpcds_store_sales.COLUMNS}
    # the lanes hold the manifest's numbers, money as cents
    files = snapshot.state.add_files_table
    ids = np.array([int(p[5:15]) for p in files.column("path").to_pylist()])
    found, n = m.stats.values(ids), len(ids)
    for name in ("ss_sold_date_sk", "ss_quantity", "ss_list_price",
                 "ss_net_profit"):
        row = idx.cols[(name,)][0]
        for k in range(3):
            assert np.array_equal(idx.vals[row + k, :n], found[name][k]), name
    assert (found["ss_net_profit"][0] < 0).any()
    # carried over landings, it equals one built from every stats string
    for _ in range(3):
        m.land(1)
        snapshot = table.update()
        sales_queries.plan_sales(snapshot, B.day(11), B.day(31))
    carried = snapshot.state.stats_index
    full = build_index(snapshot.state.add_files_table,
                       metadata=snapshot.metadata)
    n = full.n
    assert carried.n == n and carried.cols == full.cols
    assert np.array_equal(carried.vals[:, :n], full.vals[:, :n])
    assert np.array_equal(carried.valid[:, :n], full.valid[:, :n])
    assert carried.arrow_index._table.equals(full.arrow_index._table)


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
        tpcds_store_sales.generate(str(tmp_path / d), PARAMS, seed)
        trees.append(tree(str(tmp_path / d)))
    assert trees[0] == trees[1] and trees[0] != trees[2]


def test_a_batch_is_a_twentieth_of_a_sold_date():
    full = tpcds_store_sales.Batch(80, 1000)
    assert (full.batch_rows, full.rows_a_day) == (80_000, 1_579_806)
    assert full.day(0) == 2_450_816 and full.day(19) == full.day(0)
    assert full.day(20) == full.day(0) + 1
    assert (full.day(19), full.last_day(19)) == (2_450_816, 2_450_817)
    assert full.day(40_000) - full.day(0) == 2_025
    # a smaller file keeps the ratio of a batch to a day
    assert 19.7 < B.rows_a_day / B.batch_rows < 19.8 > (
        full.rows_a_day / full.batch_rows) > 19.7
    for lo, hi in ((2_450_816, 2_450_816), (2_450_820, 2_450_825)):
        first, last = full.batches_of(lo, hi)
        inside = [v for v in range(0, 400)
                  if full.last_day(v) >= lo and full.day(v) <= hi]
        assert first <= inside[0] and inside[-1] < last
        assert last - first <= len(inside) + 4


def test_the_log_is_deltalogs_but_for_stats_and_schema(tmp_path):
    """The stats of commits, checkpoint and staged commits parse to the
    manifest's numbers; all else is `deltalog`'s, line for line."""
    import pyarrow.parquet as pq

    ours = tpcds_store_sales.generate(str(tmp_path / "s"), PARAMS, seed=9)
    theirs = deltalog.generate(str(tmp_path / "d"), PARAMS, seed=9)
    assert ours.digest() == theirs.digest()
    names = [name for name, _ in tpcds_store_sales.COLUMNS]

    def holds(text, fid):
        assert ": " not in text and ", " not in text    # a writer's compact
        stats = json.loads(text, parse_float=D)
        assert list(stats) == ["numRecords", "minValues", "maxValues",
                               "nullCount"]
        found = ours.stats.values(np.array([fid]))
        rows = stats["numRecords"]
        assert rows == found["numRecords"][0] and rows in (1, 2, 4, 8)
        sizes[rows] += 1
        for k, group in enumerate(("minValues", "maxValues", "nullCount")):
            assert list(stats[group]) == names
            for name, kind in tpcds_store_sales.COLUMNS:
                got = stats[group][name]
                if "decimal" in kind and k < 2:     # two places, always
                    assert isinstance(got, D), (name, got)
                    assert got.as_tuple().exponent == -2
                    got = int(got * 100)
                assert got == found[name][k][0], (group, name)
        least, most, nulls = (stats[g] for g in ("minValues", "maxValues",
                                                 "nullCount"))
        v = fid // 80
        if rows == 8:
            assert (least["ss_sold_date_sk"], most["ss_sold_date_sk"]) == (
                B.day(v), B.last_day(v))
        assert B.day(v) <= least["ss_sold_date_sk"] \
            <= most["ss_sold_date_sk"] <= B.last_day(v)
        assert nulls["ss_item_sk"] == nulls["ss_ticket_number"] == 0
        assert all(0 <= n <= max(rows - 1, 0) for n in nulls.values())
        assert all(least[name] <= most[name] for name in names)
        if rows == 1:       # one row: every least is its most
            assert least == most
            assert least["ss_ext_list_price"] == (
                least["ss_list_price"] * least["ss_quantity"])
        assert 1 <= least["ss_quantity"] <= most["ss_quantity"] <= 100
        assert 1 <= least["ss_wholesale_cost"] <= most["ss_wholesale_cost"] \
            <= 100
        assert most["ss_list_price"] <= 300 and least["ss_coupon_amt"] >= 0

    def lines(root, where, name):
        with open(os.path.join(root, where, name)) as f:
            return [json.loads(line) for line in f]

    seen, sizes = 0, collections.Counter()
    for where, v in (("table/_delta_log", 50), ("table/_delta_log", 63),
                     ("staged", 70)):
        name = deltalog.commit_name(v)
        mine = lines(str(tmp_path / "s"), where, name)
        for got, want in zip(mine, lines(str(tmp_path / "d"), where, name)):
            if "add" in got:
                holds(got["add"].pop("stats"), int(got["add"]["path"][5:15]))
                want["add"].pop("stats")
                seen += 1
            assert got == want
    assert seen == 3 * 80
    name = os.path.join("_delta_log", f"{60:020d}.checkpoint.parquet")
    rows = pq.read_table(os.path.join(ours.table_path, name))
    theirs_rows = pq.read_table(os.path.join(theirs.table_path, name))
    assert rows.schema == theirs_rows.schema
    assert rows.column("add").combine_chunks().field("path").equals(
        theirs_rows.column("add").combine_chunks().field("path"))   # order
    schema = json.loads(rows.column("metaData")[1].as_py()["schemaString"])
    assert [(f["name"], f["type"]) for f in schema["fields"]] == list(
        tpcds_store_sales.COLUMNS)
    assert [f["name"] for f in schema["fields"] if not f["nullable"]] == [
        "ss_item_sk", "ss_ticket_number"]
    for add in rows.column("add").to_pylist()[2:40]:
        holds(add["stats"], int(add["path"][5:15]))
    # one file in eight is small: half of those under the tests' 8 rows
    assert {1, 2, 4, 8} == set(sizes)
    assert 0.03 < (sizes[1] + sizes[2] + sizes[4]) / sum(sizes.values()) < 0.1


def test_one_file_in_eight_is_small_at_the_real_size():
    """And those are the files a bucket rules out: of the window's files
    times the buckets asked, the share PERF.md states."""
    stats = tpcds_store_sales.FileStats(80, 1000, seed=2**31 + 4242)
    ids = np.arange(80 * 500)
    found = stats.values(ids)
    rows = found["numRecords"]
    assert set(np.unique(rows)) == {1, 2, 4, 8, 16, 32, 1000}
    assert 0.115 < (rows < 1000).mean() < 0.135
    assert np.array_equal(rows, stats.rows_of(ids))
    assert np.array_equal(stats.values(ids[777:999])["ss_list_price"][0],
                          found["ss_list_price"][0][777:999])
    one = rows == 1
    for name, _ in tpcds_store_sales.COLUMNS:
        least, most, nulls = found[name]
        assert (least <= most).all() and (nulls[one] == 0).all(), name
        assert np.array_equal(least[one], most[one]), name
        assert (nulls <= np.maximum(rows - 1, 0)).all()
    # a full file spans every range a bucket asks; a small one does not
    pruned = pairs = 0
    for block in (block_of(1), block_of(2**31 + 17)):
        for params in block:
            bucket = DRIVER.bucket_of(params)
            if bucket is None:
                continue
            q_lo, q_hi, p, c, w = bucket

            def between(name, lo, hi, unit=1):
                return ((found[name][1] >= lo * unit)
                        & (found[name][0] <= hi * unit))

            keep = between("ss_quantity", q_lo, q_hi) & (
                between("ss_list_price", p, p + 10, 100)
                | between("ss_coupon_amt", c, c + 1000, 100)
                | between("ss_wholesale_cost", w, w + 20, 100))
            assert keep[rows == 1000].all() and not keep.all()
            pruned, pairs = pruned + (~keep).sum(), pairs + len(keep)
    assert 0.04 < pruned / pairs < 0.08


def test_the_checkpoint_is_written_in_row_groups_of_a_block(tmp_path,
                                                            monkeypatch):
    """So that no chunk of stats strings passes 2 GiB at 2.4M files."""
    import pyarrow.parquet as pq

    monkeypatch.setattr(tpcds_store_sales, "CHECKPOINT_BLOCK", 1000)
    m = tpcds_store_sales.generate(str(tmp_path), PARAMS, seed=9)
    meta = pq.ParquetFile(os.path.join(
        m.table_path, "_delta_log",
        f"{60:020d}.checkpoint.parquet")).metadata
    groups = [meta.row_group(i).num_rows for i in range(meta.num_row_groups)]
    assert len(groups) >= 3 and max(groups) == 1000
    with open(os.path.join(m.table_path, "_delta_log",
                           "_last_checkpoint")) as f:
        assert sum(groups) == json.load(f)["size"] > 3000
    from delta_tpu import Table

    snapshot = Table.for_path(m.table_path).latest_snapshot()
    assert snapshot.num_files == m.num_files()


def test_a_money_value_is_written_with_two_places_and_its_sign():
    cents = np.array([1801, 5, 0, -35, -829635, 100, 123456789012345678])
    pieces = tpcds_store_sales._money(cents)
    texts = ["".join(p[i].as_py() for p in pieces) for i in range(len(cents))]
    assert texts == ["18.01", "0.05", "0.00", "-0.35", "-8296.35", "1.00",
                     "1234567890123456.78"]
    assert [D(t) * 100 for t in texts] == [D(int(c)) for c in cents]


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "sales_plan_oracle.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any("delta_tpu" in line for line in imports)
    assert "chipbench.gen" not in source and "parse_float=decimal" in source


# ---- the driver's reading of the mix ----

def block_of(seed):
    schedule = traffic.schedule(MIX, seed)
    return [next(schedule) for _ in range(MIX["block"])]


@pytest.mark.parametrize("seed", [1, 2**31 + 17, 2**31 + 18])
def test_a_block_is_workload_e_with_three_buckets_in_four(seed):
    block = block_of(seed)
    assert [i for i, p in enumerate(block) if p["refresh"]] == [
        19, 39, 59, 79, 99]
    assert sorted(DRIVER.scan_length(p["length"]) for p in block) == list(
        range(1, 101))
    buckets = [DRIVER.bucket_of(p) for p in block]
    scans = [b for b, p in zip(buckets, block) if not p["refresh"]]
    assert (len(scans), scans.count(None)) == (95, 24)     # 71 / 24 / 5
    assert None not in [b for b, p in zip(buckets, block) if p["refresh"]]
    drawn = [b for b in buckets if b is not None]
    by_q = collections.Counter(b[0] for b in drawn)
    assert set(by_q) == {lo for lo, _ in DRIVER.QUANTITIES} == {
        0, 6, 11, 16, 21, 26}
    assert {b[:2] for b in drawn} == set(DRIVER.QUANTITIES) == {
        (0, 5), (6, 10), (11, 15), (16, 20), (21, 25), (26, 30)}
    assert max(by_q.values()) - min(by_q.values()) <= 3     # equal shares
    for k, top in ((2, 190), (3, 18_000), (4, 80)):
        values = [b[k] for b in drawn]
        assert 0 <= min(values) and max(values) <= top
        assert max(values) > 0.9 * top and min(values) < 0.1 * top
        assert all(isinstance(v, int) for v in values)


def test_the_driver_draws_sold_dates_and_buckets(tmp_path):
    m = tpcds_store_sales.generate(str(tmp_path), PARAMS, seed=10)
    driver = DRIVER.Driver(DeltaTpu(), m)
    assert driver.commits.n == 64 + 24
    shapes = collections.Counter()
    for params in block_of(2**31 + 17):
        landed, lo, hi, bucket = driver.prepare(params)
        assert B.day(0) <= lo <= B.day(m.version)
        assert lo <= hi <= lo + B.day(100) - B.day(0) + 1
        shapes[(landed, bucket is not None)] += 1
    assert shapes == {(0, True): 71, (0, False): 24, (1, True): 5}
    assert m.version == 63 + 5 and driver.shapes == {
        "refresh", "alone", "selection"}
    # the siblings' Zipfian, scramble, scan length and warm-up, no copies
    assert DRIVER.ScrambledZipfian.__module__ == (
        "chipbench.drivers.scan_under_ingest")
    assert DRIVER.Driver.warm_up is DRIVER.bids.Driver.warm_up
    # the driver knows no base: on the tests' table of decimal(18,2) the
    # manifest and the system asked (`OnItsBase`) stand the amounts on it
    wide = tpcds_store_sales.generate(str(tmp_path / "w"), WIDE, seed=10)
    bucket = DRIVER.Driver(DeltaTpu(), wide).prepare(
        {**block_of(1)[0], "refresh": 0, "bucket": 0.9})[3]
    assert all(0 <= amount <= 18_000 for amount in bucket[2:])


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == "tpcds-store-sales-4m-stream"
    assert cell.entry["chips"] == 1 and cell.mix["driver"] == (
        "sales_buckets_under_ingest")
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    # a lower bound, and no place in the file: a later PR may add to the
    # cell's metrics, and to the file before or behind its entries
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert SALES_METRICS <= mine
    for name in SALES_METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpcds-store-sales-4m-stream")
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == ["commits"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["sales_skip_roofline"]["source"] == "device_trace"
    assert by_name["sales_index_upload_mb"]["source"] == "program_counter"
    assert {by_name[n]["moves"] for n in SALES_METRICS} == {
        "op_p50_ms", "ops_per_s"}


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tpcds-store-sales-4m-stream.json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    assert list(config["reduced"]) == ["commits"]
    assert len(config["guarantees"]) == 3
    assert "decimals compared exactly" in config["guarantees"][2]
    assert {"tpcds", "pricing", "query28", "micro_batch", "layout",
            "stats_form", "small_files", "what_a_bucket_prunes",
            "record_is_a_micro_batch",
            "route", "client", "storage", "checkpoint_writer", "log_cleanup",
            "allocator"} <= set(config["assumed"])
    for name, kind in tpcds_store_sales.COLUMNS:     # all 23, by name
        assert name in config["schema"], name
    assert config["schema"].count("decimal(7,2)") == 1
    assert len(tpcds_store_sales.MONEY) == 12
    assert len(tpcds_store_sales.COLUMNS) == 23
    same = dict(config["generator"], kind="deltastream")
    assert same.pop("rows_per_file") == 1000
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-stream.json")) as f:
        sibling = json.load(f)
    assert same == sibling["generator"]     # the log's shape is the sibling's
    assert config["environment"] == sibling["environment"]
    assert MIX["fixture"] == {"staged_commits": 2000}


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5,
        cell="tiny-sales-under-ingest"):
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            bench_path=TINY, require_chip=False,
                            system=system)


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
    assert set(result["metrics"]) == SALES_METRICS - {"sales_skip_roofline"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["sales_refresh_ms"] > m["sales_index_rebuild_ms"] > 0
    assert m["sales_refresh_ms"] > m["sales_plan_ms"] > 0
    # the money columns are on the lanes and the OR is distributed
    assert m["sales_host_conjuncts_pct"] == 0
    # 71 plans of 28 atoms (24 on money) and 24 of 2, were the window whole
    assert 75 < m["sales_decimal_atoms_pct"] <= 100 * 24 / 28
    # 70 lanes of 4,096 padded rows and their validity words, a refresh
    assert m["sales_index_upload_mb"] == pytest.approx(
        70 * 4096 * (8 + 1 / 8) / 1e6)


class OnItsBase(DeltaTpu):
    """The program, asked on the tests' table of decimal(18,2): a
    bucket's three amounts stand on the table's base."""

    def __init__(self, base=0):
        self.base = base

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        return sales_queries.plan_sales(snapshot, lo, hi,
                                        on_base(bucket, self.base))


class ByTheStatsAsRead(OnItsBase):
    """Plans the window with the program and the bucket by the plain
    reference's rule over the window's stats, read by `json.loads` with
    this system's `parse_float`."""

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        files = snapshot.scan(
            filter=sales_queries.window_predicate(lo, hi)).add_files_table()
        bucket = on_base(bucket, self.base)
        return [path for path, text in zip(files.column("path").to_pylist(),
                                           files.column("stats").to_pylist())
                if sales_plan_oracle.admits(
                    json.loads(text, parse_float=self.parse_float),
                    lo, hi, bucket)]


class DecimalAsDouble(ByTheStatsAsRead):
    """The program before this deployment, where it did compare a money
    column: through doubles, as `json.loads` reads a number with a
    point. Wrong from 16 digits on; at `decimal(7,2)` under whole-number
    literals a double orders every value as its digits do, so there it
    is right, and reads so (`REAL_SIZE`)."""

    parse_float = float


class CutsTheCents(ByTheStatsAsRead):
    """A money lane without its scale: the stat's whole units alone, as
    an `int` lane would hold a decimal column. Keeps the file whose
    least lies cents past the range, omits none."""

    @staticmethod
    def parse_float(text):
        return int(text.partition(".")[0])


class CompilesTheBucketToNothing(DeltaTpu):
    """Plans the window and leaves the bucket out: what a plan costs
    least."""

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        return sales_queries.plan_sales(snapshot, lo, hi)


class DropsTheDisjunction(DeltaTpu):
    """Leaves the OR over the three ranges out, as a compiler that
    cannot take an AND under an OR: keeps more."""

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        from delta_tpu.expressions import col, lit

        pred = sales_queries.window_predicate(lo, hi)
        if bucket is not None:
            pred = pred & (col("ss_quantity") >= lit(bucket[0])) & (
                col("ss_quantity") <= lit(bucket[1]))
        return snapshot.scan(filter=pred).file_paths()


class OrOfTheEnds(DeltaTpu):
    """Distributes the wrong way round: for the OR of three ranges, the
    OR of their two ends, each the AND of its three bounds. Omits a
    file that one range admits and another's bound rules out."""

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        from delta_tpu.expressions import col, lit

        pred = sales_queries.window_predicate(lo, hi)
        if bucket is not None:
            q_lo, q_hi, p, c, w = bucket
            lp, ca, wc = (col(n) for n in ("ss_list_price", "ss_coupon_amt",
                                           "ss_wholesale_cost"))
            pred = pred & (col("ss_quantity") >= lit(q_lo)) & (
                col("ss_quantity") <= lit(q_hi)) & (
                ((lp >= lit(p)) & (ca >= lit(c)) & (wc >= lit(w)))
                | ((lp <= lit(p + 10)) & (ca <= lit(c + 1000))
                   & (wc <= lit(w + 20))))
        return snapshot.scan(filter=pred).file_paths()


class TruncatesTheLiteral(DeltaTpu):
    """Asks `ss_quantity < q_hi + 0.5`, which over whole quantities is
    the bucket's `<= q_hi`, and cuts the literal to the column's type as
    a cast would: `< q_hi` omits the file whose least quantity is
    `q_hi`."""

    def plan_sales(self, snapshot, lo, hi, bucket=None):
        from delta_tpu.expressions import col, lit

        if bucket is None:
            return sales_queries.plan_sales(snapshot, lo, hi)
        asked = D(bucket[1]) + D("0.5")
        pred = sales_queries.window_predicate(lo, hi) \
            & sales_queries.bucket_predicate(*bucket) \
            & (col("ss_quantity") < lit(int(asked)))
        return snapshot.scan(filter=pred).file_paths()


WIDE_CELL = "tiny-sales-18-under-ingest"
CONTROLS = [(lambda: DecimalAsDouble(BASE), WIDE_CELL),
            (lambda: CutsTheCents(BASE), WIDE_CELL),
            (CutsTheCents, "tiny-sales-under-ingest"),
            (CompilesTheBucketToNothing, "tiny-sales-under-ingest"),
            (DropsTheDisjunction, "tiny-sales-under-ingest"),
            (OrOfTheEnds, "tiny-sales-under-ingest"),
            (TruncatesTheLiteral, "tiny-sales-under-ingest")]
# at the cell's own size, on the chip (this file run as a program): what
# each has to read there
REAL_SIZE = {"DecimalAsDouble": True, "CutsTheCents": False,
             "CompilesTheBucketToNothing": False,
             "DropsTheDisjunction": False, "OrOfTheEnds": False,
             "TruncatesTheLiteral": False, "OnItsBase": True}


@pytest.mark.parametrize("system,cell", CONTROLS, ids=[
    "DecimalAsDouble-18", "CutsTheCents-18", "CutsTheCents",
    "CompilesTheBucketToNothing", "DropsTheDisjunction", "OrOfTheEnds",
    "TruncatesTheLiteral"])
def test_a_broken_guarantee_is_not_correct(system, cell, capsys):
    result = run(system=system(), cell=cell)
    assert result["correct"] is False
    assert "first mismatch: got" in capsys.readouterr().out


def test_a_double_orders_decimal_7_2_as_its_digits_do(capsys):
    """Why `DecimalAsDouble` is right at the deployment's own types: no
    two-place value of seven digits rounds across a whole number or
    across another. From 16 digits on it is not
    (`DecimalAsDouble-18`)."""
    assert run(system=DecimalAsDouble())["correct"]
    cents = np.arange(-2_000_000, 2_000_000, 7)
    texts = ["%s%d.%02d" % ("-" if c < 0 else "", abs(c) // 100, abs(c) % 100)
             for c in cents.tolist()]
    doubles = np.array([float(t) for t in texts])
    assert (np.diff(doubles) > 0).all()
    whole = np.arange(-20_000, 20_000)
    assert np.array_equal(np.searchsorted(doubles, whole, "left"),
                          np.searchsorted(cents, whole * 100, "left"))
    assert np.array_equal(np.searchsorted(doubles, whole, "right"),
                          np.searchsorted(cents, whole * 100, "right"))


def test_the_program_is_correct_where_a_double_is_not(capsys):
    """The cell `DecimalAsDouble` fails: decimal(18,2), every amount 16
    digits long."""
    result = run(system=OnItsBase(BASE), cell=WIDE_CELL)
    assert result["correct"] and result["failed"] == 0
    # and asking `< q_hi + 0.5` itself, untruncated, is the bucket's answer
    class AsksTheFraction(DeltaTpu):
        def plan_sales(self, snapshot, lo, hi, bucket=None):
            from delta_tpu.expressions import col, lit

            if bucket is None:
                return sales_queries.plan_sales(snapshot, lo, hi)
            pred = sales_queries.window_predicate(lo, hi) \
                & sales_queries.bucket_predicate(*bucket) \
                & (col("ss_quantity") < lit(D(bucket[1]) + D("0.5")))
            return snapshot.scan(filter=pred).file_paths()

    assert run(system=AsksTheFraction())["correct"]


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
BUCKET = dict(DEVICE, atoms=28, groups=12, decimal_atoms=24, distributed=1)
ALONE = dict(DEVICE, atoms=2, groups=2, decimal_atoms=0, distributed=0)
# plans at 0, 100 and 200 ms; refreshes at 1,000 and 2,000 ms
OPS = [op("plan", 0, 50), op("plan", 100, 130), op("plan", 200, 290),
       op("refresh", 1000, 1900), op("refresh", 2000, 2700)]
RECORDED = [
    span("scan.plan", 1, 40), span("plan.skip", 2, 10, **BUCKET),
    span("skip.wait", 3, 5, rows_read=16),
    span("scan.plan", 101, 20), span("plan.skip", 102, 10, **ALONE),
    span("skip.wait", 103, 5, rows_read=4),
    span("scan.plan", 201, 80), span("plan.skip", 202, 10, **BUCKET),
    span("skip.wait", 203, 5, rows_read=16),
    span("table.update", 1000, 300),
    span("scan.plan", 1300, 590), span("plan.skip", 1301, 580, **BUCKET),
    span("stats.index_build", 1310, 400),
    span("stats.index_upload", 1720, 100),
    span("skip.wait", 1850, 20, rows_read=16),
    span("table.update", 2000, 200),
    span("scan.plan", 2200, 490), span("plan.skip", 2201, 480, **BUCKET),
    span("stats.index_build", 2210, 300),
    span("stats.index_upload", 2520, 100),
    span("skip.wait", 2650, 20, rows_read=16),
    span("scan.plan", 5000, 7),     # outside every operation
]
N_PAD = 2_621_440
UPLOAD = {"kernel": "stats.index_upload", "h2d_bytes": 70 * N_PAD * 8}
LAUNCH = {"kernel": "skipping.mask_block", "h2d_bytes": 0,
          "attrs": {"lanes": 70, "n_pad": N_PAD}}
DISPATCHES = [LAUNCH] * 3 + [UPLOAD, LAUNCH, UPLOAD, LAUNCH]
EVENTS = [("jit_skipping_mask_block/fusion.1", 0, 30_000_000),
          ("jit_skipping_mask_block/fusion.2", 20_000_000, 40_000_000),
          ("jit_stats_index_upload/fusion", 0, 90_000_000)]


def recorded(spans=RECORDED, dispatches=DISPATCHES, events=EVENTS):
    trace = types.SimpleNamespace(events=[list(events)] if events else [])
    return types.SimpleNamespace(ops=OPS, spans=spans, trace=trace,
                                 dispatches=list(dispatches),
                                 device_kind="TPU v5 lite")


def least_s(rows_read):
    return (rows_read * N_PAD * 9 + N_PAD) / 819e9


@pytest.mark.parametrize("name,want", [
    ("sales_plan_ms", 40),                      # of 40, 20, 80: no refresh's
    ("sales_refresh_ms", (890 + 690) / 2),      # update + the plan after it
    ("sales_index_rebuild_ms", (500 + 400) / 2),    # build + upload
    ("sales_index_upload_mb", 70 * N_PAD * 8 / 1e6),    # a refresh: 1,468 MB
    ("sales_host_conjuncts_pct", 0),
    ("sales_decimal_atoms_pct", 100 * 4 * 24 / (4 * 28 + 2)),
    # four launches read 16 rows and one 4, in 40 ms of device time
    ("sales_skip_roofline", 100 * (4 * least_s(16) + least_s(4)) / 40e-3),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_the_mixs_share_of_decimal_atoms_is_the_issues():
    spans = ([span("plan.skip", i, 1, **BUCKET) for i in range(71)]
             + [span("plan.skip", 100 + i, 1, **ALONE) for i in range(24)])
    assert reader("sales_decimal_atoms_pct")(recorded(spans)) == (
        pytest.approx(83.7, abs=0.05))


def test_the_roofline_charges_the_rows_a_launch_reads_not_the_index():
    mine = module("layers", "sales_skip_mask_bytes").sales_skip_mask_bytes
    theirs = module("layers", "skip_mask_bytes").skip_mask_bytes
    assert mine(16, N_PAD) == 16 * N_PAD * 9 + N_PAD
    assert mine(4, N_PAD) == theirs(4, N_PAD)   # the sibling's whole index
    assert mine(16, N_PAD) < theirs(70, N_PAD)
    assert 0 < reader("sales_skip_roofline")(recorded()) < 100


def without(spans, *names, drop_attr=()):
    out = [s for s in spans if s["name"] not in names]
    return [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k not in drop_attr}) for s in out]


def plan_skips(**attrs):
    return [span("plan.skip", 2, 10, **dict(BUCKET, **attrs)),
            span("plan.skip", 102, 10, **BUCKET),
            span("plan.skip", 202, 10, **BUCKET),
            span("plan.skip", 302, 10, **BUCKET)]


@pytest.mark.parametrize("name,spans,dispatches,want", [
    # the parent, could it load the table: no atoms by kind on plan.skip,
    # a bucket's OR and its money columns left to the ladder
    ("sales_decimal_atoms_pct",
     without(RECORDED, drop_attr=("atoms", "decimal_atoms")), [], None),
    ("sales_decimal_atoms_pct", [], [], None),
    ("sales_host_conjuncts_pct", plan_skips(skip_fallback_conjuncts=1),
     [], 25),
    ("sales_host_conjuncts_pct", plan_skips(uncompared=1), [], 25),
    ("sales_host_conjuncts_pct", plan_skips(skip_route="host"), [], 25),
    ("sales_host_conjuncts_pct",
     [span("plan.skip", 2, 10, rows=5, conjuncts=2)], [], 100),
    ("sales_host_conjuncts_pct", [], [], None),
    ("sales_skip_roofline", without(RECORDED, drop_attr=("rows_read",)),
     DISPATCHES, None),
    ("sales_skip_roofline", RECORDED, [UPLOAD], None),  # no plan on the chip
    ("sales_skip_roofline", without(RECORDED, "skip.wait"), DISPATCHES, None),
    ("sales_index_upload_mb", RECORDED, [LAUNCH], None),
    ("sales_plan_ms", [], [], None), ("sales_refresh_ms", [], [], None),
    ("sales_index_rebuild_ms", [], [], None),
])
def test_a_reader_on_a_program_without_its_spans(name, spans, dispatches,
                                                 want):
    got = reader(name)(recorded(spans, dispatches))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_roofline_finds_nothing_without_a_device_plane():
    assert reader("sales_skip_roofline")(recorded(events=())) is None


if __name__ == "__main__":      # the cell itself, on the chip, broken
    import argparse

    t0 = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("system", choices=sorted(REAL_SIZE))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    asked = parser.parse_args()
    result = harness.run_cell(CELL, asked.seed, asked.seconds, False, t0,
                              system=globals()[asked.system]())
    print(json.dumps({"system": asked.system, "cell": CELL,
                      "seed": asked.seed, "correct": result["correct"],
                      "has_to_read": REAL_SIZE[asked.system],
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "device": result["device"]}), flush=True)
    raise SystemExit(result["correct"] is not REAL_SIZE[asked.system])
