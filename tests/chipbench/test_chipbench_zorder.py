"""The deployment `tpcds-store-sales-day-zorder` and its cell
`optimize-zorder-under-ingest`, at a test's size on the CPU: OPTIMIZE
... ZORDER BY on a day's partition that late micro-batches keep landing
in. The generator's tables against what the library reads back; the
reference's replay against `Snapshot`'s; whole runs, plain and traced;
three systems with one guarantee broken, which have to read not
correct; the twelve readers on a recorded run; and the cell's files by
name.

Run as a program, the same file runs the cell at its real size on one
of the broken systems (`chiprun -- python3
tests/chipbench/test_chipbench_zorder.py RanksInSixteenBits --seed <n>
--seconds 8`, the checkout on `PYTHONPATH`) and exits 0 when the
harness's comparison reads not correct."""

import contextlib
import importlib.util
import json
import os
import sys
import time
import types

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from chipbench import harness                                   # noqa: E402
from chipbench.gen import tpcds_store_sales_day as gen          # noqa: E402
from chipbench.reference import zorder_oracle as oracle         # noqa: E402
from chipbench.system import DeltaTpu                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "zorder", "benchmark.json")
TINY_CELL = "tiny-zorder-under-ingest"
SMALL_CELL = "small-zorder-under-ingest"
CELL = "optimize-zorder-under-ingest"
CONFIG = "tpcds-store-sales-day-zorder"
MIX = "zorder-late-batches"
OP = "optimize-zorder"
SEED = 2**31 + 29
MS = 1_000_000
METRICS = {
    "zorder_optimize_ms", "zorder_read_ms", "zorder_keys_ms",
    "zorder_curve_ms", "zorder_gather_ms", "zorder_write_ms",
    "zorder_commit_ms", "zorder_rows_per_s", "zorder_h2d_mb_per_op",
    "zorder_curve_roofline", "zorder_interleave_roofline",
    "zorder_idle_pct"}


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"zorder_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "optimize_zorder")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def tiny_params(**over):
    return dict(load_json("tests", "chipbench", "zorder", "configs",
                          "tiny-day-zorder.json")["generator"], **over)


# ---- the generator --------------------------------------------------------

def in_any_order(table: pa.Table) -> pa.Table:
    """The rows sorted by every column: equal for equal multisets."""
    return table.sort_by([(name, "ascending")
                          for name in table.column_names]).combine_chunks()


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    return gen.generate(str(tmp_path_factory.mktemp("zorder")),
                        tiny_params(), SEED)


def test_the_generators_tables_equal_what_the_library_reads_back(made):
    import delta_tpu.api as dta

    params = tiny_params()
    rngs = [np.random.default_rng([SEED, i]) for i in range(3)]
    before = gen.rows_of_a_date(4250, made.day_sk - 1, params, rngs[0])
    day = gen.rows_of_a_date(4250, made.day_sk, params, rngs[1])
    late = gen.rows_of_a_date(19 * 200, made.day_sk, params, rngs[2])
    back = dta.read_table(made.table_path).select(day.column_names)
    assert in_any_order(back).equals(
        in_any_order(pa.concat_tables([before, day])))
    assert pa.concat_tables(made.late).equals(late)
    assert [b.num_rows for b in made.late] == [200] * 19
    assert made.day_digest == oracle.key_digest(day)
    # one sold date each, tickets of one customer, keys in their domains
    assert set(day.column("ss_sold_date_sk").to_pylist()) == {made.day_sk}
    assert pc.max(day.column("ss_item_sk")).as_py() <= 360_000
    assert pc.max(day.column("ss_ticket_number")).as_py() <= 720_000_000
    assert day.column("ss_item_sk").null_count == 0
    assert day.column("ss_ticket_number").null_count == 0
    assert 0 < day.column("ss_customer_sk").null_count < 0.1 * 4250
    per_ticket = day.group_by("ss_ticket_number").aggregate(
        [("ss_customer_sk", "count_distinct")])
    assert pc.max(per_ticket.column(
        "ss_customer_sk_count_distinct")).as_py() == 1
    assert day.schema == before.schema
    assert [f.name for f in day.schema] == [
        name for name, _ in gen.tpcds_sf1.SCHEMAS["store_sales"]]


def test_the_day_lands_in_a_sinks_files_and_the_day_before_in_one(made):
    log = os.path.join(made.table_path, "_delta_log")
    assert made.version == 5 and made.num_files() == 44
    live = oracle.replay(log, made.version)
    day = oracle.in_partition(live, gen.PARTITION_BY, made.day_sk)
    before = oracle.in_partition(live, gen.PARTITION_BY, made.day_sk - 1)
    assert len(before) == 1 and oracle.stated_rows(before[0]) == 4250
    assert sorted(oracle.stated_rows(a) for a in day) == [50] + [100] * 42
    # micro-batches of ten files: four commits of ten and one of three
    sizes = [sum("add" in a for a in oracle.read_commit(log, v))
             for v in range(1, 6)]
    assert sizes == [10, 10, 10, 10, 3]
    assert all(a["dataChange"] for a in live.values())
    assert made.load_actions == 44 + 6 + 2 and made.log_bytes > 0
    assert set(made.took) == {"generate", "land the day before",
                              "land the day", "account"}


def test_another_seed_is_another_table(tmp_path):
    other = gen.generate(str(tmp_path), tiny_params(late_batches=1),
                         SEED + 1)
    assert other.day_digest != gen.generate(
        str(tmp_path / "again"), tiny_params(late_batches=1),
        SEED).day_digest


# ---- the reference's replay against Snapshot's ---------------------------

def test_the_references_replay_equals_the_snapshots(tmp_path):
    import delta_tpu.api as dta
    from delta_tpu import Table

    made = gen.generate(str(tmp_path), tiny_params(), SEED)
    table = Table.for_path(made.table_path)
    log = os.path.join(made.table_path, "_delta_log")
    replay = oracle.Replay(log)
    for step in range(4):
        if step % 2:
            dta.write_table(made.table_path, made.late[step], mode="append",
                            target_rows_per_file=made.file_rows)
        else:
            DRIVER.optimize_zorder(table, made.day_sk, made.zorder_by)
        snapshot = table.update()
        live = replay.at(snapshot.version)
        assert sorted(live) == sorted(
            snapshot.state.add_files_table.column("path").to_pylist())
        assert live == oracle.replay(log, snapshot.version)
    with pytest.raises(ValueError, match="only advances"):
        replay.at(2)


# ---- whole runs -----------------------------------------------------------

def run_cell(cell=TINY_CELL, seconds=0.4, trace=False, system=None,
             seed=SEED):
    from delta_tpu import obs

    try:
        return harness.run_cell(cell, seed, seconds, trace,
                                time.perf_counter(), bench_path=TINY,
                                require_chip=False, system=system)
    finally:
        if trace:
            obs.set_trace_mode(None)
            obs.set_device_obs_mode(None)
            obs.reset_trace_buffer()
            obs.reset_device_obs()


def compared_in(out, title):
    """name -> (compared, mismatches) of the harness's account."""
    found = {}
    for line in out.splitlines():
        if line.startswith(title + " ") and ": compared " in line:
            name, rest = line[len(title) + 1:].split(": compared ", 1)
            n, rest = rest.split(", mismatches ", 1)
            found[name] = (int(n), int(rest.split(" ", 1)[0]))
    return found


EVERY_OPERATION = {
    "version", "late_batch_version", "commit_in_log", "operation",
    "zorder_by", "removed_paths", "removes_data_change", "adds_data_change",
    "adds_of_other_partitions", "files_removed", "files_added",
    "live_files_of_the_day", "live_files_of_the_day_before",
    "live_files_of_other_days", "rows_by_num_records", "rows_by_footers",
    "key_digest"}
BY_ROWS = {
    "rows_in", "rows_out", "row_digest_out",
    "files_not_the_references_row_for_row",
    "files_whose_stats_are_not_the_files"}
COLD_LOAD = {"cold_load_version", "cold_load_num_files", "cold_load_paths"}
# the warm-up's first operation by rows, compared once the window has closed
FIRST_BY_ROWS = {"first_optimize_" + name for name in BY_ROWS}


def test_a_run_reclusters_the_day_after_every_late_batch(capsys):
    result = run_cell()
    out = capsys.readouterr().out
    assert result["correct"] and result["failed"] == 0
    n = result["attempted"]
    assert n >= 2
    assert set(result["metrics"]) == {"op_p50_ms", "ops_per_s", "setup_s"}
    warm, window = compared_in(out, "warm-up"), compared_in(out, "window")
    # the warm-up: the sink's 43 files into one, then the steady shape
    # once, each with a cold load; the window's every operation by the
    # commit, the footers and the digest; once the window has closed, its
    # last by rows and a cold load, and the warm-up's first by rows (the
    # reference's seconds are no part of the set-up)
    assert set(warm) == EVERY_OPERATION | COLD_LOAD
    assert set(window) == (EVERY_OPERATION | BY_ROWS | COLD_LOAD
                           | FIRST_BY_ROWS)
    assert all(warm[name] == (2, 0) for name in warm)
    assert all(window[name] == (n, 0) for name in EVERY_OPERATION)
    assert all(window[name] == (1, 0)
               for name in BY_ROWS | COLD_LOAD | FIRST_BY_ROWS)
    said = [line for line in out.splitlines()
            if line.startswith("OPTIMIZE at version ")]
    assert len(said) == n + 2
    assert "4450 rows, 45 files" in said[0] and "in full" not in said[0]
    # every later one: the file the last wrote and the two late files;
    # a late batch and an OPTIMIZE a time, so the versions are even
    for k, line in enumerate(said[1:], 2):
        assert f"{4250 + 200 * k} rows, 3 files" in line and " into 1 " in line
    assert [line.split()[3].rstrip(",") for line in said] == [
        str(7 + 2 * k) for k in range(n + 2)]
    assert "in full" in said[-1] and "in full" not in said[1]
    assert "set-up: fixture (generate " in out and "first OPTIMIZE" in out
    by_rows = [line for line in out.splitlines()
               if line.startswith("full check against the reference: ")]
    assert len(by_rows) == 2 and "4450 rows of 45 files" in by_rows[1]
    assert out.index(by_rows[0]) > out.index("warm-up version: compared 2")
    assert "process RSS " in out


def test_the_driver_deletes_what_older_commits_removed(tmp_path):
    made = gen.generate(str(tmp_path), tiny_params(), SEED)
    driver = DRIVER.Driver(DeltaTpu(), made)
    driver.table, snapshot = driver.system.load(made.table_path)
    driver.replay.at(snapshot.version)
    driver.before = "unused here"
    driver.warming = True

    def on_disk():
        return len(os.listdir(os.path.join(
            made.table_path, f"ss_sold_date_sk={made.day_sk}")))

    # what the newest commit removed stays for its check: the sink's 43
    # files and the first late batch's go when the second OPTIMIZE has
    # been checked
    for expected in (45 + 1, 45 + 1 + 2 + 1, 1 + 2 + 1 + 2 + 1,
                     1 + 2 + 1 + 2 + 1):
        prep = driver.prepare({})
        answer = driver.timed(prep)
        driver.check(prep, answer, False)
        assert on_disk() == expected
    driver.prepare({})      # and a late batch lands
    assert on_disk() == 1 + 2 + 1 + 2
    assert os.listdir(os.path.join(
        made.table_path, f"ss_sold_date_sk={made.day_sk - 1}"))


def test_the_first_operations_files_stay_for_the_check_after_the_window(
        tmp_path):
    made = gen.generate(str(tmp_path), tiny_params(), SEED)
    driver = DRIVER.Driver(DeltaTpu(), made)
    driver.table, snapshot = driver.system.load(made.table_path)
    driver.before = paths_before = DRIVER.paths_of(oracle.in_partition(
        driver.replay.at(snapshot.version), "ss_sold_date_sk",
        made.day_sk - 1))
    day = os.path.join(made.table_path, f"ss_sold_date_sk={made.day_sk}")

    def operation(full):
        prep = driver.prepare({})
        answer = driver.timed(prep)
        return dict((name, (got, want)) for name, got, want
                    in driver.check(prep, answer, full)[1])

    driver.warming = True
    first = operation(True)
    assert not set(first) & (BY_ROWS | FIRST_BY_ROWS)
    assert len(driver.kept) == 45 + 1 and len(os.listdir(day)) == 45 + 1
    operation(True)
    driver.warming = False
    operation(False)
    closing = operation(True)
    # of what the second operation read, its late batch's two files have
    # gone; what the first read and wrote has not
    assert len(os.listdir(day)) == (45 + 1) + (2 + 1) + (2 + 1) + (2 + 1) - 2
    assert driver.kept <= {
        f"ss_sold_date_sk={made.day_sk}/{name}" for name in os.listdir(day)}
    assert FIRST_BY_ROWS | BY_ROWS <= set(closing)
    assert all(got == want for got, want in closing.values())
    assert closing["first_optimize_rows_in"] == (4450, 4450)
    assert closing["rows_in"] == (4450 + 3 * 200, 4450 + 3 * 200)
    assert driver.first is None and paths_before.startswith("1 paths")


def test_the_driver_stops_before_a_batch_crosses_the_bucket(tmp_path):
    made = gen.generate(str(tmp_path), tiny_params(rows_a_date=8100,
                                                   late_batches=2), SEED)
    driver = DRIVER.Driver(DeltaTpu(), made)
    driver.table, _ = driver.system.load(made.table_path)
    driver.warming = True
    with pytest.raises(RuntimeError, match="past 8192 rows"):
        driver.prepare({})
    driver.late, driver.bucket = [], 1 << 20
    with pytest.raises(RuntimeError, match="late batches are used up"):
        driver.prepare({})


# ---- three systems with one guarantee broken -----------------------------

@contextlib.contextmanager
def patched(owner, name, make):
    real = getattr(owner, name)
    setattr(owner, name, make(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


class Broken(DeltaTpu):
    """The system with one function of the program put out of order
    from `__enter__` on (for the whole run: a program patched an
    operation at a time would compile inside the window)."""

    def __enter__(self):
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(self.patch())
        return self

    def __exit__(self, *exc):
        self._stack.close()


class RanksInSixteenBits(Broken):
    """Breaks guarantee 3 by the lower precision: the ranks are carried
    in sixteen-bit lanes, so a rank past 65,535 wraps and a day of more
    rows than that comes out in another order; every other guarantee
    holds. (Ranks merely rounded to their top sixteen bits would almost
    never show: two rows' keys tie only where all three ranks agree
    there.)"""

    @contextlib.contextmanager
    def patch(self):
        import jax.numpy as jnp

        from delta_tpu.ops import zorder

        def narrow(scale):
            return lambda ranks, n, bits: scale(
                ranks & jnp.uint32(0xFFFF), n, bits)

        zorder._curve_perm.clear_cache()
        with patched(zorder, "_scale_ranks", narrow):
            yield
        zorder._curve_perm.clear_cache()


class KeepsDataChange(Broken):
    """Breaks guarantee 2: the new files are added with `dataChange`
    true, as a writer's are, so a stream reading the table would take
    the day's rows a second time."""

    @contextlib.contextmanager
    def patch(self):
        from delta_tpu.commands import optimize

        def loud(write):
            return lambda **kw: write(**dict(kw, data_change=True))

        with patched(optimize, "write_data_files", loud):
            yield


class DropsNullCustomers(Broken):
    """Breaks guarantee 1: the rows whose customer is null are lost on
    the way to the new files."""

    @contextlib.contextmanager
    def patch(self):
        from delta_tpu.commands import optimize

        def lossy(write):
            return lambda **kw: write(**dict(kw, data=kw["data"].filter(
                pc.is_valid(kw["data"].column("ss_customer_sk")))))

        with patched(optimize, "write_data_files", lossy):
            yield


BROKEN = {
    # system: (the tests' cell, comparisons that must read a mismatch,
    #          comparisons that must not)
    "RanksInSixteenBits": (
        RanksInSixteenBits, SMALL_CELL,
        {"files_not_the_references_row_for_row"},
        {"key_digest", "row_digest_out", "rows_by_footers",
         "adds_data_change", "files_whose_stats_are_not_the_files"}),
    "KeepsDataChange": (
        KeepsDataChange, TINY_CELL, {"adds_data_change"},
        {"key_digest", "files_not_the_references_row_for_row",
         "removes_data_change"}),
    "DropsNullCustomers": (
        DropsNullCustomers, TINY_CELL,
        {"key_digest", "rows_by_footers", "rows_by_num_records"},
        {"adds_data_change"}),
}


@pytest.mark.parametrize("name", sorted(BROKEN))
def test_a_broken_guarantee_is_not_correct(name, capsys):
    system, cell, must, must_not = BROKEN[name]
    with system() as broken:
        result = run_cell(cell, system=broken)
    out = capsys.readouterr().out
    assert result["correct"] is False
    warm, window = compared_in(out, "warm-up"), compared_in(out, "window")
    for title, found in (("warm-up", warm), ("window", window)):
        wrong = {n for n, (_, bad) in found.items() if bad}
        # by rows the warm-up is compared with the window's last
        assert must & set(found) <= wrong and not must_not & wrong, (
            title, wrong)
    for n in must & BY_ROWS:
        assert window[n] == window["first_optimize_" + n] == (1, 1)
    if name == "DropsNullCustomers":
        # the check of every operation catches it, without the reference
        window = compared_in(out, "window")
        assert window["key_digest"] == (result["attempted"],
                                        result["attempted"])
        assert result["failed"] == result["attempted"]


def test_sixteen_bits_are_enough_for_a_day_they_can_count(capsys):
    """The control is the lower precision and nothing else: no rank of
    a day under 65,536 rows wraps, and the run is correct."""
    with RanksInSixteenBits() as broken:
        result = run_cell(TINY_CELL, system=broken)
    assert result["correct"]


# ---- the readers, on a recorded run --------------------------------------

def reader(name):
    return module("layers", name).read


def span(name, start_ms, dur_ms, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "attrs": attrs}


def operation(start_ms, end_ms):
    return {"kind": OP, "start_unix_ns": start_ms * MS,
            "end_unix_ns": end_ms * MS}


def one_operation(t, write_ms):
    """The spans of one OPTIMIZE that begins at `t` ms."""
    return [
        span("command.optimize", t, 7200 + write_ms),
        span("optimize.plan", t + 10, 30, candidates=10, bins=1),
        span("optimize.read", t + 50, 2000, files=10, rows=4_747_406),
        span("optimize.keys", t + 2100, 200, columns=3, n_pad=5_242_880),
        span("optimize.curve", t + 2300, 900, curve="zorder"),
        span("optimize.gather", t + 3200, 3900, rows=4_747_406, columns=23),
        span("optimize.write", t + 7100, write_ms, files=2),
        span("optimize.commit", t + 7100 + write_ms, 80, adds=2, removes=10),
    ]


def curve_record(rows, attrs=True):
    record = {"kernel": "zorder.curve_perm", "h2d_bytes": 3 * 5_242_880 * 4}
    if attrs:
        record["attrs"] = {"columns": 3, "n_pad": 5_242_880, "rows": rows}
    return record


def recorded(with_spans=True, with_attrs=True):
    """Two operations, 9,000 and 11,000 ms in `optimize.write`, with a
    late batch's `table.write` between them, on a chip whose curve
    program covers 800 ms a launch, 4 ms of them the interleave's."""
    spans = (one_operation(0, 9000) + one_operation(20_000, 11_000)
             + [span("table.write", 17_000, 60)])
    if not with_spans:
        spans = [s for s in spans if s["name"] == "command.optimize"]
    events = [[]]
    for t in (2400, 22_400):
        events[0] += [
            ("jit_zorder_curve_perm/while.32", t * MS, (t + 500) * MS),
            ("jit_zorder_curve_perm/sort.24", (t + 100) * MS, (t + 300) * MS),
            ("jit_zorder_curve_perm/%interleave_bits_tiled.1",
             (t + 500) * MS, (t + 504) * MS),
            ("jit_zorder_curve_perm/while.27", (t + 504) * MS,
             (t + 800) * MS),
            ("jit_replay_single/x", (t + 900) * MS, (t + 950) * MS)]
    return types.SimpleNamespace(
        ops=[operation(0, 16_300), operation(20_000, 38_300)], spans=spans,
        dispatches=[curve_record(4_747_406, with_attrs),
                    curve_record(4_755_406, with_attrs),
                    {"kernel": "replay.single", "h2d_bytes": 5}],
        trace=types.SimpleNamespace(events=events, busy_s=1.7,
                                    window_s=40.0),
        device_kind="TPU v5 lite")


CURVE_BYTES = (3 + 1) * 4 * 5_242_880
INTERLEAVE_BYTES = 2 * 3 * 4 * 5_242_880
BY_HAND = {
    "zorder_optimize_ms": (16_200 + 18_200) / 2,
    "zorder_read_ms": 2000.0,
    "zorder_keys_ms": 200.0,
    "zorder_curve_ms": 900.0,
    "zorder_gather_ms": 3900.0,
    "zorder_write_ms": 10_000.0,
    "zorder_commit_ms": 80.0,
    "zorder_rows_per_s": (4_747_406 + 4_755_406) / 34.4,
    "zorder_h2d_mb_per_op": 62.91456,
    # two launches' least time over the 1.6 s their operations cover
    "zorder_curve_roofline": 100 * (2 * CURVE_BYTES / 819e9) / 1.6,
    "zorder_interleave_roofline":
        100 * (2 * INTERLEAVE_BYTES / 819e9) / 0.008,
    "zorder_idle_pct": 100 * (1 - 1.7 / 40.0),
}


def test_the_twelve_readers_are_the_cells_twelve_metrics():
    assert set(BY_HAND) == METRICS
    counts = module("layers", "zorder_curve_bytes")
    attrs = {"columns": 3, "n_pad": 5_242_880}
    assert counts.curve_bytes(attrs) == CURVE_BYTES == 83_886_080
    assert counts.interleave_bytes(attrs) == INTERLEAVE_BYTES == 125_829_120
    assert BY_HAND["zorder_curve_roofline"] < 100
    assert BY_HAND["zorder_interleave_roofline"] < 100


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_reader_gives_the_hand_computed_value(name):
    assert reader(name)(recorded()) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name,want", [
    # the parent of PR 55 under the new readers: `command.optimize` alone,
    # a dispatch record without `attrs`
    ("zorder_optimize_ms", (16_200 + 18_200) / 2),
    ("zorder_h2d_mb_per_op", 62.91456),
    ("zorder_idle_pct", 100 * (1 - 1.7 / 40.0)),
    ("zorder_read_ms", None), ("zorder_keys_ms", None),
    ("zorder_curve_ms", None), ("zorder_gather_ms", None),
    ("zorder_write_ms", None), ("zorder_commit_ms", None),
    ("zorder_rows_per_s", None), ("zorder_curve_roofline", None),
    ("zorder_interleave_roofline", None),
])
def test_a_reader_on_the_parents_spans_reads_what_is_there(name, want):
    got = reader(name)(recorded(with_spans=False, with_attrs=False))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", sorted(METRICS - {"zorder_idle_pct"}))
def test_a_reader_finds_nothing_in_a_run_without_the_command(name):
    empty = types.SimpleNamespace(
        ops=[operation(0, 10)], spans=[], dispatches=[],
        trace=types.SimpleNamespace(events=[[]], busy_s=0.0, window_s=1.0),
        device_kind="TPU v5 lite")
    assert reader(name)(empty) is None


def test_a_traced_run_reads_the_cells_metrics(capsys):
    """On the CPU nothing runs on a chip, so the two shares of a
    roofline read nothing; the other ten read, and the spans add up."""
    from delta_tpu import obs

    before = obs.counter("optimize.rows_clustered").value
    result = run_cell(trace=True)
    out = capsys.readouterr().out
    assert result["correct"]
    silent = {"zorder_curve_roofline", "zorder_interleave_roofline"}
    assert set(result["metrics"]) == METRICS - silent
    for name in silent:
        assert f"metric {name}: nothing to read in this run" in out
    values = {k: v["value"] for k, v in result["metrics"].items()}
    parts = sum(values[f"zorder_{p}_ms"] for p in (
        "read", "keys", "curve", "gather", "write", "commit"))
    assert 0.5 * values["zorder_optimize_ms"] < parts < 1.1 * values[
        "zorder_optimize_ms"]
    assert values["zorder_h2d_mb_per_op"] == pytest.approx(
        3 * 8192 * 4 / 1e6)
    assert values["zorder_rows_per_s"] > 0
    # the span table of the closing operation
    for name in DRIVER.SPANS:
        assert f"  span {name}: " in out
    # the counter counts the rows the dispatch records name, and the
    # warm-up's: the reader's are the window's alone
    n = result["attempted"]
    rows = sum(4250 + 200 * k for k in range(1, n + 3))
    assert obs.counter("optimize.rows_clustered").value - before == rows


# ---- the cell's files -----------------------------------------------------

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == CONFIG and cell.entry["traffic"] == MIX
    assert cell.entry["chips"] == 1
    assert cell.mix["driver"] == "optimize_zorder" and "draws" not in cell.mix
    assert cell.config["generator"]["kind"] == "tpcds_store_sales_day"
    assert cell.module("gen", "tpcds_store_sales_day").generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert METRICS <= mine
    for name in METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    bench = load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == ["dates"] == list(cell.config["reduced"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if CELL not in m.get("workloads", [])}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] in layers     # a layer PERF.md has
        assert by_name[name]["moves"] == (
            "ops_per_s" if name in ("zorder_rows_per_s", "zorder_idle_pct")
            else "op_p50_ms")
    for name in ("zorder_curve_roofline", "zorder_interleave_roofline",
                 "zorder_idle_pct"):
        assert by_name[name]["source"] == "device_trace"
    for name in ("zorder_rows_per_s", "zorder_h2d_mb_per_op"):
        assert by_name[name]["source"] == "program_counter"
    # the new entries stand at the end of their lists
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in bench["per_layer"][-12:]} == METRICS
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_configuration_states_its_source_cuts_and_guarantees():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    assert config["architecture"] is None
    assert list(config["reduced"]) == ["dates"]
    assert len(config["guarantees"]) == 4 and "limit 0" in config["tolerance"]
    for key in ("tpcds", "pricing", "generator", "micro_batches",
                "the_day_before", "command", "curve", "route", "bucket",
                "vacuum", "checkpoints", "on_the_chip", "client", "storage",
                "allocator"):
        assert config["assumed"][key]
    # no width is cut: the rows of a sold date, the columns, the domains
    # of scale factor 3000, the sink's files
    g = config["generator"]
    assert g["rows_a_date"] == 4_739_406 == -(-8_639_936_081 // 1_823)
    assert g["file_rows"] == 1000 and g["late_files"] == 8
    assert g["domains"]["item"] == 360_000
    assert g["domains"]["customer"] == 30_000_000
    assert g["zorder_by"] == ["ss_item_sk", "ss_customer_sk",
                              "ss_ticket_number"]
    sibling = load_json("chipbench", "configs", "tpcds-sf1.json")
    assert config["schema"]["store_sales"] == sibling["schema"]["store_sales"]
    assert config["environment"] == sibling["environment"]
    # what `assumed.bucket` says: the window's rows stay in one bucket
    from delta_tpu.commands.optimize import DEFAULT_MAX_FILE_SIZE
    from delta_tpu.ops.replay import pad_bucket

    rows = g["rows_a_date"]
    late = g["late_batches"] * g["late_files"] * g["file_rows"]
    assert pad_bucket(rows, min_bucket=1024) == 5_242_880 == pad_bucket(
        rows + late, min_bucket=1024)
    assert (5_242_880 - rows) // 8000 == 62 == g["late_batches"]
    assert DEFAULT_MAX_FILE_SIZE == 256 * 1024 * 1024


def test_the_tests_own_cells_are_the_cell_at_smaller_days():
    tiny, bench = load_json("tests", "chipbench", "zorder",
                            "benchmark.json"), load_json("BENCHMARK.json")
    assert [w["name"] for w in tiny["workloads"]] == [TINY_CELL, SMALL_CELL]
    assert all(w["traffic"] == MIX for w in tiny["workloads"])
    assert {m["name"] for m in tiny["per_layer"]} == METRICS
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in tiny["per_layer"]:
        assert dict(m, workloads=[CELL]) == by_name[m["name"]]
    real = load_json("chipbench", "configs", CONFIG + ".json")["generator"]
    sized = ("rows_a_date", "file_rows", "files_a_commit", "late_files",
             "late_batches")
    for name in ("tiny-day-zorder", "small-day-zorder"):
        g = load_json("tests", "chipbench", "zorder", "configs",
                      name + ".json")["generator"]
        assert {k: v for k, v in g.items() if k not in sized} == {
            k: v for k, v in real.items() if k not in sized}
    # the control's day has to be one that sixteen bits cannot count
    assert load_json("tests", "chipbench", "zorder", "configs",
                     "small-day-zorder.json")["generator"][
                         "rows_a_date"] > 1 << 16


if __name__ == "__main__":      # the cell itself, on the chip, broken
    import argparse

    t0 = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("system", choices=sorted(BROKEN))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    asked = parser.parse_args()
    with BROKEN[asked.system][0]() as broken_system:
        result = harness.run_cell(CELL, asked.seed, asked.seconds, False, t0,
                                  system=broken_system)
    print(json.dumps({"system": asked.system, "cell": CELL,
                      "seed": asked.seed, "correct": result["correct"],
                      "has_to_read": False,
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "device": result["device"]}), flush=True)
    raise SystemExit(result["correct"] is not False)
