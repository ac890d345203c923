"""The deployment `tpcds-sf1` and its cell `tpcds-q-mix`, at a test's
size on the CPU (60,000 rows of `store_sales` on every 13th sold date,
the dimensions that scale with the rows cut with them): the generator's
tables against what the library reads back of them; the reference
(SQLite) against `HostEngine`, `TpuEngine` by the default gate and
`TpuEngine` with the device route forced, for all eight queries on three
seeds; the comparison's rules on built cases; whole runs of the cell;
the ten readers; three broken systems.

`python3 tests/chipbench/test_chipbench_tpcds.py <broken system> --seed
<n> --seconds <s>` runs the cell itself, at its real size and on the
chip, on one of them: the last line is the harness's result, and the
exit code is 0 when it read not correct."""

import ast
import importlib.util
import json
import os
import re
import time
import types

import numpy as np
import pyarrow as pa
import pytest

from chipbench import harness, tpcds_queries
from chipbench.gen import tpcds_sf1
from chipbench.reference import tpcds_oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tpcds", "benchmark.json")
CELL = "tpcds-q-mix"
SEEDS = (2**31 + 49, 7, 1_000_003)
NAMES = ("q3", "q7", "q19", "q68", "q96", "q42", "q52", "q55")
with open(os.path.join(HERE, "tpcds", "configs", "tiny-tpcds.json")) as f:
    PARAMS = json.load(f)["generator"]
with open(os.path.join(ROOT, "chipbench", "configs", "tpcds-sf1.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "chipbench", "mixes", "q-mix.json")) as f:
    MIX = json.load(f)
SQL_METRICS = {"sql_scan_ms", "sql_join_ms", "sql_agg_sort_ms",
               "sql_device_rows_pct", "sql_fallback_pct",
               "sql_h2d_mb_per_op", "sql_device_wait_ms",
               "sql_join_roofline", "sql_join_codes_roofline",
               "sql_idle_pct"}
ROOFLINES = {"sql_join_roofline", "sql_join_codes_roofline"}
Q = tpcds_queries.QUERIES


def reader(name):
    path = os.path.join(ROOT, "chipbench", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("tpcds_layer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the generator's tables = the reference = three engines ----

class Loaded:
    """One seed's tables, loaded once: the manifest, the reference's
    answers, and a catalog an engine."""

    def __init__(self, root, seed):
        from delta_tpu.catalog import Catalog
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.engine.tpu import TpuEngine

        self.manifest = tpcds_sf1.generate(
            root, dict(PARAMS, queries=list(NAMES)), seed)
        self.tables = dict(self.manifest.tables)
        oracle = tpcds_oracle.Oracle(self.tables, [q.text for q in Q.values()])
        self.want = {n: oracle.answer(q.text, q.kinds) for n, q in Q.items()}
        self.catalogs = {}
        for name, engine in (("host", HostEngine()), ("tpu", TpuEngine())):
            cat = Catalog(os.path.join(root, "catalog-" + name), engine=engine)
            for table, path in self.manifest.table_paths.items():
                cat.register(table, path)
            self.catalogs[name] = cat


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    made = {}

    def get(seed):
        if seed not in made:
            made[seed] = Loaded(str(tmp_path_factory.mktemp("tpcds")), seed)
        return made[seed]

    return get


SYSTEMS = {"host-engine": ("host", None), "tpu-default-gate": ("tpu", None),
           "tpu-device-forced": ("tpu", "force")}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("system", sorted(SYSTEMS))
def test_every_engine_answers_as_the_reference(loaded, monkeypatch, system,
                                               name, seed):
    data = loaded(seed)
    catalog, forced = SYSTEMS[system]
    if forced:
        monkeypatch.setenv("DELTA_TPU_DEVICE_SQL", forced)
    rows = tpcds_queries.run_query(data.catalogs[catalog], Q[name])
    want = data.want[name]
    assert want, "the reference's answer is empty: the test shows nothing"
    assert len(rows) == min(100, len(want))
    assert tpcds_queries.broken_rows(rows, want, Q[name]) == 0


def test_three_of_the_answers_pass_the_limit(loaded):
    long = [n for n in NAMES if len(loaded(SEEDS[0]).want[n]) > 100]
    assert len(long) >= 2, long


@pytest.mark.parametrize("table", sorted(tpcds_sf1.SCHEMAS))
def test_a_table_reads_back_as_the_generator_made_it(loaded, table):
    import delta_tpu.api as dta

    data = loaded(SEEDS[0])
    made = data.tables[table]
    back = dta.read_table(data.manifest.table_paths[table]).select(
        made.column_names)
    assert back.schema.types == made.schema.types
    key = [(c, "ascending") for c in made.column_names[:3]] + (
        [("ss_ticket_number", "ascending")] if table == "store_sales" else [])
    assert back.sort_by(key).equals(made.sort_by(key))


def test_store_sales_is_partitioned_by_sold_date_with_its_null_partition(
        loaded):
    from delta_tpu import Table

    data = loaded(SEEDS[0])
    snap = Table.for_path(
        data.manifest.table_paths["store_sales"]).latest_snapshot()
    assert snap.partition_columns == ["ss_sold_date_sk"]
    values = [dict(pv)["ss_sold_date_sk"] for pv in
              snap.state.add_files_table.column("partition_values").to_pylist()]
    assert values.count(None) == 1
    dates = sorted(int(v) for v in values if v is not None)
    assert dates == list(range(2_450_816, 2_450_816 + 1_823, 13))
    assert not any("." in v for v in values if v is not None)
    assert data.manifest.files["store_sales"] == len(dates) + 1
    assert data.manifest.num_files() == len(dates) + 1 + 9
    assert data.manifest.version == 0 and data.manifest.log_bytes > 0
    assert data.manifest.load_actions > data.manifest.num_files()


def test_the_generator_is_deterministic_in_the_seed():
    small = {"rows": dict(PARAMS["rows"], store_sales=500,
                          customer_demographics=70), "sold_date_step": 13}
    a, b, c = (tpcds_sf1.tables(small, s) for s in (5, 5, 6))
    assert all(a[t].equals(b[t]) for t in a)
    assert not a["store_sales"].equals(c["store_sales"])


# ---- the tables at scale factor 1, by the specification ----

def test_the_demographics_are_the_specifications_cross_products():
    rng = np.random.default_rng(0)
    cd = tpcds_sf1.customer_demographics(1_920_800, rng)
    groups = cd.group_by(["cd_gender", "cd_marital_status",
                          "cd_education_status"]).aggregate([([], "count_all")])
    assert groups.num_rows == 2 * 5 * 7
    assert set(groups.column("count_all").to_pylist()) == {1_920_800 // 70}
    assert cd.column("cd_dep_college_count").to_pylist()[-1] == 6
    hd = tpcds_sf1.household_demographics(7_200, rng)
    assert sorted(set(hd.column("hd_vehicle_count").to_pylist())) == list(
        range(-1, 5))
    assert sorted(set(hd.column("hd_dep_count").to_pylist())) == list(range(10))
    assert len(set(hd.column("hd_income_band_sk").to_pylist())) == 20


def test_the_dates_and_times_are_the_specifications():
    rng = np.random.default_rng(0)
    dates = tpcds_sf1.date_dim(73_049, rng)
    first, last = dates.slice(0, 1).to_pylist()[0], dates.slice(
        73_048, 1).to_pylist()[0]
    assert (first["d_date_sk"], str(first["d_date"])) == (2_415_022,
                                                          "1900-01-02")
    assert str(last["d_date"]) == "2100-01-01"
    sold = dates.slice(2_450_816 - 2_415_022, 1).to_pylist()[0]
    assert (str(sold["d_date"]), sold["d_year"], sold["d_moy"],
            sold["d_dom"], sold["d_day_name"]) == (
        "1998-01-02", 1998, 1, 2, "Friday")
    times = tpcds_sf1.time_dim(86_400, rng)
    half_past_four = times.slice(16 * 3600 + 30 * 60, 1).to_pylist()[0]
    assert (half_past_four["t_hour"], half_past_four["t_minute"],
            half_past_four["t_am_pm"]) == (16, 30, "PM")


def test_the_fact_table_at_scale_factor_one():
    counts = tpcds_sf1.SF1_ROWS
    sales = tpcds_sf1.store_sales(counts["store_sales"],
                                  np.random.default_rng(1), counts, 1)
    assert sales.num_rows == 2_880_404 and sales.num_columns == 23
    types = {f.name: f.type for f in sales.schema}
    assert types["ss_ticket_number"] == pa.int64()
    assert types["ss_quantity"] == types["ss_item_sk"] == pa.int32()
    assert sum(t == pa.decimal128(7, 2) for t in types.values()) == 12
    sold = sales.column("ss_sold_date_sk")
    assert 0.03 < sold.null_count / sales.num_rows < 0.05
    assert sales.column("ss_item_sk").null_count == 0
    assert sales.column("ss_ticket_number").null_count == 0
    import pyarrow.compute as pc
    assert pc.count_distinct(sold).as_py() == 1_823
    assert pc.min(sold).as_py() == 2_450_816
    tickets = pc.count_distinct(sales.column("ss_ticket_number")).as_py()
    assert 11 < sales.num_rows / tickets < 13
    row = sales.slice(12_345, 1).to_pylist()[0]
    if None not in (row["ss_net_paid"], row["ss_ext_sales_price"],
                    row["ss_coupon_amt"]):
        assert row["ss_net_paid"] == (row["ss_ext_sales_price"]
                                      - row["ss_coupon_amt"])


def test_a_pool_starts_with_the_constants_the_queries_ask_for():
    assert list(tpcds_sf1.pool("i_manager_id", list(range(1, 101)), 0)) == [
        1, 26, 87]
    whole = tpcds_sf1.pool("i_manufact_id", list(range(1, 1001)), 1000)
    assert sorted(whole) == list(range(1, 1001)) and whole[0] == 816
    item = tpcds_sf1.item(18_000, np.random.default_rng(2))
    assert len(set(item.column("i_manufact_id").to_pylist()) - {None}) == 1000
    assert len(set(item.column("i_manager_id").to_pylist()) - {None}) == 100


# ---- the texts, the files, the configuration ----

@pytest.mark.parametrize("name", NAMES)
def test_a_text_is_the_repositorys_verbatim(name):
    from benchmarks import tpcds_queries as corpus

    assert Q[name].text == corpus.QUERIES[name]
    assert re.search(r"limit\s+100\s*$", Q[name].text.strip(), re.I)
    assert len(Q[name].kinds) >= max(c for c, _ in Q[name].order) + 1


def test_the_reference_shares_no_code_with_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "tpcds_oracle.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "re", "sqlite3", "time", "pyarrow"}


def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == "tpcds-sf1"
    assert cell.entry["chips"] == 1 and cell.mix["driver"] == "sql_q_mix"
    assert cell.module("gen", cell.config["generator"]["kind"]).generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert SQL_METRICS <= mine
    for name in SQL_METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    assert {by_name[n]["source"] for n in ROOFLINES | {"sql_idle_pct"}} == {
        "device_trace"}
    assert {by_name[n]["moves"] for n in SQL_METRICS} == {"op_p50_ms"}
    assert all(by_name[n]["workloads"] == [CELL] for n in SQL_METRICS)
    entry = next(c for c in cell.bench["configs"] if c["name"] == "tpcds-sf1")
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == ["scale", "queries"]


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tpcds-sf1.json")) as f:
        text = f.read()
    assert "DELTA_TPU_" not in text and len(CONFIG["source"]) <= 200
    assert list(CONFIG["reduced"]) == ["scale", "queries"]
    assert len(CONFIG["guarantees"]) == 4
    assert "to the cent" in CONFIG["guarantees"][0]
    assert "1e-9" in CONFIG["guarantees"][0] and "2^-53" in CONFIG["tolerance"]
    assert {"tpcds", "pricing", "values", "filter_constants",
            "null_sold_dates", "writer", "held_snapshots", "on_the_chip",
            "route", "client", "storage", "allocator"} <= set(CONFIG["assumed"])
    assert CONFIG["rows"] == CONFIG["generator"]["rows"] == tpcds_sf1.SF1_ROWS
    assert CONFIG["rows"]["store_sales"] == 2_880_404
    assert CONFIG["rows"]["customer_demographics"] == 1_920_800
    assert CONFIG["generator"]["sold_date_step"] == 1
    assert set(CONFIG["schema"]) == set(tpcds_sf1.SCHEMAS)
    for table, columns in tpcds_sf1.SCHEMAS.items():
        for name, kind in columns:
            assert f"{name} {kind}" in CONFIG["schema"][table], name
    assert CONFIG["schema"]["store_sales"].count("decimal(7,2)") == 12
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deltalog-4m-stream.json")) as f:
        assert CONFIG["environment"] == json.load(f)["environment"]
    listed = MIX["fixture"]["queries"]
    assert 4 <= len(listed) and listed == list(NAMES[:len(listed)])


# ---- the comparison's rules, on built cases ----

TIES = tpcds_queries.Query("ties", "", ("exact", "exact", "cents"),
                           ((2, True), (0, False)))


def _reference(n=150):
    """Rows whose ORDER BY keys tie in threes: (key, tag, cents)."""
    return [(i // 3, f"r{i}", 10_000 - i // 3) for i in range(n)]


def _as_engine(rows):
    return [(k, tag, cents / 100.0) for k, tag, cents in rows]


@pytest.mark.parametrize("case, rows, broken", [
    ("the first hundred", lambda w: w[:100], 0),
    # rows 99, 100 and 101 tie: any one of them may be the hundredth
    ("another of the tie at the cut", lambda w: w[:99] + [w[101]], 0),
    ("a tie in another order", lambda w: w[:3][::-1] + w[3:100], 0),
    ("a row from past the tie at the cut", lambda w: w[:99] + [w[102]], 1),
    ("the same row of a tie twice", lambda w: [w[0], w[0]] + w[2:100], 1),
    ("two rows in the wrong order", lambda w: [w[3], w[0]] + w[1:3]
     + w[4:100], 2),
    ("cut before the sort", lambda w: sorted(w[50:150]), 100),
    ("one row short", lambda w: w[:99], 1),
    ("one row over", lambda w: w[:101], 1),
])
def test_the_limit_takes_the_references_first_hundred(case, rows, broken):
    want = _reference()
    got = _as_engine(rows(want))
    assert tpcds_queries.broken_rows(got, want, TIES) == broken


@pytest.mark.parametrize("case, got, want, kind, same", [
    ("a sum to the cent", 1234.56, 123456, "cents", True),
    ("a sum's last bits", 1234.5600000001, 123456, "cents", True),
    ("a cent off", 1234.57, 123456, "cents", False),
    ("a float32 sum past 2^24 cents", float(np.float32(171234.57)),
     17123457, "cents", False),
    ("an avg in another order of summation", 50.5 * (1 + 5e-13), 50.5,
     "avg", True),
    ("an avg carried in float32", float(np.float32(50.123456)), 50.123456,
     "avg", False),
    ("a null for a null", None, None, "cents", True),
    ("a null for a sum", None, 0, "cents", False),
    ("a NaN for a null", float("nan"), None, "exact", False),
    ("a key that came out a float", 7003001.0, 7003001, "exact", True),
    ("another string", "Bethel", "Summit", "exact", False),
])
def test_a_value_is_compared_by_its_columns_kind(case, got, want, kind, same):
    assert tpcds_queries.same_value(got, want, kind) is same


def test_the_reference_gives_cents_and_units_by_kind():
    table = pa.table({
        "ss_item_sk": pa.array([1, 1, 2], pa.int32()),
        "ss_list_price": tpcds_sf1.decimal_array(
            np.array([1050, 2025, 999]), pa.decimal128(7, 2),
            np.array([False, False, False]))})
    text = ("select ss_item_sk, sum(ss_list_price), avg(ss_list_price) "
            "from store_sales group by ss_item_sk order by ss_item_sk "
            "limit 1")
    oracle = tpcds_oracle.Oracle({"store_sales": table}, [text])
    assert oracle.loaded == {"store_sales": (3, ["ss_item_sk",
                                                 "ss_list_price"])}
    assert oracle.answer(text, ("exact", "cents", "avg_cents")) == [
        (1, 3075, 15.375), (2, 999, 9.99)]


# ---- whole runs of the cell at a test's size ----

def run(trace=False, system=None, seed=2**31 + 17, seconds=0.5):
    return harness.run_cell("tiny-tpcds-q-mix", seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system)


def test_a_run_is_correct_and_reports_its_end_to_end_metrics(capsys):
    result = run()
    assert result["correct"] and result["failed"] == 0
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    out = capsys.readouterr().out
    for name in MIX["fixture"]["queries"]:
        assert f"window {name}.rows: compared" in out
        assert f"window {name}.broken_rows: compared" in out
    assert "mismatches 0 (limit 0)" in out and " pass (median" in out
    assert "reference: SQLite loaded" in out and "warm-up pass 2:" in out
    assert "queries of the window (median s, longest s): q3 " in out
    assert "version 0, " in out and "process RSS at its peak" in out


def test_a_traced_run_reads_the_cells_metrics():
    # the CPU's link is free, so the default gate sends every operator
    # to the kernels: their spans and records are the chip's; there is
    # no device plane here, so the two rooflines have nothing to read
    result = run(trace=True)
    assert result["correct"]
    assert set(result["metrics"]) == SQL_METRICS - ROOFLINES
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["sql_scan_ms"] > 0 and m["sql_join_ms"] > 0
    assert m["sql_agg_sort_ms"] > 0 and m["sql_device_wait_ms"] > 0
    assert m["sql_join_ms"] > m["sql_device_wait_ms"]
    assert m["sql_device_rows_pct"] == 100 and m["sql_fallback_pct"] == 0
    assert m["sql_h2d_mb_per_op"] > 0
    assert m["sql_idle_pct"] == 100


# ---- broken systems: the comparison has to read each not correct ----

class Broken(DeltaTpu):
    """The system, with one function of the SQL executor put out of
    order for the length of a query."""

    patches = ()

    def run_query(self, catalog, query):
        from delta_tpu.sqlengine import executor

        kept = [(name, getattr(executor, name)) for name, _ in self.patches]
        for name, make in self.patches:
            setattr(executor, name, make(getattr(executor, name)))
        try:
            return tpcds_queries.run_query(catalog, query)
        finally:
            for name, original in kept:
                setattr(executor, name, original)


def _nulls_as_values(normalize):
    def broken(frame):
        frame = normalize(frame)
        for c in frame.columns:
            held = frame[c].dropna()
            text = len(held) and isinstance(held.iloc[0], str)
            frame[c] = frame[c].fillna("" if text else 0)
        return frame
    return broken


class NullsAreValues(Broken):
    """Breaks guarantee 3: a null is a value like another (zero, or the
    empty string), so it joins a null, an aggregate counts it and a
    comparison with it holds or does not. It joins null to null; on this
    schema every join ends at a surrogate key, which is never null, in
    dsdgen's data as here, so that alone changes no answer: its averages
    (q7) and its zip codes (q19) do."""

    patches = (("_normalize_frame", _nulls_as_values),)


def _money_as_float32(cast):
    def broken(table):
        table = cast(table)
        for i, field in enumerate(table.schema):
            if field.type == pa.float64() and field.name.startswith("ss_"):
                table = table.set_column(
                    i, field.name, table.column(i).cast(pa.float32()))
        return table
    return broken


class SumsInFloat32(Broken):
    """Breaks guarantee 1: the money of `store_sales` is carried, summed
    and averaged in float32."""

    patches = (("_decimals_as_float64", _money_as_float32),)


class CutsBeforeTheSort(DeltaTpu):
    """Breaks guarantee 2: the LIMIT takes the first hundred rows as
    they come and the ORDER BY sorts those."""

    def run_query(self, catalog, query):
        from delta_tpu.sqlengine import execute_select
        from delta_tpu.sqlengine.parser import parse_query

        parsed = parse_query(query.text)
        [select] = parsed.selects
        select.order_by = []
        answer = execute_select(parsed, catalog=catalog)
        rows = list(zip(*(c.to_pylist() for c in answer.columns)))
        for column, descending in reversed(query.order):
            rows.sort(key=lambda r: (r[column] is not None, r[column])
                      if r[column] is not None else (False, 0),
                      reverse=descending)
        return rows


BROKEN = {"NullsAreValues": NullsAreValues, "SumsInFloat32": SumsInFloat32,
          "CutsBeforeTheSort": CutsBeforeTheSort}


@pytest.mark.parametrize("system", sorted(BROKEN))
def test_a_broken_guarantee_is_not_correct(system, capsys):
    result = run(system=BROKEN[system]())
    assert not result["correct"] and result["failed"] == result["attempted"]
    assert "first mismatch: got" in capsys.readouterr().out


# ---- the readers, on recorded spans ----

MS = 1_000_000
OPS = [{"kind": "pass", "start_unix_ns": 0, "end_unix_ns": 1000 * MS},
       {"kind": "pass", "start_unix_ns": 1000 * MS, "end_unix_ns": 2000 * MS}]


def span(name, at_ms, dur_ms, **attrs):
    return {"name": name, "span_id": f"{name}@{at_ms}", "parent_id": None,
            "start_unix_ns": at_ms * MS, "duration_ns": dur_ms * MS,
            "attrs": attrs, "thread_id": 0}


RECORDED = [
    span("sql.scan", 0, 300), span("sql.scan", 300, 100),
    span("sql.join", 400, 200), span("sql.wait", 450, 50),
    span("sql.join", 600, 100), span("sql.wait", 610, 30),
    span("sql.groupby", 700, 40), span("sql.sort", 740, 10),
    span("sql.scan", 1000, 500), span("sql.join", 1500, 300),
    span("sql.wait", 1600, 120), span("sql.groupby", 1800, 60),
]


def gate(op, rows, chosen, fell_back_to=None, gate_name="sql"):
    return {"gate": gate_name, "chosen": chosen, "reason": "economics",
            "inputs": {"op": op, "n_rows": rows},
            "fell_back_to": fell_back_to}


GATES = [gate("query", 1, "device"), gate("join", 3_000_000, "device"),
         gate("join", 200_000, "host"), gate("group-agg", 100_000, "host"),
         gate("join", 700_000, "device", "host"),
         gate("skip", 5, "device", gate_name="skip")]
LANES = {"kernel": "sqlops.join_lanes", "h2d_bytes": 25_165_824,
         "attrs": {"nl_pad": 3_145_728, "nr_pad": 131_072}}
CODES = {"kernel": "sqlops.join_codes", "h2d_bytes": 12_582_912,
         "attrs": {"n_pad": 3_145_728}}
UPLOAD = {"kernel": "sql.operand_upload", "h2d_bytes": 1_048_576}
OTHER = {"kernel": "skipping.mask_block", "h2d_bytes": 999}
EVENTS = [("jit_sqlops_join_lanes/sort.1", 0, 30 * MS),
          ("jit_sqlops_join_lanes/gather.2", 20 * MS, 50 * MS),
          ("jit_sqlops_join_lanes/sort.1", 100 * MS, 150 * MS),
          ("jit_sqlops_join_codes/sort.3", 200 * MS, 240 * MS),
          ("jit_skipping_mask_block/fusion", 300 * MS, 900 * MS)]


def recorded(spans=RECORDED, gates=GATES, dispatches=(LANES, LANES, CODES,
                                                      UPLOAD, OTHER),
             events=EVENTS):
    trace = types.SimpleNamespace(events=[list(events)] if events else [],
                                  busy_s=0.5, window_s=2.0)
    return types.SimpleNamespace(ops=OPS, spans=list(spans), trace=trace,
                                 gates=list(gates),
                                 dispatches=list(dispatches),
                                 device_kind="TPU v5 lite")


LANES_LEAST = (3_145_728 + 131_072) * (8 + 4 + 1) / 819e9
CODES_LEAST = 3_145_728 * (4 + 4 + 1) / 819e9


@pytest.mark.parametrize("name, want", [
    ("sql_scan_ms", (400 + 500) / 2),       # a pass's scans, the median
    ("sql_join_ms", (300 + 300) / 2),
    ("sql_agg_sort_ms", (50 + 60) / 2),
    ("sql_device_wait_ms", (80 + 120) / 2),
    # of 4.0M rows priced, 3.7M sent to the chip; the query's own
    # decision and another gate's are no operator's
    ("sql_device_rows_pct", 100 * 3.7 / 4.0),
    ("sql_fallback_pct", 50),               # one of the two sent there
    ("sql_h2d_mb_per_op", (2 * 25_165_824 + 12_582_912 + 1_048_576)
     / 1e6 / 2),
    # two launches' least bytes over the 100 ms their operations cover
    ("sql_join_roofline", 100 * 2 * LANES_LEAST / 100e-3),
    ("sql_join_codes_roofline", 100 * CODES_LEAST / 40e-3),
    ("sql_idle_pct", 75),
])
def test_a_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_a_join_rooflines_share_is_under_a_hundred_by_construction():
    assert 0 < reader("sql_join_roofline")(recorded()) < 100
    assert 0 < reader("sql_join_codes_roofline")(recorded()) < 100


@pytest.mark.parametrize("name, kwargs", [
    # the parent: no sql.* span, no shapes on its records
    ("sql_scan_ms", dict(spans=[])), ("sql_join_ms", dict(spans=[])),
    ("sql_agg_sort_ms", dict(spans=[])),
    ("sql_device_wait_ms", dict(spans=[])),
    ("sql_device_rows_pct", dict(gates=[])),
    ("sql_device_rows_pct", dict(gates=GATES[:1] + GATES[-1:])),
    ("sql_fallback_pct", dict(gates=[gate("join", 9, "host")])),
    ("sql_h2d_mb_per_op", dict(dispatches=[OTHER])),
    ("sql_join_roofline", dict(dispatches=[dict(LANES, attrs={})])),
    ("sql_join_roofline", dict(dispatches=[{"kernel": "sqlops.join_lanes",
                                            "h2d_bytes": 1}])),
    ("sql_join_roofline", dict(dispatches=[CODES])),
    ("sql_join_roofline", dict(events=())),     # no device plane
    ("sql_join_codes_roofline", dict(dispatches=[LANES])),
    ("sql_join_codes_roofline", dict(events=EVENTS[:3])),
])
def test_a_reader_finds_nothing_on_a_program_without_its_spans(name, kwargs):
    assert reader(name)(recorded(**kwargs)) is None


if __name__ == "__main__":      # the cell itself, on the chip, broken
    import argparse

    t0 = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("system", choices=sorted(BROKEN))
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    asked = parser.parse_args()
    result = harness.run_cell(CELL, asked.seed, asked.seconds, False, t0,
                              system=BROKEN[asked.system]())
    print(json.dumps({"system": asked.system, "cell": CELL,
                      "seed": asked.seed, "correct": result["correct"],
                      "has_to_read": False,
                      "attempted": result["attempted"],
                      "failed": result["failed"],
                      "device": result["device"]}), flush=True)
    raise SystemExit(result["correct"] is not False)
