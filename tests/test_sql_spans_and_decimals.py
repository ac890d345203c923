"""What PR 49 gave the SQL engine to be measured by: the spans of a
query (`sql.query`, `sql.scan`, `sql.join`, `sql.groupby`, `sql.sort`,
`sql.wait`), the counter `sql.scan_files`, the shapes on the `sqlops.*`
dispatch records, and a `decimal(p,s)` column that reaches the frame
exact to the cent without a Python `Decimal` a value."""

import decimal

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import obs
from delta_tpu.catalog import Catalog
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.sqlengine import execute_select
from delta_tpu.sqlengine.executor import _decimals_as_float64

QUERY = """select d_year, sum(price) total, avg(qty) mean_qty
 from dim, fact where fact.day_sk = dim.d_sk and dim.d_moy = 11
 group by d_year order by d_year, total desc limit 100"""


def _cents(rng, n):
    return rng.integers(-9_999_999, 10_000_000, n)


def _money(cents, nulls=None):
    values = [decimal.Decimal(int(c)).scaleb(-2) for c in cents]
    if nulls is not None:
        values = [None if m else v for v, m in zip(values, nulls)]
    return pa.array(values, pa.decimal128(7, 2))


@pytest.fixture
def star(tmp_path, monkeypatch):
    rng = np.random.default_rng(3)
    n = 4_000
    day = rng.integers(0, 60, n)
    fact = pa.table({
        "day_sk": pa.array(np.where(rng.random(n) < 0.04, None, day),
                           pa.int32()),
        "qty": pa.array(rng.integers(1, 101, n), pa.int32()),
        "price": _money(_cents(rng, n), rng.random(n) < 0.04)})
    dim = pa.table({
        "d_sk": pa.array(np.arange(60), pa.int32()),
        "d_year": pa.array(1998 + np.arange(60) // 20, pa.int32()),
        "d_moy": pa.array(np.where(np.arange(60) % 2, 11, 12), pa.int32())})
    root = str(tmp_path)
    catalogs = {}
    for name, engine in (("host", HostEngine()), ("tpu", TpuEngine())):
        cat = Catalog(root + "/" + name, engine=engine)
        for table, data, parts in (("fact", fact, ["day_sk"]),
                                   ("dim", dim, None)):
            path = f"{root}/{name}/{table}"
            dta.write_table(path, data, partition_by=parts, engine=engine)
            cat.register(table, path)
        catalogs[name] = cat
    yield catalogs, fact, dim
    obs.set_trace_mode("off")
    obs.set_device_obs_mode("off")


def _traced(query, catalog, **kw):
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    obs.reset_trace_buffer()
    obs.reset_device_obs()
    before = obs.counter("sql.scan_files").value
    out = execute_select(query, catalog=catalog, **kw)
    spans = [s.to_dict() for s in obs.get_finished_spans()]
    return out, spans, obs.counter("sql.scan_files").value - before


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize("route", ["host", "device"])
def test_a_query_leaves_its_spans(star, monkeypatch, route):
    catalogs, fact, _dim = star
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL",
                       "force" if route == "device" else "off")
    out, spans, files = _traced(QUERY, catalogs["tpu"], name="q-star")
    [query] = _named(spans, "sql.query")
    assert query["attrs"]["name"] == "q-star"
    assert query["attrs"]["rows"] == out.num_rows == 3
    scans = {s["attrs"]["table"]: s["attrs"] for s in _named(spans, "sql.scan")}
    assert set(scans) == {"dim", "fact"}
    assert scans["dim"]["pushed"] == 1 and scans["fact"]["pushed"] == 0
    assert scans["fact"]["files"] == 61      # sixty days and the null's
    assert scans["fact"]["rows"] == fact.num_rows
    assert scans["fact"]["columns"] == 3
    assert files == 62
    [join] = _named(spans, "sql.join")
    assert join["attrs"]["route"] == route
    assert join["attrs"]["how"] == "inner"
    assert join["attrs"]["n_left"] == 30
    assert join["attrs"]["n_right"] == fact.num_rows
    [group] = _named(spans, "sql.groupby")
    assert group["attrs"]["route"] == route and group["attrs"]["groups"] == 3
    [sort] = _named(spans, "sql.sort")
    assert sort["attrs"]["route"] == route and sort["attrs"]["rows"] == 3
    waits = _named(spans, "sql.wait")
    assert bool(waits) == (route == "device")
    if route == "device":
        assert {w["attrs"]["kernel"] for w in waits} >= {
            "sqlops.join_lanes", "sqlops.sort", "sqlops.segagg"}
        lanes = [r for r in obs.get_dispatch_records()
                 if r["kernel"] == "sqlops.join_lanes"]
        assert lanes and all(
            {"nl_pad", "nr_pad", "n_l", "n_r", "bits"} <= set(r["attrs"])
            for r in lanes)


def test_every_substrate_sums_decimals_to_the_cent(star, monkeypatch):
    catalogs, fact, dim = star
    price = fact.column("price").to_pylist()
    day = fact.column("day_sk").to_pylist()
    keep = {sk for sk, moy in zip(dim.column("d_sk").to_pylist(),
                                  dim.column("d_moy").to_pylist())
            if moy == 11}
    year = dict(zip(dim.column("d_sk").to_pylist(),
                    dim.column("d_year").to_pylist()))
    want = {}
    for d, p in zip(day, price):
        if d in keep and p is not None:
            want[year[d]] = want.get(year[d], 0) + int(p.scaleb(2))
    answers = [execute_select(QUERY, catalog=catalogs["host"]),
               execute_select(QUERY, catalog=catalogs["tpu"])]
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL", "force")
    answers.append(execute_select(QUERY, catalog=catalogs["tpu"]))
    for out in answers:
        got = dict(zip(out.column("d_year").to_pylist(),
                       (round(t * 100) for t in
                        out.column("total").to_pylist())))
        assert got == want


@pytest.mark.parametrize("precision, scale", [(7, 2), (15, 4), (5, 0)])
def test_a_decimal_column_is_the_nearest_double(precision, scale):
    rng = np.random.default_rng(precision)
    top = 10 ** precision
    unscaled = rng.integers(-top + 1, top, 5_000)
    nulls = rng.random(5_000) < 0.1
    values = [None if m else decimal.Decimal(int(u)).scaleb(-scale)
              for u, m in zip(unscaled, nulls)]
    column = pa.chunked_array([
        pa.array(values[:3_000], pa.decimal128(precision, scale)),
        pa.array(values[1_000:], pa.decimal128(precision, scale)).slice(2_000)])
    out = _decimals_as_float64(pa.table({"m": column, "k": range(5_000)}))
    assert out.schema.field("m").type == pa.float64()
    assert out.schema.field("k").type == pa.int64()
    assert out.column("m").to_pylist() == [
        None if v is None else float(v) for v in values]


def test_a_decimal_past_fifteen_digits_is_near():
    values = [decimal.Decimal("12345678901234567.89"), None,
              decimal.Decimal("-0.01")]
    out = _decimals_as_float64(
        pa.table({"m": pa.array(values, pa.decimal128(20, 2))}))
    got = out.column("m").to_pylist()
    assert got[1] is None
    assert got[0] == pytest.approx(float(values[0]), rel=1e-15)
    assert got[2] == pytest.approx(-0.01, rel=1e-15)
