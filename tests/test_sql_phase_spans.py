"""What PR 51 put under the spans of a query: `scan.assemble` and
`sql.frame` beside `scan.plan` and `scan.read` under `sql.scan`, a
`scan.read_run` a task of a dealt scan (sums of `open_ms` / `decode_ms`
by `Span.timed`, the task thread's `cpu_ms`), `join.nulls` /
`join.encode` / `join.expand` / `join.gather` round `sql.wait` under a
device-route `sql.join`, and nothing of it, not a clock read, where
tracing is off."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import obs
from delta_tpu.catalog import Catalog
from delta_tpu.engine import host
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.obs import trace
from delta_tpu.sqlengine import execute_select

QUERY = """select d_year, sum(price) total, count(*) n
 from dim, fact where fact.day_sk = dim.d_sk and dim.d_moy = 11
 group by d_year order by d_year limit 100"""
DAYS, ROWS = 40, 400_000
SCAN_PHASES = ["scan.plan", "scan.read", "scan.assemble", "sql.frame"]
JOIN_PHASES = ["join.nulls", "join.encode", "sql.wait", "join.expand",
               "join.gather"]


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """A fact table of `DAYS` + 1 files (a day a partition, and the
    null's) wide enough to time, and a dimension of one file."""
    rng = np.random.default_rng(51)
    day = rng.integers(0, DAYS, ROWS)
    fact = pa.table({
        "day_sk": pa.array(np.where(rng.random(ROWS) < 0.02, None, day),
                           pa.int32()),
        "qty": pa.array(rng.integers(1, 101, ROWS), pa.int32()),
        "price": pa.array(rng.random(ROWS) * 100.0),
        "cost": pa.array(rng.random(ROWS) * 50.0)})
    dim = pa.table({
        "d_sk": pa.array(np.arange(DAYS), pa.int32()),
        "d_year": pa.array(1998 + np.arange(DAYS) // 20, pa.int32()),
        "d_moy": pa.array(np.where(np.arange(DAYS) % 2, 11, 12), pa.int32())})
    root = str(tmp_path_factory.mktemp("star"))
    engine = TpuEngine()
    catalog = Catalog(root, engine=engine)
    for name, data, parts in (("fact", fact, ["day_sk"]), ("dim", dim, None)):
        dta.write_table(f"{root}/{name}", data, partition_by=parts,
                        engine=engine)
        catalog.register(name, f"{root}/{name}")
    return catalog


@pytest.fixture
def dealt(monkeypatch):
    """Any batch of two files or more goes to the scan pool's four."""
    monkeypatch.setenv("DELTA_TPU_SCAN_THREADS", "4")
    monkeypatch.setattr(host, "_RUN_MIN_BYTES", 1)


@pytest.fixture
def tracing():
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    yield
    obs.set_trace_mode("off")
    obs.reset_trace_buffer()


def _spans():
    return [s.to_dict() for s in obs.get_finished_spans()]


def _children(spans, parent):
    return sorted((s for s in spans if s["parent_id"] == parent["span_id"]),
                  key=lambda s: s["start_unix_ns"])


def _one(spans, name, **attrs):
    [found] = [s for s in spans if s["name"] == name and all(
        s["attrs"].get(k) == v for k, v in attrs.items())]
    return found


@pytest.mark.parametrize("route", ["host", "device"])
def test_a_source_is_its_four_phases_and_little_else(
        star, dealt, tracing, monkeypatch, route):
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL",
                       "force" if route == "device" else "off")
    covered = []
    for _ in range(3):      # a share of a few ms: the best of three
        obs.reset_trace_buffer()
        execute_select(QUERY, catalog=star)
        spans = _spans()
        scan = _one(spans, "sql.scan", table="fact")
        phases = _children(spans, scan)
        assert [s["name"] for s in phases] == SCAN_PHASES
        covered.append(sum(s["duration_ns"] for s in phases)
                       / scan["duration_ns"])
    assert max(covered) >= 0.95
    _plan, read, assemble, frame = phases
    assert read["attrs"]["files"] == scan["attrs"]["files"] == DAYS + 1
    assert assemble["attrs"] == {
        "batches": DAYS + 1, "filtered": False, "rows_in": ROWS,
        "rows": ROWS}
    assert frame["attrs"]["rows"] == scan["attrs"]["rows"] == ROWS
    assert frame["attrs"]["columns"] == 2       # day_sk, price
    assert frame["attrs"]["decimal_columns"] == 0
    assert frame["attrs"]["bytes"] >= ROWS * 12
    # the dimension's pushed conjunct runs as the scan's residual filter
    dim = _one(spans, "sql.scan", table="dim")
    assert [s["name"] for s in _children(spans, dim)] == SCAN_PHASES
    kept = _children(spans, dim)[2]["attrs"]
    assert kept["filtered"] is True
    assert (kept["rows_in"], kept["rows"]) == (DAYS, DAYS // 2)


def test_a_dealt_scan_says_what_its_tasks_did(star, dealt, tracing):
    with obs.span("caller"):
        table = star.table("fact").latest_snapshot().scan(
            columns=["qty", "price"]).to_arrow()
    spans = _spans()
    read = _one(spans, "scan.read")
    runs = [s for s in spans if s["name"] == "scan.read_run"]
    assert read["attrs"]["inline"] is False
    assert len(runs) == read["attrs"]["tasks"] > 1
    assert {s["parent_id"] for s in runs} == {read["span_id"]}
    assert read["thread_id"] == threading.get_ident()
    assert all(s["thread_name"].startswith("delta-tpu-scan") for s in runs)
    assert sum(s["attrs"]["files"] for s in runs) == read["attrs"]["files"]
    assert sum(s["attrs"]["rows"] for s in runs) == table.num_rows == ROWS
    assert sum(s["attrs"]["bytes"] for s in runs) >= ROWS * 12
    for s in runs:
        took = s["duration_ns"] / 1e6
        assert 0 < s["attrs"]["open_ms"] and 0 < s["attrs"]["decode_ms"]
        assert s["attrs"]["open_ms"] + s["attrs"]["decode_ms"] <= took
        assert 0 <= s["attrs"]["cpu_ms"]
    # the driving thread waited for the pool, and timed no file itself
    assert 0 < read["attrs"]["wait_ms"] <= read["duration_ns"] / 1e6
    assert "open_ms" not in read["attrs"]


def test_an_inline_scan_times_its_files_on_scan_read(star, tracing):
    table = star.table("dim").latest_snapshot().scan().to_arrow()
    spans = _spans()
    read = _one(spans, "scan.read")
    assert table.num_rows == DAYS and read["attrs"]["inline"] is True
    assert not [s for s in spans if s["name"] == "scan.read_run"]
    assert 0 < read["attrs"]["open_ms"] and 0 < read["attrs"]["decode_ms"]
    assert (read["attrs"]["open_ms"] + read["attrs"]["decode_ms"]
            <= read["duration_ns"] / 1e6)
    assert "wait_ms" not in read["attrs"] and "cpu_ms" not in read["attrs"]


@pytest.mark.parametrize("route", ["host", "device"])
def test_a_device_join_is_its_phases_and_a_host_join_its_merge(
        star, tracing, monkeypatch, route):
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL",
                       "force" if route == "device" else "off")
    execute_select(QUERY, catalog=star)
    spans = _spans()
    join = _one(spans, "sql.join")
    assert join["attrs"]["route"] == route
    phases = _children(spans, join)
    if route == "host":
        assert phases == []
        # no spine may take it: the null check is a span of `verbose`
        obs.set_trace_mode("verbose")
        obs.reset_trace_buffer()
        execute_select(QUERY, catalog=star)
        spans = _spans()
        assert [s["name"] for s in _children(
            spans, _one(spans, "sql.join"))] == ["join.nulls"]
        return
    assert [s["name"] for s in phases] == JOIN_PHASES
    by_name = {s["name"]: s["attrs"] for s in phases}
    n_fact = join["attrs"]["n_right"]
    matched = join["attrs"]["rows"]
    assert by_name["join.encode"]["kind"] in ("lanes", "codes")
    assert by_name["join.encode"]["rows"] in (
        join["attrs"]["n_left"], join["attrs"]["n_left"] + n_fact)
    assert by_name["join.expand"]["pairs"] == matched
    assert by_name["join.gather"]["rows"] == matched
    assert by_name["join.gather"]["columns"] == 3 + 2
    assert sum(s["duration_ns"] for s in phases) <= join["duration_ns"]


def test_with_tracing_off_a_scan_reads_no_clock(star, dealt, monkeypatch):
    """Off, a task's span is the shared no-op and so is its `timed`: no
    `perf_counter_ns` for a file, no `thread_time_ns` for a task."""
    assert not obs.trace_enabled()
    with obs.span("scan.read_run") as task:
        assert task is trace._NOOP_SPAN and not task.recording
        assert task.timed("open_ms") is trace._NOOP_CTX
        with task.timed("open_ms"):
            pass

    def no_clock():
        raise AssertionError("a clock was read with tracing off")

    monkeypatch.setattr(time, "thread_time_ns", no_clock)
    monkeypatch.setattr(trace, "_Timed", no_clock)
    fact = star.table("fact").latest_snapshot().scan(
        columns=["qty"]).to_arrow()
    dim = star.table("dim").latest_snapshot().scan().to_arrow()
    assert (fact.num_rows, dim.num_rows) == (ROWS, DAYS)
    assert obs.get_finished_spans() == []


def test_timed_adds_up_and_survives_an_exception(tracing):
    with obs.span("work") as sp:
        busy = sp.timed("busy_ms")      # kept and entered again, as a task's
        for _ in range(3):
            with busy:
                time.sleep(0.002)
        after_three = sp.attrs["busy_ms"]
        with pytest.raises(KeyError):
            with sp.timed("busy_ms"):
                time.sleep(0.002)
                raise KeyError("inside")
        with sp.timed("other_ms"):
            pass
    assert 6.0 <= after_three < sp.attrs["busy_ms"] - 1.9
    assert 0 <= sp.attrs["other_ms"] < 1.0
    [work] = _spans()
    assert work["status"] == "ok"
    assert work["attrs"]["busy_ms"] <= work["duration_ns"] / 1e6
