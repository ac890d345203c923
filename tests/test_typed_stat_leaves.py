"""Stat leaves typed by the table's schema (`stats/skipping.py`,
`stats/device_index.py`): a Delta `timestamp`'s stats reach the lanes as
UTC microseconds, its max widened by the writer's millisecond, in the
index, its numpy twin, the kernel and the Arrow ladder alike; what stays
off the lanes is counted; a literal of the wrong kind keeps and is
counted."""

import datetime as dt
import json
import threading

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.expressions import col, lit
from delta_tpu.models.actions import Metadata
from delta_tpu.models.schema import StructField, StructType, PrimitiveType
from delta_tpu.stats import collection
from delta_tpu.stats.device_index import (append_index, build_index,
                                          encode_literal)
from delta_tpu.stats.skipping import (StatsIndex, skipping_mask,
                                      stat_leaf_types)

UTC = dt.timezone.utc
T0 = dt.datetime(2024, 1, 1, tzinfo=UTC)
US0 = int((T0 - dt.datetime(1970, 1, 1, tzinfo=UTC)).total_seconds()) * 10**6


def metadata_of(*fields, configuration=None):
    schema = StructType([StructField(name, PrimitiveType(kind))
                         for name, kind in fields])
    return Metadata(id="t", schemaString=json.dumps(schema.to_json_value()),
                    partitionColumns=[], configuration=configuration or {})


def stats_row(least, most, records=10):
    return json.dumps({"numRecords": records, "minValues": least,
                       "maxValues": most,
                       "nullCount": {k: 0 for k in least}},
                      separators=(",", ":"))


def files_of(*rows):
    return pa.table({"stats": pa.array(list(rows), pa.string())})


BIDS = metadata_of(("auction", "long"), ("dateTime", "timestamp"),
                   ("channel", "string"))


def counter(name):
    return obs.counter(name).value


# ---- which leaves the schema types, and how a timestamp's stats read ----

def test_leaf_types_follow_the_schema_and_its_physical_names():
    assert stat_leaf_types(BIDS) == {("auction",): "long",
                                     ("dateTime",): "timestamp",
                                     ("channel",): "string"}
    nested = StructType([
        StructField("a", StructType([
            StructField("t", PrimitiveType("timestamp_ntz"),
                        metadata={"delta.columnMapping.physicalName": "c2"}),
            StructField("d", PrimitiveType("decimal(10,2)"))]),
            metadata={"delta.columnMapping.physicalName": "c1"})])
    text = json.dumps(nested.to_json_value())
    plain = Metadata(id="t", schemaString=text, partitionColumns=[],
                     configuration={})
    mapped = Metadata(id="t", schemaString=text, partitionColumns=[],
                      configuration={"delta.columnMapping.mode": "name"})
    assert stat_leaf_types(plain) == {("a", "t"): "timestamp_ntz",
                                      ("a", "d"): "decimal(10,2)"}
    assert stat_leaf_types(mapped) == {("c1", "c2"): "timestamp_ntz",
                                       ("c1", "d"): "decimal(10,2)"}


FORMS = {   # the instant 2024-01-01T00:00:08.7Z, as writers spell it
    "upstream-Z": "2024-01-01T00:00:08.700Z",
    "upstream-offset": "2024-01-01T00:00:08.700+00:00",
    "microseconds": "2024-01-01T00:00:08.700000+00:00",
    "old-delta-tpu": "2024-01-01T00:00:08.700000+0000",
    "another-zone": "2023-12-31T17:00:08.700-07:00",
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_timestamp_stat_reads_as_its_utc_instant_max_plus_1_ms(form):
    row = stats_row({"dateTime": FORMS[form]}, {"dateTime": FORMS[form]})
    untyped = StatsIndex.from_stats_column(files_of(row).column("stats"))
    assert pa.types.is_string(untyped.min_values(("dateTime",)).type)
    typed = StatsIndex.from_stats_column(
        files_of(row).column("stats"), leaf_types=stat_leaf_types(BIDS))
    least, most = (typed.min_values(("dateTime",)),
                   typed.max_values(("dateTime",)))
    assert least.type == most.type == pa.timestamp("us", tz="UTC")
    assert least.cast(pa.int64()).to_pylist() == [US0 + 8_700_000]
    assert most.cast(pa.int64()).to_pylist() == [US0 + 8_701_000]


def test_a_timestamp_ntz_stat_reads_zone_less_and_is_widened_too():
    md = metadata_of(("t", "timestamp_ntz"))
    row = stats_row({"t": "2024-01-01T00:00:08.700"},
                    {"t": "2024-01-01T00:00:09.123"})
    typed = StatsIndex.from_stats_column(files_of(row).column("stats"),
                                         leaf_types=stat_leaf_types(md))
    assert typed.min_values(("t",)).type == pa.timestamp("us")
    assert typed.max_values(("t",)).cast(pa.int64()).to_pylist() == [
        US0 + 9_124_000]
    idx = build_index(files_of(row), metadata=md)
    assert idx.cols == {("t",): (0, "ts")}


def test_a_table_without_a_timestamp_parses_as_it_did():
    md = metadata_of(("x", "long"), ("s", "string"))
    rows = [stats_row({"x": i, "s": "a"}, {"x": i + 5, "s": "b"})
            for i in range(4)]
    before = build_index(files_of(*rows))
    after = build_index(files_of(*rows), metadata=md)
    assert after.arrow_index._table.equals(before.arrow_index._table)
    assert after.cols == before.cols == {("x",): (0, "int")}
    assert np.array_equal(after.vals, before.vals)
    assert np.array_equal(after.valid, before.valid)
    assert after.unindexed == {"string": 1}


# ---- lanes: which types get one, and what is counted ----

def test_lanes_for_numbers_and_times_and_a_count_of_the_rest():
    md = metadata_of(("auction", "long"), ("dateTime", "timestamp"),
                     ("channel", "string"), ("amount", "decimal(20,2)"),
                     ("when", "timestamp"))
    rows = [stats_row(
        {"auction": i, "dateTime": FORMS["upstream-Z"], "channel": "a",
         "amount": 1.25, "when": "yesterday"},
        {"auction": i + 9, "dateTime": FORMS["upstream-Z"], "channel": "b",
         "amount": 9.75, "when": "tomorrow"}) for i in range(3)]
    idx = build_index(files_of(*rows), metadata=md)
    assert idx.cols == {("auction",): (0, "int"), ("dateTime",): (3, "tstz")}
    assert idx.vals.shape[0] == 7       # 2 columns x 3 + numRecords
    assert idx.unindexed == {"string": 1, "decimal": 1, "unparsed": 1}
    assert idx.vals[3, :3].tolist() == [US0 + 8_700_000] * 3
    assert idx.vals[4, :3].tolist() == [US0 + 8_701_000] * 3
    # with no schema, as before: text stays text, a decimal reads as a float
    plain = build_index(files_of(*rows))
    assert set(plain.cols) == {("auction",), ("amount",)}
    assert plain.unindexed == {"string": 3}


@pytest.mark.parametrize("value,kind,want", [
    (T0, "tstz", US0),
    (T0.astimezone(dt.timezone(dt.timedelta(hours=-7))), "tstz", US0),
    (T0.replace(tzinfo=None), "tstz", None),     # no session zone assumed
    ("2024-01-01T00:00:00Z", "tstz", None),
    (dt.date(2024, 1, 1), "tstz", None),
    (T0, "ts", None),
    (T0.replace(tzinfo=None), "ts", US0),
    (dt.date(2024, 1, 1), "ts", US0),
])
def test_a_literal_meets_only_a_lane_of_its_own_kind(value, kind, want):
    assert encode_literal(value, kind) == want


# ---- planning by time: ladder, twin and kernel agree ----

def bid_rows(n=6):
    """File i holds the events of [10 i, 10 i + 1.7] s after T0; its
    stored max is truncated to the millisecond as a writer leaves it."""
    rows = []
    for i in range(n):
        lo = T0 + dt.timedelta(seconds=10 * i)
        hi = lo + dt.timedelta(seconds=1.7)
        rows.append(stats_row(
            {"auction": 100 * i, "dateTime": lo.isoformat(
                timespec="milliseconds"), "channel": "Apple"},
            {"auction": 100 * i + 99, "dateTime": hi.isoformat(
                timespec="milliseconds"), "channel": "channel-99"}))
    return files_of(*rows)


def after(seconds):
    return T0 + dt.timedelta(seconds=seconds)


class HeldState:
    """What `snapshot_stats_index` needs of a `SnapshotState`."""

    def __init__(self, files):
        self.add_files_table = files
        self.stats_index = self.stats_index_seed = None
        self._stats_index_lock = threading.Lock()
        self.live_mask = np.ones(files.num_rows, bool)


WINDOWS = {   # predicate, the files it has to keep
    "window": ((col("dateTime") >= lit(after(20)))
               & (col("dateTime") < lit(after(30))), [2]),
    "non-utc-offset": ((col("dateTime") >= lit(after(20).astimezone(
        dt.timezone(dt.timedelta(hours=5, minutes=30)))))
        & (col("dateTime") < lit(after(30))), [2]),
    "inside-the-truncated-millisecond": (
        col("dateTime") >= lit(after(51.7005)), [5]),
    "past-the-truncated-millisecond": (
        col("dateTime") > lit(after(51.701)), []),
    "at-the-widened-max": (col("dateTime") >= lit(after(51.701)), [5]),
    "before-all": (col("dateTime") < lit(after(-1)), []),
    "equals": (col("dateTime") == lit(after(31)), [3]),
    "with-a-selection": ((col("dateTime") >= lit(after(0)))
                         & col("auction").is_in(150, 420), [1, 4]),
    "zone-less-literal-keeps": (
        col("dateTime") >= lit(after(20).replace(tzinfo=None)),
        [0, 1, 2, 3, 4, 5]),
    "text-on-the-ladder": ((col("dateTime") >= lit(after(20)))
                           & (col("channel") == lit("Baidu")), [2, 3, 4, 5]),
}


@pytest.mark.parametrize("route", ["ladder", "twin", "kernel"])
@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_every_route_keeps_the_same_files(monkeypatch, case, route):
    from delta_tpu.expressions.tree import split_conjuncts

    pred, want = WINDOWS[case]
    files = bid_rows()
    state = None
    if route != "ladder":
        monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP",
                           "force" if route == "kernel" else "off")
        state = HeldState(files)
    before = counter("scan.skip_uncompared_conjuncts")
    keep = skipping_mask(files, split_conjuncts(pred), BIDS, state=state)
    assert np.flatnonzero(keep).tolist() == want
    refused = counter("scan.skip_uncompared_conjuncts") - before
    assert refused == (1 if case == "zone-less-literal-keeps" else 0)
    if state is not None:
        assert state.stats_index.cols == {("auction",): (0, "int"),
                                          ("dateTime",): (3, "tstz")}


def test_with_no_schema_a_time_is_text_and_keeps_everything_counted():
    """What the conflict checker's subsets get when no metadata is
    given: inference, as before; now the refusal is counted."""
    from delta_tpu.expressions.tree import split_conjuncts

    before = counter("scan.skip_uncompared_conjuncts")
    keep = skipping_mask(bid_rows(), split_conjuncts(WINDOWS["window"][0]),
                         None)
    assert keep.all()
    assert counter("scan.skip_uncompared_conjuncts") - before == 2


# ---- the index carried over landings ----

def test_an_appended_index_equals_one_built_in_full():
    files = bid_rows(8)
    first = build_index(files.slice(0, 5), metadata=BIDS)
    seed = first.seed(np.ones(5, bool))
    live = np.ones(8, bool)
    live[[1, 3]] = False
    now = files.filter(pa.array(live))
    appended, attrs = append_index(seed, live, now.column("stats").slice(3),
                                   metadata=BIDS)
    assert attrs == {"rows": 3, "dropped": 2}
    full = build_index(now, metadata=BIDS)
    assert appended.cols == full.cols and appended.unindexed == full.unindexed
    n = now.num_rows
    assert np.array_equal(appended.vals[:, :n], full.vals[:, :n])
    assert np.array_equal(appended.valid[:, :n], full.valid[:, :n])
    assert appended.arrow_index._table.equals(full.arrow_index._table)
    assert appended.vals[4, n - 1] == US0 + 71_701_000   # the tail, widened


# ---- written by delta_tpu, read back, planned by time ----

def test_the_writers_timestamp_form_is_one_the_reader_takes():
    aware = dt.datetime(2024, 1, 1, 0, 0, 8, 700000, tzinfo=UTC)
    assert collection._json_value(aware) == (
        "2024-01-01T00:00:08.700000+00:00")
    assert collection._json_value(aware.replace(tzinfo=None)) == (
        "2024-01-01T00:00:08.700000")
    assert collection._json_value(dt.date(2024, 1, 1)) == "2024-01-01"


@pytest.fixture
def bid_table(tmp_path):
    path = str(tmp_path / "bids")
    for b in range(6):
        start = T0 + dt.timedelta(seconds=10 * b)
        times = [start + dt.timedelta(microseconds=1700 * i)
                 for i in range(1000)]
        dta.write_table(path, pa.table({
            "auction": pa.array(np.arange(1000) + 1000 * b, pa.int64()),
            "dateTime": pa.array(times, pa.timestamp("us", tz="UTC")),
            "channel": pa.array([f"channel-{i % 7}" for i in range(1000)]),
        }), mode="append" if b else "error", engine=HostEngine())
    return path


@pytest.mark.parametrize("engine,route", [
    (HostEngine, "off"), (TpuEngine, "off"), (TpuEngine, "force")])
def test_a_table_delta_tpu_wrote_skips_files_by_time(
        bid_table, monkeypatch, engine, route):
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", route)
    snap = Table.for_path(bid_table, engine=engine()).latest_snapshot()
    assert snap.metadata.schema["dateTime"].dataType.name == "timestamp"
    stats = json.loads(
        snap.state.add_files_table.column("stats")[0].as_py())
    assert stats["minValues"]["dateTime"].endswith("+00:00")
    pred = (col("dateTime") >= lit(after(20))) & (
        col("dateTime") < lit(after(30)))
    scan = snap.scan(filter=pred)
    assert len(scan.file_paths()) == 1 and scan.skipped_by_stats == 5
    rows = scan.to_arrow()
    assert rows.num_rows == 1000
    assert set(rows.column("auction").to_pylist()) == set(range(2000, 3000))
    # the window's edge inside a file's range keeps it
    assert len(snap.scan(filter=col("dateTime") >= lit(
        after(51))).file_paths()) == 1


# ---- spans and counters ----

def test_the_spans_say_what_the_index_holds_and_what_a_launch_reads(
        bid_table, monkeypatch):
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    snap = Table.for_path(bid_table, engine=TpuEngine()).latest_snapshot()
    pred = ((col("dateTime") >= lit(after(20)))
            & (col("dateTime") < lit(after(40)))
            & col("auction").is_in(2100, 2200, 3300, 3400, 9999))
    strings = counter("scan.stats_index_unindexed_leaves.string")
    obs.set_trace_mode("on")
    obs.set_device_obs_mode("on")
    obs.reset_trace_buffer()
    obs.reset_device_obs()
    try:
        assert len(snap.scan(filter=pred).file_paths()) == 2
        assert len(snap.scan(filter=col("dateTime") >= lit(
            after(20).replace(tzinfo=None))).file_paths()) == 6
        spans = [s.to_dict() for s in obs.get_finished_spans()]
        launches = [r for r in obs.get_dispatch_records()
                    if r["kernel"] == "skipping.mask_block"]
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
        obs.reset_trace_buffer()
        obs.reset_device_obs()

    def attrs(name):
        return [s["attrs"] for s in spans if s["name"] == name]

    [build] = attrs("stats.index_build")
    assert build["columns"] == 2 and build["unindexed"] == 1
    assert build["lanes"] == 7 and build["mode"] == "full"
    assert counter("scan.stats_index_unindexed_leaves.string") == strings + 1
    first, second = attrs("plan.skip")
    assert first["skip_route"] == "device" and first["skip_atoms"] == 7
    assert first["skip_fallback_conjuncts"] == 0 and first["uncompared"] == 0
    assert second["uncompared"] == 1 and "skip_route" not in second
    [wait] = attrs("skip.wait")
    # min, max and nullCount of two columns, and numRecords
    assert wait["atoms"] == 7 and wait["a_pad"] == 8
    assert wait["rows_read"] == 7
    [launch] = launches
    assert launch["attrs"] == {"lanes": 7, "n_pad": 128}


def test_a_two_atom_plan_reads_four_rows_of_a_wider_index(bid_table,
                                                          monkeypatch):
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    snap = Table.for_path(bid_table, engine=TpuEngine()).latest_snapshot()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        snap.scan(filter=(col("dateTime") >= lit(after(20)))
                  & (col("dateTime") < lit(after(40)))).file_paths()
        [wait] = [s.to_dict()["attrs"] for s in obs.get_finished_spans()
                  if s.name == "skip.wait"]
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert wait["atoms"] == 2 and wait["rows_read"] == 4
