"""The full decode of a Parquet part is dealt out by the part's footer
(`engine/host.py::_deal_plan`, `_read_dealt`): a task a row group and,
within a group, a heavy column, on the scan pool. Held here: the table
equals `pq.read_table`'s, schema metadata included, on every shape of
part and on both kinds of store; a small file is read whole; a torn
file raises what it raised; the span and the counter say which way a
read went; a V2 checkpoint with sidecars and a multipart checkpoint
load to the same snapshot either way, the byte prefetch still ahead of
the decoder."""

import json
import os
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.config import settings
from delta_tpu.engine import host
from delta_tpu.engine.host import HostEngine, HostParquetHandler
from delta_tpu.log.checkpointer import write_checkpoint
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.storage.logstore import InMemoryLogStore, logstore_for_path
from delta_tpu.utils.threads import DeltaThreadPool

GROUP = 1000           # rows a row group of the parts written here
LOW = 16 << 10         # `_DEAL_MIN_BYTES` for parts of a test's size
NEVER = 1 << 60        # every file read whole: `pq.read_table` forced
DEALT = obs.counter("checkpoint.parts_decoded_dealt")

_MAP = pa.map_(pa.string(), pa.string())
_X = pa.struct([("x", pa.int64())])
SCHEMA = pa.schema([
    ("protocol", pa.struct([("minReaderVersion", pa.int32()),
                            ("minWriterVersion", pa.int32())])),
    ("metaData", pa.struct([("id", pa.string()),
                            ("schemaString", pa.string()),
                            ("partitionColumns", pa.list_(pa.string())),
                            ("configuration", _MAP)])),
    ("add", pa.struct([
        ("path", pa.string()), ("partitionValues", _MAP),
        ("size", pa.int64()), ("modificationTime", pa.int64()),
        ("dataChange", pa.bool_()), ("stats", pa.string()), ("tags", _MAP),
        ("deletionVector", pa.struct([
            ("storageType", pa.string()), ("pathOrInlineDv", pa.string()),
            ("offset", pa.int32()), ("sizeInBytes", pa.int32()),
            ("cardinality", pa.int64())])),
        ("stats_parsed", pa.struct([
            ("numRecords", pa.int64()), ("minValues", _X),
            ("maxValues", _X), ("nullCount", _X)]))])),
    ("remove", pa.struct([("path", pa.string()),
                          ("deletionTimestamp", pa.int64()),
                          ("dataChange", pa.bool_())])),
], metadata={"written-by": "tests/test_checkpoint_decode_dealt.py"})

_HEAD = [{"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
         {"metaData": {"id": "t", "schemaString": "{}",
                       "partitionColumns": ["p"],
                       "configuration": [("k1", "v1"), ("k2", "v2")]}}]


def _add(i, rich=False):
    row = {"path": f"p={i % 7}/part-{i:010d}.parquet",
           "partitionValues": [("p", str(i % 7))] if rich else [],
           "size": 1 << 20, "modificationTime": i, "dataChange": False,
           "stats": json.dumps({"numRecords": 1000,
                                "minValues": {"x": i * 1000},
                                "maxValues": {"x": i * 1000 + 999},
                                "nullCount": {"x": 0}})}
    if rich:
        row["tags"] = [("ZCUBE_ID", f"z{i % 3}")] if i % 2 else None
        row["deletionVector"] = {
            "storageType": "u", "pathOrInlineDv": f"dv{i}", "offset": i % 5,
            "sizeInBytes": 40, "cardinality": i % 9} if i % 3 == 0 else None
        row["stats_parsed"] = {
            "numRecords": 1000, "minValues": {"x": i * 1000},
            "maxValues": {"x": i * 1000 + 999},
            "nullCount": {"x": 0 if i % 4 else None}}
    return {"add": row}


def _remove(i):
    return {"remove": {"path": f"gone-{i:010d}.parquet",
                       "deletionTimestamp": i, "dataChange": False}}


def _part(rows, schema=SCHEMA, group=GROUP) -> bytes:
    sink = pa.BufferOutputStream()
    pq.write_table(pa.Table.from_pylist(rows, schema=schema), sink,
                   row_group_size=group, compression="snappy")
    return sink.getvalue().to_pybytes()


def _several_row_groups():
    return _part(_HEAD + [_add(i) for i in range(4200)])


def _one_group_with_a_dominant_leaf():
    return _part(_HEAD + [_add(i) for i in range(3000)], group=1 << 20)


def _adds_and_removes_interleaved():
    """Adds and removes row by row over two groups, then a group that
    holds removes only (`add` all null in it), then adds again."""
    rows = _HEAD + [_add(i) if i % 2 else _remove(i)
                    for i in range(2 * GROUP - 2)]
    rows += [_remove(i) for i in range(GROUP)]
    rows += [_add(i) for i in range(GROUP // 2)]
    return _part(rows)


def _nested_children_under_add():
    return _part(_HEAD + [_add(i, rich=True) for i in range(2500)])


def _a_part_that_lacks_columns():
    narrow = pa.schema([f for f in SCHEMA if f.name in ("protocol", "add")],
                       metadata=SCHEMA.metadata)
    return _part(_HEAD[:1] + [_add(i) for i in range(2500)], schema=narrow)


def _a_small_data_file():
    rows = 1000
    table = pa.table({"x": np.arange(rows),
                      "s": [f"value-{i:08d}" for i in range(rows)],
                      "y": np.linspace(0.0, 1.0, rows)})
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink)
    return sink.getvalue().to_pybytes()


def _a_data_file_of_plain_columns():
    """No struct on top: strings, a list, numbers with nulls."""
    rows = 3500
    table = pa.table({
        "id": np.arange(rows),
        "url": [f"https://example.org/item/{i:012d}?ref={i % 97}"
                for i in range(rows)],
        "tags": [[f"t{i % 5}", f"u{i % 3}"] if i % 4 else None
                 for i in range(rows)],
        "price": pa.array(np.linspace(0.0, 9.0, rows),
                          mask=np.arange(rows) % 11 == 0),
        "note": ["n" * (i % 40) for i in range(rows)]})
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, row_group_size=GROUP)
    return sink.getvalue().to_pybytes()


# shape -> (builder, `_DEAL_MIN_BYTES` (None: as shipped), row groups,
#           the span's `decode`)
SHAPES = {
    "several_row_groups": (_several_row_groups, LOW, 5, "dealt"),
    "one_row_group_with_a_dominant_leaf": (
        _one_group_with_a_dominant_leaf, LOW, 1, "dealt"),
    "adds_and_removes_interleaved_and_a_group_without_adds": (
        _adds_and_removes_interleaved, LOW, 4, "dealt"),
    "maps_a_deletion_vector_and_parsed_stats_under_add": (
        _nested_children_under_add, LOW, 3, "dealt"),
    "a_part_that_lacks_columns": (_a_part_that_lacks_columns, LOW, 3,
                                  "dealt"),
    "a_data_file_of_plain_columns": (_a_data_file_of_plain_columns, LOW, 4,
                                     "dealt"),
    "a_small_one_group_data_file": (_a_small_data_file, None, 1, "whole"),
}
STORES = ["local", "memory"]


@pytest.fixture(autouse=True)
def _fresh():
    clear_parse_cache()
    part_size = settings.checkpoint_part_size
    yield
    settings.checkpoint_part_size = part_size
    obs.set_trace_mode(None)
    obs.reset_trace_buffer()
    clear_parse_cache()


def _placed(data: bytes, store: str, tmp_path, name="part.parquet") -> str:
    if store == "local":
        path = str(tmp_path / name)
    else:
        path = f"memory://dealt-{tmp_path.name}/{name}"
    logstore_for_path(path).write(path, data, overwrite=True)
    return path


def _fetches(monkeypatch) -> list:
    """The paths `InMemoryLogStore.read` is asked for from here on."""
    asked = []
    read = InMemoryLogStore.read
    monkeypatch.setattr(
        InMemoryLogStore, "read",
        lambda self, p: asked.append(p) or read(self, p))
    return asked


def _read(path, **kwargs):
    """(the handler's table of `path`, what the read left on the span
    around it)."""
    obs.set_trace_mode("on")
    with obs.span("checkpoint.read_part") as sp:
        [tbl] = HostParquetHandler().read_parquet_files([path], **kwargs)
    obs.set_trace_mode(None)
    return tbl, dict(sp.attrs)


@pytest.mark.parametrize("store", STORES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_table_equals_read_tables(shape, store, tmp_path, monkeypatch):
    build, threshold, groups, decode = SHAPES[shape]
    if threshold is not None:
        monkeypatch.setattr(host, "_DEAL_MIN_BYTES", threshold)
    data = build()
    path = _placed(data, store, tmp_path)
    fetches = _fetches(monkeypatch)
    before = DEALT.value
    got, attrs = _read(path)
    want = pq.read_table(pa.BufferReader(data))
    assert got.schema.equals(want.schema, check_metadata=True)
    assert got.equals(want)
    got.validate(full=True)
    assert (attrs["decode"], attrs["row_groups"]) == (decode, groups)
    # on a store that is not local the bytes are fetched once, whatever
    # the number of tasks that read them
    assert fetches == ([path] if store == "memory" else [])
    if decode == "dealt":
        assert attrs["decode_tasks"] > groups >= 1
        assert DEALT.value - before == 1
        # a chunk a row group, where `read_table` cuts by its batch size
        assert [len(c) for c in got.column(0).chunks] == [
            pq.ParquetFile(pa.BufferReader(data)).metadata.row_group(g)
            .num_rows for g in range(groups)]
    else:
        assert (attrs["decode_tasks"], DEALT.value - before) == (1, 0)


@pytest.mark.parametrize("store", STORES)
def test_a_projected_read_takes_the_columns_the_file_has(store, tmp_path,
                                                         monkeypatch):
    """`columns` is not the full read: it goes as it went, onto the
    columns the file has of those asked for."""
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", LOW)
    data = _a_part_that_lacks_columns()
    before = DEALT.value
    got, attrs = _read(_placed(data, store, tmp_path),
                       columns=["protocol", "metaData", "txn"])
    want = pq.read_table(pa.BufferReader(data), columns=["protocol"])
    assert got.equals(want) and got.column_names == ["protocol"]
    assert "decode" not in attrs and DEALT.value == before


def test_the_plan_goes_by_the_footers_bytes_and_values(monkeypatch):
    """Of the part with several groups (1,000 rows each and 202 in the
    last; a full group's `add.stats` 104 KB, `add.path` 32 KB, every
    other leaf a thousand values in under 10 KB)."""
    md = pq.ParquetFile(pa.BufferReader(_several_row_groups())).metadata
    units = {f"{f.name}.{c.name}" for f in SCHEMA for c in f.type}

    def plan(threshold):
        monkeypatch.setattr(host, "_DEAL_MIN_BYTES", threshold)
        return host._deal_plan(md, SCHEMA)

    # at 40 KB `add.stats` alone passes: a task of its own in each full
    # group, the heaviest first; the others share tasks of at most the
    # threshold, a column in exactly one; the short group is one task
    tasks = plan(40 << 10)
    assert [cols for _, cols in tasks[:4]] == [["add.stats"]] * 4
    assert sorted(g for g, _ in tasks[:4]) == [0, 1, 2, 3]
    assert tasks.count((4, None)) == 1
    for g in range(4):
        dealt = [c for group, cols in tasks if group == g for c in cols]
        assert sorted(dealt) == sorted(units)
        assert sum(1 for group, _ in tasks if group == g) == 3
    # at 24 KB `add.path` passes too, and the short group (31 KB) is
    # dealt out by its columns, none of which stands alone
    tasks = plan(24 << 10)
    assert [cols for _, cols in tasks[:8]] == (
        [["add.stats"]] * 4 + [["add.path"]] * 4)
    short = [cols for g, cols in tasks if g == 4]
    assert len(short) == 2 and sorted(sum(short, [])) == sorted(units)
    # a leaf counts a byte a value at the least: the 26 light leaves of
    # a full group (under 2 KB together but for `modificationTime`) are
    # no single task where their values pass the threshold
    light = [cols for g, cols in plan(3 * GROUP)
             if g == 0 and cols[0] not in ("add.path", "add.stats")]
    assert len(light) > 3 and all(len(cols) <= 3 for cols in light)
    # no row group at the threshold, or one task in all: read whole
    assert plan(NEVER) == []
    one = pq.ParquetFile(pa.BufferReader(_a_small_data_file()))
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", 1 << 20)
    assert host._deal_plan(one.metadata, one.schema_arrow) == []
    one = pq.ParquetFile(pa.BufferReader(
        _part([_remove(i) for i in range(3000)], group=1 << 20)))
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", 1 << 20)
    assert host._deal_plan(one.metadata, SCHEMA) == []


def test_names_that_hold_a_dot_are_read_whole(tmp_path, monkeypatch):
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", LOW)
    rows = 5000
    table = pa.table({
        "a.b": [f"value-{i:020d}" for i in range(rows)],
        "a": pa.StructArray.from_arrays(
            [pa.array([f"other-{i:020d}" for i in range(rows)])], ["b"])})
    path = str(tmp_path / "dots.parquet")
    pq.write_table(table, path, row_group_size=GROUP)
    got, attrs = _read(path)
    assert got.equals(table) and attrs["decode"] == "whole"


def _torn(data: bytes, how: str) -> bytes:
    return {"cut_in_half": data[:len(data) // 2],
            "footer_cut_short": data[:-6],
            "a_footer_longer_than_the_file": (
                data[:-8] + (len(data) * 2).to_bytes(4, "little") + b"PAR1"),
            "a_footer_of_noise": (data[:-8 - 4000] + b"\xa5" * 4000
                                  + data[-8:]),
            "no_parquet_at_all": b"not a parquet file at all"}[how]


@pytest.mark.parametrize("store", STORES)
@pytest.mark.parametrize("how", ["cut_in_half", "footer_cut_short",
                                 "a_footer_longer_than_the_file",
                                 "a_footer_of_noise", "no_parquet_at_all"])
def test_a_torn_file_raises_what_it_raised(how, store, tmp_path,
                                           monkeypatch):
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", LOW)
    data = _torn(_several_row_groups(), how)
    with pytest.raises(Exception) as before:
        pq.read_table(pa.BufferReader(data))
    with pytest.raises(Exception) as now:
        _read(_placed(data, store, tmp_path))
    assert type(now.value) is type(before.value)
    assert isinstance(now.value, (pa.ArrowException, OSError))


@pytest.mark.parametrize("store", STORES)
def test_a_file_that_is_not_there_raises_file_not_found(store, tmp_path):
    path = (str(tmp_path / "absent.parquet") if store == "local"
            else f"memory://dealt-{tmp_path.name}/absent.parquet")
    with pytest.raises(FileNotFoundError):
        _read(path)


def test_a_chunk_torn_under_a_sound_footer_raises_from_its_task(
        tmp_path, monkeypatch):
    """The footer parses and the plan is made; the task that reads the
    damaged chunk raises, and the read raises it."""
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", LOW)
    data = bytearray(_several_row_groups())
    md = pq.ParquetFile(pa.BufferReader(bytes(data))).metadata
    chunk = md.row_group(2).column(md.num_columns - 1)
    at = chunk.data_page_offset
    data[at:at + 64] = b"\xff" * 64
    with pytest.raises((pa.ArrowException, OSError)):
        _read(_placed(bytes(data), "local", tmp_path))


# --- through a load ---------------------------------------------------


def _table(tmp_path, commits=14):
    """A table of `commits` commits, a file each."""
    path = str(tmp_path / "t")
    for i in range(commits):
        dta.write_table(path, pa.table({"x": pa.array([i], pa.int64())}),
                        mode="append" if i else "error", engine=HostEngine())
    return path


def _one_more(path):
    dta.write_table(path, pa.table({"x": pa.array([99], pa.int64())}),
                    mode="append", engine=HostEngine())


def _classic(tmp_path):
    path = _table(tmp_path)
    Table.for_path(path, HostEngine()).checkpoint()
    _one_more(path)
    return path


def _multipart(tmp_path):
    path = _table(tmp_path)
    settings.checkpoint_part_size = 4
    Table.for_path(path, HostEngine()).checkpoint()
    log = os.path.join(path, "_delta_log")
    assert len([f for f in os.listdir(log) if ".checkpoint.00" in f]) >= 3
    _one_more(path)
    return path


def _v2_with_sidecars(tmp_path):
    path = _table(tmp_path)
    table = Table.for_path(path, HostEngine())
    settings.checkpoint_part_size = 4
    write_checkpoint(table.engine, table.latest_snapshot(), policy="v2")
    sidecars = os.path.join(path, "_delta_log", "_sidecars")
    assert len(os.listdir(sidecars)) >= 3
    _one_more(path)
    return path


def _loaded(path, threshold, monkeypatch):
    """(files, sizes, paths sorted; the full reads' span attrs; parts
    dealt) of a cold load at `_DEAL_MIN_BYTES` = `threshold`."""
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", threshold)
    clear_parse_cache()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    before = DEALT.value
    snap = Table.for_path(path, HostEngine()).latest_snapshot()
    live = snap.state.add_files_table.sort_by("path")
    answer = (snap.num_files, live.column("size").to_pylist(),
              live.column("path").to_pylist())
    reads = [s.to_dict() for s in obs.get_finished_spans()
             if s.name == "checkpoint.read_part"
             and "row_groups_read" not in s.attrs]
    obs.set_trace_mode(None)
    return answer, reads, DEALT.value - before


@pytest.mark.parametrize("layout", ["classic", "multipart",
                                    "v2_with_sidecars"])
def test_a_checkpoint_loads_to_the_same_snapshot_either_way(
        layout, tmp_path, monkeypatch):
    path = {"classic": _classic, "multipart": _multipart,
            "v2_with_sidecars": _v2_with_sidecars}[layout](tmp_path)
    whole, reads_whole, none = _loaded(path, NEVER, monkeypatch)
    # every part of these is a few KB: one byte deals each of them out,
    # a leaf a task
    dealt, reads_dealt, parts = _loaded(path, 1, monkeypatch)
    assert dealt == whole and whole[0] > 0
    assert none == 0 and parts == len(reads_dealt) == len(reads_whole) > 0
    assert {r["attrs"]["decode"] for r in reads_whole} == {"whole"}
    assert {r["attrs"]["decode"] for r in reads_dealt} == {"dealt"}
    for r in reads_dealt:
        assert set(r["attrs"]) == {"bytes", "rows", "row_groups",
                                   "decode_tasks", "decode"}
        assert r["attrs"]["decode_tasks"] > r["attrs"]["row_groups"]
        # the span is the consuming thread's: the tasks open none
        assert r["thread_id"] == threading.get_ident()
    assert [r["attrs"]["rows"] for r in reads_dealt] == [
        r["attrs"]["rows"] for r in reads_whole]


def test_the_prefetch_stays_ahead_of_a_dealt_decode(tmp_path, monkeypatch):
    """Several paths in one call: while part i is decoded (dealt out),
    `_PARQUET_PREFETCH_DEPTH` reads past it are in flight or done, and
    no part is fetched twice."""
    monkeypatch.setattr(host, "_DEAL_MIN_BYTES", LOW)
    data = _several_row_groups()
    paths = [_placed(data, "memory", tmp_path, f"part{i}.parquet")
             for i in range(6)]
    fetched = _fetches(monkeypatch)
    handed, seen = [], []
    submit = DeltaThreadPool.submit
    monkeypatch.setattr(
        DeltaThreadPool, "submit", lambda self, fn, *a:
        (handed.append(a) if self.name == "io" else None)
        or submit(self, fn, *a))
    plan = host._deal_plan

    def spy(md, schema):
        seen.append(len(handed))     # reads handed over as decode i starts
        return plan(md, schema)

    monkeypatch.setattr(host, "_deal_plan", spy)
    before = DEALT.value, host._PARQUET_PREFETCHED.value
    want = pq.read_table(pa.BufferReader(data))
    tables = list(HostParquetHandler().read_parquet_files(paths))
    assert len(tables) == 6 and all(t.equals(want) for t in tables)
    assert fetched == paths
    depth = host._PARQUET_PREFETCH_DEPTH
    assert seen == [min(6, i + 1 + depth) for i in range(6)]
    assert DEALT.value - before[0] == 6
    assert host._PARQUET_PREFETCHED.value - before[1] == 5


def test_the_counter_is_cataloged():
    with open(os.path.join(os.path.dirname(host.__file__), os.pardir,
                           "resources", "metric_names.json")) as f:
        assert DEALT.name in json.load(f)["counters"]
