"""A scan reads its data files as one batch (`read/reader.py::read_scan`
-> `engine/host.py::HostParquetHandler._read_projected`): runs of files
as tasks of the scan pool, or on the calling thread where the batch is
not worth that. Held here: the table equals, row for row and type for
type, what a plain loop over the plan's files builds (kept below as the
reference), dealt out and inline, on every kind of table the reader
aligns; a missing file raises what it raised; the rows come in the
plan's order whichever task ends first; a file task never waits for the
pool it runs on; the span and the counters say which way a read went."""

import datetime
import os
import threading
import time
from urllib.parse import unquote

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.commands.alter import (
    add_columns,
    change_column_type,
    set_properties,
)
from delta_tpu.commands.dml import delete
from delta_tpu.engine import host
from delta_tpu.engine.host import HostEngine, HostParquetHandler
from delta_tpu.expressions import col, lit
from delta_tpu.models.schema import (
    LONG,
    PrimitiveType,
    StructField,
    to_arrow_type,
)
from delta_tpu.storage.logstore import DelegatingLogStore, LocalLogStore

FILES = 72             # partitions, a file each, of the tables made here
ROWS = 5               # rows a file
DEALT = obs.counter("scan.files_dealt")
INLINE = obs.counter("scan.files_inline")


@pytest.fixture(params=["dealt", "inline"])
def way(request, monkeypatch):
    """Both sides of `_file_runs`'s rule at a test's size: every file a
    run's worth on four workers, or no batch ever worth a task."""
    monkeypatch.setenv("DELTA_TPU_SCAN_THREADS", "4")
    monkeypatch.setattr(host, "_RUN_MIN_BYTES",
                        1 if request.param == "dealt" else 1 << 60)
    return request.param


def _rows(partition: pa.Array, start: int = 0) -> pa.Table:
    """`ROWS` rows a value of `partition`: an id, a price that is null
    in every seventh row, a note."""
    n = len(partition) * ROWS
    ids = np.arange(start, start + n, dtype=np.int32)
    return pa.table({
        "id": pa.array(ids),
        "price": pa.array(ids * 0.25, mask=ids % 7 == 0),
        "note": pa.array([f"n{i}" for i in ids]),
        "p": pc.take(partition, pa.array(np.arange(n) // ROWS)),
    })


def _int_partitions(n: int = FILES) -> pa.Array:
    return pa.array(np.arange(100, 100 + n, dtype=np.int32))


def _parse(value, dtype: pa.DataType):
    if value is None:
        return None
    if pa.types.is_integer(dtype):
        return int(value)
    if pa.types.is_date(dtype):
        return datetime.date.fromisoformat(value)
    return value


def reference(snap, files: pa.Table, columns=None, keep=None) -> pa.Table:
    """The plan's rows by a plain loop: a file at a time by
    `pq.read_table`, its columns under their logical names, a column
    the file predates as nulls, a narrower one cast up, the partition
    value repeated; then `keep` (a mask over the whole, for what a
    deletion vector or a filter drops) and the projection."""
    logical = {f.name: to_arrow_type(f.dataType) for f in snap.schema.fields}
    physical = {f.physical_name: f.name for f in snap.schema.fields}
    parts = snap.partition_columns
    tables = []
    for path, values in zip(files.column("path").to_pylist(),
                            files.column("partition_values").to_pylist()):
        t = pq.read_table(os.path.join(snap.table_path, unquote(path)))
        t = t.rename_columns([physical.get(c, c) for c in t.column_names])
        for name, dtype in logical.items():
            if name in parts:
                continue
            if name not in t.column_names:
                t = t.append_column(name, pa.nulls(t.num_rows, dtype))
            elif t.schema.field(name).type != dtype:
                at = t.column_names.index(name)
                t = t.set_column(at, pa.field(name, dtype),
                                 t.column(name).cast(dtype))
        for c in parts:
            value = dict(values).get(snap.schema[c].physical_name,
                                     dict(values).get(c))
            t = t.append_column(c, pa.array(
                [_parse(value, logical[c])] * t.num_rows, logical[c]))
        tables.append(t)
    whole = pa.concat_tables(tables, promote_options="permissive")
    if keep is not None:
        whole = whole.filter(keep(whole))
    return whole if columns is None else whole.select(columns)


class _traced:
    """The spans finished inside the block, as `.spans` after it."""

    def __enter__(self):
        obs.set_trace_mode("on")
        obs.reset_trace_buffer()
        return self

    def __exit__(self, *exc):
        self.spans = list(obs.get_finished_spans())
        obs.set_trace_mode(None)


def _snap(path):
    return Table.for_path(path, engine=HostEngine()).latest_snapshot()


def _plain(path):
    dta.write_table(path, _rows(_int_partitions()), partition_by=["p"],
                    engine=HostEngine())


def _mapped(path):
    dta.write_table(path, _rows(_int_partitions()), partition_by=["p"],
                    engine=HostEngine(),
                    properties={"delta.columnMapping.mode": "name"})


def _mapped_after_the_files(path):
    _plain(path)
    set_properties(Table.for_path(path, engine=HostEngine()),
                   {"delta.columnMapping.mode": "name"})


def _with_a_deletion_vector(path):
    dta.write_table(path, _rows(_int_partitions()), partition_by=["p"],
                    engine=HostEngine(),
                    properties={"delta.enableDeletionVectors": "true"})
    delete(Table.for_path(path, engine=HostEngine()),
           (col("p") == lit(117)) & (col("id") > lit(86)))


def _with_an_added_column(path):
    _plain(path)
    add_columns(Table.for_path(path, engine=HostEngine()),
                [StructField("score", PrimitiveType("double"))])
    late = _rows(_int_partitions(8), start=10_000)
    late = late.add_column(3, "score", pa.array(
        np.arange(late.num_rows, dtype=np.float64)))
    dta.write_table(path, late, partition_by=["p"], engine=HostEngine())


def _with_a_widened_column(path):
    _plain(path)
    table = Table.for_path(path, engine=HostEngine())
    set_properties(table, {"delta.enableTypeWidening": "true"})
    change_column_type(Table.for_path(path, engine=HostEngine()), "id", LONG)
    late = _rows(_int_partitions(8), start=10_000)
    late = late.set_column(0, "id", late.column("id").cast(pa.int64()))
    dta.write_table(path, late, partition_by=["p"], engine=HostEngine())


def _with_a_null_partition(path):
    values = _int_partitions().to_pylist()
    values[5] = None
    dta.write_table(path, _rows(pa.array(values, pa.int32())),
                    partition_by=["p"], engine=HostEngine())


def _by_date(path):
    days = pa.array([datetime.date(2001, 1, 1) + datetime.timedelta(days=d)
                     for d in range(FILES)], pa.date32())
    dta.write_table(path, _rows(days), partition_by=["p"],
                    engine=HostEngine())


def _columns_in_another_order(path):
    """Every other file rewritten with its columns the other way round,
    as a second writer might lay them out."""
    _plain(path)
    files = _snap(path).scan().add_files_table().column("path").to_pylist()
    for name in files[::2]:
        at = os.path.join(path, name)
        t = pq.read_table(at)
        pq.write_table(t.select(t.column_names[::-1]), at)


def _by_string(path):
    keys = [None if i == 9 else f"k-{i}" for i in range(FILES)]
    dta.write_table(path, _rows(pa.array(keys, pa.string())),
                    partition_by=["p"], engine=HostEngine())


def _one_file(path):
    dta.write_table(path, _rows(_int_partitions(1)), partition_by=["p"],
                    engine=HostEngine())


# (the table, the projection, the scan's filter,
#  the rows the reference keeps of the whole)
CASES = {
    "no_projection": (_plain, None, None, None),
    "projection_without_the_partition": (_plain, ["note", "id"], None, None),
    "the_partition_column_alone": (_plain, ["p"], None, None),
    "filter_on_an_unprojected_column": (
        _plain, ["note"], col("id") > lit(200),
        lambda t: pc.greater(t.column("id"), 200)),
    "filter_on_the_partition_and_a_column": (
        _plain, ["id", "p"], (col("p") >= lit(130)) & (col("price") < lit(50.0)),
        lambda t: pc.less(t.column("price"), 50.0).fill_null(False)),
    "column_mapping_by_name": (_mapped, None, None, None),
    "column_mapping_projected": (_mapped, ["p", "price"], None, None),
    "files_written_before_the_mapping": (
        _mapped_after_the_files, None, None, None),
    "a_deletion_vector_among_files_without": (
        _with_a_deletion_vector, None, None,
        lambda t: pc.invert(pc.and_(pc.equal(t.column("p"), 117),
                                    pc.greater(t.column("id"), 86)))),
    "files_that_predate_a_column": (_with_an_added_column, None, None, None),
    "the_added_column_projected": (
        _with_an_added_column, ["score", "id"], None, None),
    "a_widened_type": (_with_a_widened_column, None, None, None),
    "a_widened_type_projected": (
        _with_a_widened_column, ["id"], None, None),
    "the_null_partition": (_with_a_null_partition, None, None, None),
    "the_null_partition_projected": (
        _with_a_null_partition, ["p", "id"], None, None),
    "a_date_partition": (_by_date, None, None, None),
    "a_date_partition_projected": (_by_date, ["p"], None, None),
    "files_in_another_column_order": (
        _columns_in_another_order, None, None, None),
    "files_in_another_column_order_projected": (
        _columns_in_another_order, ["price", "note"], None, None),
    "a_string_partition": (_by_string, None, None, None),
    "one_file": (_one_file, ["id", "p"], None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_batch_read_equals_the_loop_over_the_files(tmp_path, way, case):
    make, columns, where, keep = CASES[case]
    path = str(tmp_path / "t")
    make(path)
    snap = _snap(path)
    scan = snap.scan(filter=where, columns=columns)
    files = scan.add_files_table()
    dealt, inline = DEALT.value, INLINE.value
    got = scan.to_arrow()
    want = reference(snap, files, columns, keep)
    assert got.schema.equals(want.schema), (got.schema, want.schema)
    assert got.equals(want)
    assert got.num_rows > 0
    # the side the case was meant to take is the side it took
    if way == "dealt" and files.num_rows > 1:
        assert (DEALT.value - dealt, INLINE.value - inline) == (
            files.num_rows, 0)
    else:
        assert (DEALT.value - dealt, INLINE.value - inline) == (
            0, files.num_rows)


@pytest.mark.parametrize("columns", [None, ["id"], ["p"]])
def test_a_plan_of_no_file_is_an_empty_table_of_the_schema(
        tmp_path, way, columns):
    path = str(tmp_path / "t")
    _plain(path)
    got = _snap(path).scan(filter=col("p") > lit(10_000),
                           columns=columns).to_arrow()
    assert got.num_rows == 0
    assert got.column_names == (columns or ["id", "price", "note", "p"])
    if "p" in got.column_names:
        assert got.schema.field("p").type == pa.int32()


def test_a_missing_data_file_raises_what_it_raised(tmp_path, way):
    path = str(tmp_path / "t")
    _plain(path)
    snap = _snap(path)
    gone = snap.scan().add_files_table().column("path").to_pylist()[40]
    os.remove(os.path.join(path, gone))
    with pytest.raises(FileNotFoundError):
        snap.scan(columns=["id"]).to_arrow()


class _SlowFirst(DelegatingLogStore):
    """A store (not the local one: every file is fetched through
    `read`) whose first data file takes its time, and which keeps the
    order the reads ended in."""

    def __init__(self, first: str):
        super().__init__(LocalLogStore())
        self.first = first
        self.ended = []
        self.lock = threading.Lock()

    def read(self, path: str) -> bytes:
        data = super().read(path)
        if path.endswith(self.first):
            time.sleep(0.3)
        with self.lock:
            self.ended.append(path)
        return data


def test_the_rows_come_in_the_plans_order_when_tasks_end_out_of_order(
        tmp_path, way):
    path = str(tmp_path / "t")
    _plain(path)
    snap = _snap(path)
    files = snap.scan().add_files_table()
    first = files.column("path").to_pylist()[0]
    store = _SlowFirst(first)
    engine = HostEngine()
    engine.parquet = HostParquetHandler(lambda p: store)
    slow = Table.for_path(path, engine=HostEngine()).latest_snapshot()
    slow._engine = engine
    got = slow.scan(columns=["id", "p"]).to_arrow()
    assert got.equals(reference(snap, files, ["id", "p"]))
    assert len(store.ended) == files.num_rows
    if way == "dealt":
        assert not store.ended[0].endswith(first)


def _one_large_file_among_small_ones(path) -> int:
    """A table of 40 small files and one of a row group past
    `_DEAL_MIN_BYTES`; the large file's bytes."""
    dta.write_table(path, _rows(_int_partitions(40)), partition_by=["p"],
                    engine=HostEngine())
    n = 600_000
    ids = np.arange(1_000_000, 1_000_000 + n, dtype=np.int32)
    rng = np.random.default_rng(7)
    large = pa.table({
        "id": pa.array(ids),
        "price": pa.array(rng.random(n)),
        "note": pa.array(rng.integers(0, 1 << 62, n).astype(str)),
        "p": pa.array(np.full(n, 999, dtype=np.int32)),
    })
    dta.write_table(path, large, partition_by=["p"], engine=HostEngine())
    snap = _snap(path)
    sizes = snap.scan().add_files_table().column("size").to_pylist()
    assert max(sizes) > host._DEAL_MIN_BYTES, max(sizes)
    return n


def _within(seconds: float, fn):
    """`fn()`'s result, or a failure where it is still running after
    `seconds` (a task that waits for the pool it runs on never ends)."""
    box = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:  # handed to the test's thread
            box["error"] = e

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    thread.join(seconds)
    assert not thread.is_alive(), f"still running after {seconds} s"
    if "error" in box:
        raise box["error"]
    return box["result"]


def test_a_file_task_never_waits_for_the_pool_it_runs_on(
        tmp_path, monkeypatch):
    """`SELECT *` over a table with a data file whose full read would be
    dealt out by row group: on a scan pool of two, with every file a
    task, the scan ends."""
    from delta_tpu.utils import threads

    monkeypatch.setenv("DELTA_TPU_SCAN_THREADS", "2")
    monkeypatch.setattr(threads, "_SCAN", None)     # a pool of two
    monkeypatch.setattr(host, "_RUN_MIN_BYTES", 1)
    path = str(tmp_path / "t")
    rows = _one_large_file_among_small_ones(path)
    snap = _snap(path)
    with _traced() as traced:
        got = _within(120, lambda: snap.scan().to_arrow())
    spans = traced.spans
    assert got.num_rows == 40 * ROWS + rows
    [read] = [s for s in spans if s.name == "scan.read"]
    assert read.attrs["inline"] is False and read.attrs["files"] == 41
    assert not [s for s in spans if s.attrs.get("decode") == "dealt"]


def test_a_part_read_while_a_scan_is_in_flight_is_still_dealt_out(
        tmp_path, monkeypatch):
    """The full read of a large Parquet file (a checkpoint part) from
    another thread, while a scan's tasks fill the scan pool: dealt out by
    its footer as alone, and done."""
    from delta_tpu.utils import threads

    monkeypatch.setenv("DELTA_TPU_SCAN_THREADS", "2")
    monkeypatch.setattr(threads, "_SCAN", None)
    monkeypatch.setattr(host, "_RUN_MIN_BYTES", 1)
    path = str(tmp_path / "t")
    rows = _one_large_file_among_small_ones(path)
    snap = _snap(path)
    files = snap.scan().add_files_table()
    large = os.path.join(path, files.column("path").to_pylist()[
        int(np.argmax(files.column("size").to_numpy()))])
    started = threading.Event()

    class _Signals(DelegatingLogStore):
        def read(self, p):
            started.set()
            return super().read(p)

    engine = HostEngine()
    engine.parquet = HostParquetHandler(lambda p: _Signals(LocalLogStore()))
    snap._engine = engine
    scans = []

    def scan():
        for _ in range(3):
            scans.append(snap.scan().to_arrow().num_rows)

    scanner = threading.Thread(target=scan, daemon=True)
    scanner.start()
    assert started.wait(60)
    with _traced() as traced:
        with obs.span("part"):
            [part] = list(HostParquetHandler().read_parquet_files([large]))
        scanner.join(120)
    spans = traced.spans
    assert not scanner.is_alive()
    assert part.num_rows == rows and scans == [40 * ROWS + rows] * 3
    [span] = [s for s in spans if s.name == "part"]
    assert span.attrs["decode"] == "dealt" and span.attrs["decode_tasks"] > 1


def test_a_scan_says_how_it_read_its_files(tmp_path, monkeypatch):
    """`scan.read`: `files`, `bytes`, `tasks`, `threads`, `inline`, under
    whoever called `to_arrow`; a storage span opened in a worker has it
    as its ancestor."""
    monkeypatch.setenv("DELTA_TPU_SCAN_THREADS", "4")
    monkeypatch.setattr(host, "_RUN_MIN_BYTES", 1)
    path = str(tmp_path / "t")
    _plain(path)
    snap = _snap(path)
    files = snap.scan().add_files_table()

    class _Spans(DelegatingLogStore):
        def read(self, p):
            with obs.span("test.storage_read",
                          thread=threading.current_thread().name):
                return super().read(p)

    engine = HostEngine()
    engine.parquet = HostParquetHandler(lambda p: _Spans(LocalLogStore()))
    snap._engine = engine
    dealt, inline = DEALT.value, INLINE.value
    with _traced() as traced:
        with obs.span("caller"):
            snap.scan(columns=["id"]).to_arrow()
            snap.scan(filter=col("p") == lit(100), columns=["id"]).to_arrow()
    spans = traced.spans
    many, one = [s for s in spans if s.name == "scan.read"]
    [caller] = [s for s in spans if s.name == "caller"]
    assert many.parent_id == caller.span_id == one.parent_id
    assert many.attrs["files"] == FILES and many.attrs["inline"] is False
    assert many.attrs["tasks"] == 16 and many.attrs["threads"] == 4
    assert many.attrs["bytes"] == sum(files.column("size").to_pylist())
    assert one.attrs["files"] == 1 and one.attrs["inline"] is True
    assert one.attrs["tasks"] == 0 and one.attrs["threads"] == 1
    assert (DEALT.value - dealt, INLINE.value - inline) == (FILES, 1)
    reads = [s for s in spans if s.name == "test.storage_read"]
    assert len(reads) == FILES + 1
    by_id = {s.span_id: s for s in spans}

    def scan_read_over(s):
        """The `scan.read` a storage span lies under: its parent, or in
        a pool task the parent of the task's `scan.read_run`."""
        above = by_id[s.parent_id]
        if above.name == "scan.read_run":
            above = by_id[above.parent_id]
        assert above.name == "scan.read"
        return above

    in_workers = {s.attrs["thread"] for s in reads
                  if scan_read_over(s) is many}
    assert threading.current_thread().name not in in_workers
    assert all(name.startswith("delta-tpu-scan") for name in in_workers)


@pytest.mark.parametrize("sizes, workers, runs", [
    ([], 8, [(0, 0)]),
    ([60_000], 8, [(0, 1)]),
    ([60_000] * 40, 8, [(0, 40)]),              # 12 MB: under a run a worker
    ([500 << 20] * 3, 8, [(0, 3)]),             # three large files: Arrow's
    ([60_000] * 1824, 1, [(0, 1824)]),          # one worker: no one to deal to
])
def test_a_batch_not_worth_dealing_out_is_one_run(sizes, workers, runs):
    assert host._file_runs(sizes, workers) == runs


@pytest.mark.parametrize("sizes, workers", [
    ([60_000] * 1824, 13), ([60_000] * 1824, 2), ([64 << 20] * 40, 8),
    ([1 << 20] * 30 + [300 << 20] + [1 << 20] * 30, 4), ([0] * 5000, 8),
])
def test_runs_cover_the_batch_in_order_and_share_its_weight(sizes, workers):
    runs = host._file_runs(sizes, workers)
    assert workers <= len(runs) <= workers * host._RUNS_A_WORKER
    assert [a for a, _ in runs] == [0] + [b for _, b in runs[:-1]]
    assert runs[-1][1] == len(sizes) and all(a < b for a, b in runs)
    weight = [sum(sizes[a:b]) + (b - a) * host._FILE_FIXED_BYTES
              for a, b in runs]
    heaviest_file = max(sizes) + host._FILE_FIXED_BYTES
    assert max(weight) <= sum(weight) / len(runs) + heaviest_file
