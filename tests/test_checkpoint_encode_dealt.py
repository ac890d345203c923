"""A checkpoint's Parquet encode is dealt over the scan pool by row group
and leaf and stitched under one footer (`log/parquet_stitch.py`, behind
`log/checkpointer.py::_encode_parquet`). Held here: the file is, byte
for byte, the one `pq.write_table(table, sink, compression="snappy")`
writes, on every shape of table the checkpoint writer makes; the footer
transcoder copies a footer to itself; a table under the small-table line
and a footer that holds what the stitcher was not written for are
encoded in the one call, and say so; a task that raises leaves no file;
parts encoded at once from the shared pool's threads finish; the
counters and the span's attributes say what happened. No assertion here
is on wall time."""

import json
import os
import threading
from unittest import mock

import numpy as np
import pyarrow as pa
import pyarrow._parquet as _parquet
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from delta_tpu import obs
from delta_tpu.config import settings
from delta_tpu.engine.host import HostEngine
from delta_tpu.log import checkpointer, parquet_stitch
from delta_tpu.log.checkpointer import (
    PROTOCOL_STRUCT,
    _encode_parquet,
    _single_action_table,
    write_checkpoint,
)
from delta_tpu.log.last_checkpoint import read_last_checkpoint
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.table import Table
from delta_tpu.write import ckpt_pipeline

GROUP = 1000     # rows a row group, for tables of a test's size
LOW = 64         # `_DEAL_MIN_ROWS` for them
ROW_GROUP = _parquet._DEFAULT_ROW_GROUP_SIZE   # pyarrow's own: 1,048,576

DEALT = obs.counter("checkpoint.encodes_dealt")
SERIAL = obs.counter("checkpoint.encodes_serial")


def reference(table: pa.Table) -> bytes:
    """What `_encode_parquet` was: the file it has to give."""
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, compression="snappy")
    return sink.getvalue().to_pybytes()


@pytest.fixture
def small(monkeypatch):
    """Tables of a test's size cut as a checkpoint of millions is: the
    writer's default row group (asked of pyarrow by both sides, the
    reference included) and the small-table line brought down."""
    monkeypatch.setattr(_parquet, "_DEFAULT_ROW_GROUP_SIZE", GROUP)
    monkeypatch.setattr(parquet_stitch, "_DEAL_MIN_ROWS", LOW)


@pytest.fixture(autouse=True)
def _fresh():
    old = settings.checkpoint_part_size
    clear_parse_cache()
    yield
    settings.checkpoint_part_size = old
    clear_parse_cache()
    obs.set_trace_mode(None)


# --------------------------------------------------- tables, hand-built

_PROTOCOL = pa.array([{"minReaderVersion": 1, "minWriterVersion": 2,
                       "readerFeatures": None, "writerFeatures": None}],
                     PROTOCOL_STRUCT)


def _numbered(prefix: str, n: int, start: int = 0) -> pa.Array:
    digits = pc.cast(pa.array(np.arange(start, start + n)), pa.string())
    return pc.binary_join_element_wise(prefix, digits, "")


def _adds(n: int) -> pa.StructArray:
    """`n` add rows of short strings, the checkpoint's six plain leaves."""
    at = np.arange(n, dtype=np.int64)
    return pa.StructArray.from_arrays(
        [_numbered("p-", n),
         pa.MapArray.from_arrays(np.zeros(n + 1, dtype=np.int32),
                                 pa.array([], pa.string()),
                                 pa.array([], pa.string())),
         pa.array(at % 977), pa.array(at * 3), pa.array(at % 2 == 0),
         _numbered('{"numRecords":', n)],
        names=["path", "partitionValues", "size", "modificationTime",
               "dataChange", "stats"])


def _plain(n_adds: int) -> pa.Table:
    return _single_action_table(n_adds + 1, _PROTOCOL, None, None, None,
                                _adds(n_adds), None)


# ----------------------------------- tables, as the writer makes them

def _schema_string(fields) -> str:
    return json.dumps({"type": "struct", "fields": [
        {"name": n, "type": t, "nullable": True, "metadata": {}}
        for n, t in fields]})


def _log(path, commits, fields=(("x", "long"),), partition_columns=(),
         configuration=None) -> str:
    log = os.path.join(str(path), "_delta_log")
    os.makedirs(log, exist_ok=True)
    head = [{"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
            {"metaData": {"id": "encode-dealt", "format": {
                "provider": "parquet", "options": {}},
                "schemaString": _schema_string(fields),
                "partitionColumns": list(partition_columns),
                "configuration": configuration or {}}}]
    for v, actions in enumerate([head] + list(commits)):
        with open(os.path.join(log, f"{v:020d}.json"), "w") as f:
            for a in actions:
                f.write(json.dumps(a, separators=(",", ":")) + "\n")
    return log


def _add(i, **more):
    row = {"path": f"part-{i:06d}.parquet", "partitionValues": {},
           "size": 100 + i, "modificationTime": 1000 + i,
           "dataChange": True,
           "stats": json.dumps({"numRecords": 10, "minValues": {"x": i},
                                "maxValues": {"x": i + 9},
                                "nullCount": {"x": 0}})}
    row.update(more)
    return {"add": row}


def _remove(i):
    return {"remove": {"path": f"part-{i:06d}.parquet",
                       "deletionTimestamp": 4_000_000_000_000 + i,
                       "dataChange": True}}


def _writer_table(path) -> pa.Table:
    (table, _), = _writer_tables(path)
    return table


def _writer_tables(path, policy=None, part_size=None):
    """The tables the checkpoint writer hands to `_encode_parquet` for
    the log at `path`, and what it made of each."""
    seen = []
    real = checkpointer._encode_parquet

    def spy(table):
        data = real(table)
        seen.append((table, data))
        return data

    settings.checkpoint_part_size = part_size
    eng = HostEngine()
    snap = Table.for_path(str(path), eng).latest_snapshot()
    with mock.patch.object(checkpointer, "_encode_parquet", spy):
        write_checkpoint(eng, snap, policy=policy)
    return seen


def _small_actions_then_adds(path):
    """protocol, metaData, txn and domainMetadata rows before the adds;
    rows no multiple of a row group or of anything else."""
    commits = [[{"txn": {"appId": f"app-{v}", "version": v,
                         "lastUpdated": 4_000_000_000_000}},
                {"domainMetadata": {"domain": f"d{v}", "configuration": "{}",
                                    "removed": False}}]
               + [_add(v * 1000 + i) for i in range(777)]
               for v in range(1, 4)]
    _log(path, commits)
    return _writer_table(path)


def _removes_after_the_adds(path):
    commits = [[_add(i) for i in range(2600)],
               [_remove(i) for i in range(0, 2600, 2)]]
    _log(path, commits)
    return _writer_table(path)


def _partitioned(path):
    commits = [[_add(i, path=f"p={i % 7}/part-{i:06d}.parquet",
                     partitionValues={"p": str(i % 7) if i % 11 else None})
                for i in range(2300)]]
    _log(path, commits, fields=(("x", "long"), ("p", "string")),
         partition_columns=("p",))
    return _writer_table(path)


def _dv(i):
    return {"storageType": "u", "pathOrInlineDv": f"ab^-aqEH.-t@S}}K{i:06d}",
            "offset": i % 5, "sizeInBytes": 40 + i % 3,
            "cardinality": 1 + i % 9}


def _deletion_vectors(path):
    _log(path, [[_add(i, **({"deletionVector": _dv(i)} if i % 3 == 0 else {}))
                 for i in range(2500)]])
    return _writer_table(path)


def _tags_beside_a_deletion_vector(path):
    """A part as another writer leaves it: a `tags` map among `add`'s
    leaves (this repository's writer carries none), the struct after
    it, schema metadata of its own."""
    smap = pa.map_(pa.string(), pa.string())
    add = pa.struct([
        ("path", pa.string()), ("partitionValues", smap),
        ("size", pa.int64()), ("tags", smap),
        ("deletionVector", pa.struct([
            ("storageType", pa.string()), ("pathOrInlineDv", pa.string()),
            ("offset", pa.int32()), ("sizeInBytes", pa.int32()),
            ("cardinality", pa.int64())])),
        ("stats", pa.string())])
    schema = pa.schema(
        [("txn", pa.struct([("appId", pa.string()),
                            ("version", pa.int64())])),
         ("add", add)], metadata={"written-by": "somebody else"})
    rows = [{"txn": {"appId": "a", "version": 7}}] + [
        {"add": {"path": f"p={i % 5}/f{i}", "size": i,
                 "partitionValues": [("p", str(i % 5))],
                 "tags": [("ZCUBE_ID", f"z{i % 3}"), ("k", str(i))]
                 if i % 2 else None,
                 "deletionVector": _dv(i) if i % 3 == 0 else None,
                 "stats": f'{{"numRecords":{i}}}' if i % 7 else None}}
        for i in range(2222)]
    return pa.Table.from_pylist(rows, schema=schema)


def _stats_as_struct(path):
    """`stats_parsed` with nested leaves, a decimal and a timestamp among
    them, beside the JSON form."""
    def typed(i):
        stats = {"numRecords": 10,
                 "minValues": {"x": i, "d": f"{i}.25",
                               "ts": f"2024-03-{1 + i % 28:02d}T00:00:00.000Z",
                               "s": f"a{i}"},
                 "maxValues": {"x": i + 9, "d": f"{i + 9}.75",
                               "ts": f"2024-03-{1 + i % 28:02d}T12:00:00.000Z",
                               "s": f"z{i}"},
                 "nullCount": {"x": 0, "d": i % 3, "ts": 0, "s": 1}}
        return _add(i, stats=json.dumps(stats) if i % 13 else None)

    _log(path, [[typed(i) for i in range(2400)]],
         fields=(("x", "long"), ("d", "decimal(7,2)"),
                 ("ts", "timestamp"), ("s", "string")),
         configuration={"delta.checkpoint.writeStatsAsStruct": "true"})
    return _writer_table(path)


def _all_null_leaves(path):
    """No stats on any add and the JSON form switched off: `add.stats` is
    null on every row, as are `deletionVector` and all under it,
    `baseRowId`, `defaultRowCommitVersion`, `clusteringProvider`."""
    commits = [[_add(i, stats=None) for i in range(2100)]]
    _log(path, commits, configuration={
        "delta.checkpoint.writeStatsAsJson": "false"})
    return _writer_table(path)


SHAPES = {
    "small_actions_then_adds_odd_rows": _small_actions_then_adds,
    "removes_after_the_adds": _removes_after_the_adds,
    "partitioned": _partitioned,
    "deletion_vectors": _deletion_vectors,
    "tags_beside_a_deletion_vector": _tags_beside_a_deletion_vector,
    "stats_as_struct": _stats_as_struct,
    "all_null_leaves": _all_null_leaves,
}


def _footer(data: bytes) -> bytes:
    n = int.from_bytes(data[-8:-4], "little")
    return data[-8 - n:-8]


def _spans(name):
    return [s for s in obs.get_finished_spans() if s.name == name]


# ------------------------------------------------------ the same bytes

@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_file_is_the_one_write_table_writes(tmp_path, small, shape):
    table = SHAPES[shape](tmp_path)
    before = DEALT.value
    data = _encode_parquet(table)
    assert data == reference(table)
    assert DEALT.value == before + 1          # and not by standing down
    md = pq.ParquetFile(pa.BufferReader(data)).metadata
    assert md.num_row_groups == -(-table.num_rows // GROUP) > 2
    # the transcoder copies the whole file's footer, and the footer of
    # every piece it was made from, to itself
    footer = _footer(data)
    assert parquet_stitch.write_footer(
        parquet_stitch.read_footer(footer)) == footer
    for pieces in parquet_stitch._plan(table, GROUP):
        for piece in pieces:
            footer = _footer(reference(piece.table))
            assert parquet_stitch.write_footer(
                parquet_stitch.read_footer(footer)) == footer


@pytest.mark.parametrize("n_adds,groups", [
    (150_000, 1),                 # one row group, over the line
    (2 * ROW_GROUP + 1001, 3),    # just past two of pyarrow's own
])
def test_the_same_bytes_at_pyarrows_own_row_groups(n_adds, groups):
    """Nothing patched: the row-group size asked of pyarrow, the line
    where it stands."""
    table = _plain(n_adds)
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    with obs.span("checkpoint.serialize") as sp:
        data = _encode_parquet(table)
    assert data == reference(table)
    assert sp.attrs["dealt"] is True and sp.attrs["row_groups"] == groups
    assert "serial_reason" not in sp.attrs
    md = pq.ParquetFile(pa.BufferReader(data)).metadata
    assert md.num_row_groups == groups and md.num_rows == n_adds + 1
    # a piece a column, and of `add` a piece a leaf where the group
    # holds enough of it (the third group's thousand rows do not)
    tasks = sum(table.num_columns + (
        5 if rows >= parquet_stitch._DEAL_MIN_ROWS else 0)
        for rows in [min(ROW_GROUP, table.num_rows - at)
                     for at in range(0, table.num_rows, ROW_GROUP)])
    assert sp.attrs["tasks"] == tasks == len(_spans("serialize.piece"))
    assert sp.attrs["threads"] >= 1


def test_a_small_table_is_encoded_in_one_call():
    table = _plain(parquet_stitch._DEAL_MIN_ROWS - 2)
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    dealt, serial = DEALT.value, SERIAL.value
    with obs.span("checkpoint.serialize") as sp:
        data = _encode_parquet(table)
    assert data == reference(table)
    assert sp.attrs["dealt"] is False and sp.attrs["serial_reason"] == "small"
    assert sp.attrs["tasks"] == 1
    assert (DEALT.value, SERIAL.value) == (dealt, serial + 1)
    assert not _spans("serialize.piece") and not _spans("serialize.stitch")


def test_a_table_of_no_rows_and_one_of_no_struct(small):
    empty = _single_action_table(0)
    assert _encode_parquet(empty) == reference(empty)
    flat = pa.table({"a": pa.array(np.arange(3 * GROUP + 5)),
                     "b": _numbered("v", 3 * GROUP + 5)})
    before = DEALT.value
    assert _encode_parquet(flat) == reference(flat)
    assert DEALT.value == before + 1


# ------------------------------------------------------- standing down

def _page_indexed(table: pa.Table) -> pa.Buffer:
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, compression="snappy", write_page_index=True)
    return sink.getvalue()


@pytest.mark.parametrize("how,reason,pieces_encoded", [
    # a field of every column chunk, the zero-row template's too: seen
    # before a piece is encoded
    ("encoding_stats", "footer_field:ColumnMetaData.13", False),
    # one that only a chunk with rows carries: seen in the pieces
    ("size_statistics", "footer_field:ColumnMetaData.16", True),
    # a writer that leaves a page index: positions this module does not
    # carry, and bytes between the last chunk and the footer (a chunk of
    # no rows has none, so the pieces show it)
    ("page_index", "footer_field:ColumnChunk.4", True),
])
def test_an_unknown_footer_field_stands_down(tmp_path, small, monkeypatch,
                                             how, reason, pieces_encoded):
    _log(tmp_path, [[_add(i) for i in range(2500)]])
    (table, _), = _writer_tables(tmp_path)
    if how == "page_index":
        monkeypatch.setattr(parquet_stitch, "_write", _page_indexed)
        want = _page_indexed(table).to_pybytes()
    else:
        drop = {"encoding_stats": 13, "size_statistics": 16}[how]
        monkeypatch.setattr(parquet_stitch, "_CHUNK_META_FIELDS",
                            parquet_stitch._CHUNK_META_FIELDS - {drop})
        want = reference(table)
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    dealt, serial = DEALT.value, SERIAL.value
    with obs.span("checkpoint.serialize") as sp:
        data = _encode_parquet(table)
    assert data == want
    assert sp.attrs["dealt"] is False and sp.attrs["serial_reason"] == reason
    assert (DEALT.value, SERIAL.value) == (dealt, serial + 1)
    assert bool(_spans("serialize.piece")) == pieces_encoded
    # the stitch that found it says so and gives no file
    assert [(s.status, s.attrs["error.message"])
            for s in _spans("serialize.stitch")] == (
        [("error", reason)] if pieces_encoded else [])


# ------------------------------------------------------------- errors

def test_a_task_that_raises_leaves_no_file(tmp_path, small, monkeypatch):
    log = _log(tmp_path, [[_add(i) for i in range(2500)]])
    eng = HostEngine()
    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    real = parquet_stitch._write
    running, ended = [], []

    def fails_on_stats(table):
        running.append(1)
        try:
            if table.num_rows and table.schema.names == ["add"] and [
                    f.name for f in table.schema.field("add").type] == ["stats"]:
                raise OSError("no room for the stats leaf")
            return real(table)
        finally:
            ended.append(1)

    monkeypatch.setattr(parquet_stitch, "_write", fails_on_stats)
    with pytest.raises(ckpt_pipeline.CheckpointWriteError) as err:
        write_checkpoint(eng, snap)
    # every task was settled before the error left
    assert len(running) == len(ended) > 3
    assert isinstance(err.value.cause, OSError)
    assert not [f for f in os.listdir(log) if "checkpoint" in f]
    assert read_last_checkpoint(eng.fs, log) is None
    # and the writer is whole again
    monkeypatch.setattr(parquet_stitch, "_write", real)
    write_checkpoint(eng, snap)
    assert read_last_checkpoint(eng.fs, log).version == 1


# ---------------------------------------- parts at once, from the pool

@pytest.mark.parametrize("policy", ["multipart", "v2"])
def test_parts_encoded_at_once_finish_and_equal_their_serial_bytes(
        tmp_path, small, policy):
    """Four file-action parts, each built on a `shared_pool()` thread and
    each dealing its pieces into the one scan pool: none waits for
    another, and each is the file `pq.write_table` writes of its rows."""
    _log(tmp_path, [[_add(i) for i in range(4 * 1500)]])
    made, failed = [], []

    def run():
        try:
            made.extend(_writer_tables(
                tmp_path, policy="v2" if policy == "v2" else None,
                part_size=1500))
        except BaseException as e:      # pragma: no cover - shown below
            failed.append(e)

    before = DEALT.value
    worker = threading.Thread(target=run, daemon=True)
    worker.start()
    worker.join(timeout=120)
    assert not worker.is_alive(), "the parts wait for one another"
    assert not failed, failed
    parts = [(t, d) for t, d in made if t.num_rows == 1500]
    assert len(parts) == 4 and DEALT.value == before + 4
    for table, data in made:
        assert data == reference(table)
    snap = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    assert snap.state.num_files == 6000


# ------------------------------------------------ what the trace says

def test_the_span_and_the_counters_say_what_happened(tmp_path, small):
    log = _log(tmp_path, [[_add(i) for i in range(2500)]])
    eng = HostEngine()
    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    dealt, serial = DEALT.value, SERIAL.value
    write_checkpoint(eng, snap)
    assert (DEALT.value, SERIAL.value) == (dealt + 1, serial)
    (ser,), (stitch,) = _spans("checkpoint.serialize"), _spans(
        "serialize.stitch")
    pieces = _spans("serialize.piece")
    size = os.path.getsize(os.path.join(
        log, f"{1:020d}.checkpoint.parquet"))
    # ONE span of the name, on the calling thread, around the whole
    assert ser.attrs["dealt"] is True and ser.attrs["row_groups"] == 3
    assert ser.attrs["tasks"] == len(pieces) > 3
    assert ser.attrs["bytes"] == stitch.attrs["bytes"] == size
    assert "serial_reason" not in ser.attrs
    for s in pieces + [stitch]:
        assert s.parent_id == ser.span_id
        assert s.start_unix_ns >= ser.start_unix_ns
    assert {s.attrs["row_group"] for s in pieces} == {0, 1, 2}
    assert {"add.path", "add.stats", "add.partitionValues", "remove",
            "protocol"} <= {s.attrs["column"] for s in pieces}
    # a piece says its rows and the bytes of its own file; the rows of a
    # column's pieces are the table's
    by_column = {}
    for s in pieces:
        assert s.attrs["bytes"] > 0
        by_column[s.attrs["column"]] = by_column.get(
            s.attrs["column"], 0) + s.attrs["rows"]
    assert set(by_column.values()) == {2500 + 2}


def test_the_counters_are_cataloged():
    with open(os.path.join(os.path.dirname(obs.__file__), os.pardir,
                           "resources", "metric_names.json")) as f:
        assert {DEALT.name, SERIAL.name} <= set(json.load(f)["counters"])
