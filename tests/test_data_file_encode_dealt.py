"""A data file's Parquet encode dealt over the scan pool by row group
and column and stitched under one footer (`log/parquet_stitch.py`,
behind `engine/host.py::HostParquetHandler.write_parquet_file`). Held
here: the stored file is, byte for byte, the one
`pq.write_table(table, sink, compression="snappy")` writes, on every
shape of table a writer of data files hands over; the span
`write.encode` and the counters `write.encodes_*` say what happened,
one span a file, and the checkpoint writer's counters count none of
it; through `write_data_files` an `AddFile` states the stored length
and the statistics of the file as pyarrow reads it back."""

import decimal
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow._parquet as _parquet
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from delta_tpu import obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.log import parquet_stitch
from delta_tpu.models.schema import from_arrow_schema
from delta_tpu.write.writer import write_data_files

GROUP = 1000     # rows a row group, for tables of a test's size
LOW = 64         # `_DEAL_MIN_ROWS` for them
ROWS = 2 * GROUP + 500

DEALT = obs.counter("write.encodes_dealt")
SERIAL = obs.counter("write.encodes_serial")
CKPT = [obs.counter("checkpoint.encodes_dealt"),
        obs.counter("checkpoint.encodes_serial")]


def reference(table: pa.Table) -> bytes:
    """What `write_parquet_file` stored before: the file it has to give."""
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, compression="snappy")
    return sink.getvalue().to_pybytes()


@pytest.fixture
def small(monkeypatch):
    """Tables of a test's size cut as a file of millions of rows is."""
    monkeypatch.setattr(_parquet, "_DEFAULT_ROW_GROUP_SIZE", GROUP)
    monkeypatch.setattr(parquet_stitch, "_DEAL_MIN_ROWS", LOW)


@pytest.fixture
def traced():
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    yield
    obs.set_trace_mode(None)
    obs.reset_trace_buffer()


def _spans(name):
    return [s for s in obs.get_finished_spans() if s.name == name]


def _stored(tmp_path, table: pa.Table, name: str = "f.parquet") -> bytes:
    path = os.path.join(str(tmp_path), name)
    status = HostEngine().parquet.write_parquet_file(path, table)
    with open(path, "rb") as f:
        data = f.read()
    assert status.size == len(data)
    return data


# ------------------------------------------------------------ the shapes

def _nulled(values: np.ndarray, rng, typ=None, share=0.04) -> pa.Array:
    return pa.array(values, typ, mask=rng.random(len(values)) < share)


def _sales(rows: int = ROWS, seed: int = 0) -> pa.Table:
    """The benchmark's `store_sales` without its partition column: nine
    `integer`, a `long`, twelve `decimal(7,2)`, ~4% nulls in all but the
    item and the ticket."""
    rng = np.random.default_rng(seed)
    columns = {"ss_item_sk": pa.array(
        rng.integers(1, 360_000, rows).astype(np.int32))}
    for i in range(8):
        columns[f"ss_key_{i}"] = _nulled(
            rng.integers(1, 10 ** (i + 2), rows).astype(np.int32), rng)
    columns["ss_ticket_number"] = pa.array(
        rng.integers(1, 720_000_000, rows).astype(np.int64))
    for i in range(12):
        cents = rng.integers(-999_999, 999_999, rows)
        columns[f"ss_money_{i}"] = _nulled(
            np.array([decimal.Decimal(int(c)).scaleb(-2) for c in cents],
                     dtype=object), rng, pa.decimal128(7, 2))
    return pa.table(columns)


def _long_strings(rows: int = ROWS) -> pa.Table:
    """A chunk of 1,000 distinct strings of 1.2 KB passes the dictionary
    page's megabyte: the writer falls back to plain pages midway."""
    rng = np.random.default_rng(1)
    return pa.table({
        "id": pa.array(np.arange(rows)),
        "body": pa.array([f"{i:07d}" + "x" * 1200 for i in range(rows)]),
        "tag": pa.array([f"t{i % 13}" for i in range(rows)]),
        "note": _nulled(np.array([f"n{i}" for i in range(rows)],
                                 dtype=object), rng, pa.string(), 0.3)})


def _nested(rows: int = ROWS) -> pa.Table:
    rng = np.random.default_rng(2)
    point = pa.struct([("x", pa.int32()), ("where", pa.struct(
        [("lat", pa.float64()), ("names", pa.list_(pa.string()))]))])
    return pa.table({
        "id": pa.array(np.arange(rows)),
        "items": pa.array(
            [None if i % 11 == 0 else list(range(i % 5)) for i in range(rows)],
            pa.list_(pa.int64())),
        "attrs": pa.array(
            [None if i % 7 == 0 else [(f"k{j}", f"v{i}") for j in range(i % 3)]
             for i in range(rows)], pa.map_(pa.string(), pa.string())),
        "point": pa.array(
            [None if i % 9 == 0 else {
                "x": None if i % 4 == 0 else i,
                "where": None if i % 5 == 0 else {
                    "lat": float(rng.random()),
                    "names": [f"p{i}", None] if i % 2 else []}}
             for i in range(rows)], point)})


def _an_all_null_column(rows: int = ROWS) -> pa.Table:
    return pa.table({
        "id": pa.array(np.arange(rows)),
        "never": pa.nulls(rows, pa.string()),
        "never_money": pa.nulls(rows, pa.decimal128(7, 2)),
        "n": pa.array(np.arange(rows) % 3)})


def _many_chunks(rows: int = ROWS) -> pa.Table:
    """A plain compaction's table: `concat_tables` of as many pieces as
    the bin had files, no `take` laying them end to end; a piece ends
    where it ends, inside a row group or on its edge."""
    whole = _sales(rows, seed=3)
    cuts = [0, 1, 338, 999, 1000, 1007, 1500, 2000, 2250, rows]
    table = pa.concat_tables(
        [whole.slice(a, b - a) for a, b in zip(cuts, cuts[1:])])
    assert table.column(0).num_chunks == len(cuts) - 1
    return table


def _mapped_fields(rows: int = ROWS) -> pa.Table:
    """Physical names and field ids, as a writer under column mapping by
    id lays them down: they are the schema's, so the template footer's."""
    plain = _sales(rows, seed=4).select(range(4))
    fields = [pa.field(f"col-{i:04x}", f.type, f.nullable, {
        b"PARQUET:field_id": str(i + 1).encode(),
        b"delta.columnMapping.physicalName": f"col-{i:04x}".encode()})
        for i, f in enumerate(plain.schema)]
    return pa.Table.from_arrays(
        plain.columns, schema=pa.schema(fields, {b"writer": b"a test"}))


SHAPES = {
    "the_cells_schema": _sales,
    "strings_past_the_dictionary_page": _long_strings,
    "list_map_and_nested_struct": _nested,
    "an_all_null_column": _an_all_null_column,
    "a_table_of_many_chunks": _many_chunks,
    "field_ids_under_column_mapping": _mapped_fields,
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_stored_file_is_the_one_write_table_writes(
        tmp_path, small, traced, shape):
    table = SHAPES[shape]()
    dealt, serial = DEALT.value, SERIAL.value
    checkpoints = [c.value for c in CKPT]
    data = _stored(tmp_path, table)
    assert data == reference(table)
    assert (DEALT.value, SERIAL.value) == (dealt + 1, serial)
    assert [c.value for c in CKPT] == checkpoints
    # a piece a column; a struct that holds enough rows a piece a leaf
    names = set(table.column_names)
    if shape == "list_map_and_nested_struct":
        names = names - {"point"} | {
            "point.x", "point.where.lat", "point.where.names"}
    [enc] = _spans("write.encode")
    assert enc.attrs == {
        "rows": table.num_rows, "columns": table.num_columns,
        "bytes": len(data), "dealt": True, "row_groups": 3,
        "tasks": 3 * len(names), "threads": enc.attrs["threads"]}
    pieces = _spans("serialize.piece")
    [stitch] = _spans("serialize.stitch")
    assert len(pieces) == 3 * len(names)
    assert all(s.parent_id == enc.span_id for s in pieces + [stitch])
    assert {s.attrs["column"] for s in pieces} == names
    if shape == "strings_past_the_dictionary_page":
        md = pq.ParquetFile(pa.BufferReader(data)).metadata
        assert "PLAIN" in md.row_group(0).column(1).encodings
    if shape == "field_ids_under_column_mapping":
        back = pq.ParquetFile(pa.BufferReader(data)).schema_arrow
        assert [f.metadata[b"PARQUET:field_id"] for f in back] == [
            b"1", b"2", b"3", b"4"]
        assert back.metadata[b"writer"] == b"a test"


def test_the_same_bytes_at_pyarrows_own_row_group(tmp_path, traced):
    """Nothing patched: a flat table just over the line, which a table
    of 22 columns reaches at 6 / 22 of the rows a checkpoint's does; one
    row group of pyarrow's own, a piece a column."""
    rows = -(-parquet_stitch._DEAL_MIN_ROWS * 6 // 22)
    assert rows == 27_273
    table = _sales(rows, seed=5)
    assert not parquet_stitch.small(table)
    assert parquet_stitch.small(table.slice(1))
    assert parquet_stitch.small(table.select(range(6)))
    assert not parquet_stitch.small(pa.concat_tables(
        [table.select(range(6))] * 4).slice(0, parquet_stitch._DEAL_MIN_ROWS))
    # however wide, a piece needs an eighth of the line's rows
    wide = pa.table({f"c{i}": table.column(0) for i in range(88)})
    assert not parquet_stitch.small(wide.slice(0, 12_500))
    assert parquet_stitch.small(wide.slice(0, 12_499))
    assert _stored(tmp_path, table) == reference(table)
    [enc] = _spans("write.encode")
    assert enc.attrs["dealt"] is True and enc.attrs["row_groups"] == 1
    assert enc.attrs["tasks"] == table.num_columns == 22


# ----------------------------------------------- one call after all

def test_a_table_under_the_floor_is_one_call(tmp_path, traced):
    table = _sales(1000, seed=6)
    assert parquet_stitch.small(table)
    dealt, serial = DEALT.value, SERIAL.value
    checkpoints = [c.value for c in CKPT]
    with obs.span("table.write") as caller:
        data = _stored(tmp_path, table)
    assert data == reference(table)
    assert (DEALT.value, SERIAL.value) == (dealt, serial + 1)
    assert [c.value for c in CKPT] == checkpoints
    # under `on` a small file opens no span (a sink writes thousands)
    # and says nothing on its caller's
    assert not _spans("write.encode") and not _spans("serialize.piece")
    assert caller.attrs == {}
    obs.set_trace_mode("verbose")
    _stored(tmp_path, table, "again.parquet")
    [enc] = _spans("write.encode")
    assert enc.attrs == {"rows": 1000, "columns": 22, "bytes": len(data),
                         "dealt": False, "tasks": 1, "threads": 1,
                         "serial_reason": "small"}


def test_a_footer_field_not_carried_stands_the_stitcher_down(
        tmp_path, small, traced, monkeypatch):
    monkeypatch.setattr(parquet_stitch, "_CHUNK_META_FIELDS",
                        parquet_stitch._CHUNK_META_FIELDS - {13})
    table = _sales()
    dealt, serial = DEALT.value, SERIAL.value
    assert _stored(tmp_path, table) == reference(table)
    assert (DEALT.value, SERIAL.value) == (dealt, serial + 1)
    [enc] = _spans("write.encode")
    assert enc.attrs["dealt"] is False and enc.attrs["tasks"] == 1
    assert enc.attrs["serial_reason"] == "footer_field:ColumnMetaData.13"
    assert "row_groups" not in enc.attrs
    assert not _spans("serialize.piece")    # seen in the template


# ------------------------------------------- through write_data_files

def _as_file_says(value):
    return decimal.Decimal(str(value)) if value is not None else None


def test_write_data_files_states_the_stored_file(tmp_path, small, traced):
    """Two files of one call: each `AddFile` has the stored length and
    the statistics pyarrow computes from the file read back, each file
    is `pq.write_table`'s of its rows, and each encode has a span of
    its own under the caller's, which learns nothing of either."""
    data = _sales(2 * ROWS, seed=7)
    dealt = DEALT.value
    with obs.span("optimize.write") as caller:
        adds = write_data_files(
            HostEngine(), str(tmp_path), data, from_arrow_schema(data.schema),
            [], {}, data_change=False, target_rows_per_file=ROWS)
    assert len(adds) == 2 and DEALT.value == dealt + 2
    assert caller.attrs == {}
    encodes = _spans("write.encode")
    assert [e.attrs["rows"] for e in encodes] == [ROWS, ROWS]
    assert all(e.parent_id == caller.span_id and e.attrs["dealt"] is True
               and e.attrs["row_groups"] == 3 and e.attrs["tasks"] == 66
               for e in encodes)
    for i, (add, enc) in enumerate(zip(adds, encodes)):
        path = os.path.join(str(tmp_path), add.path)
        with open(path, "rb") as f:
            stored = f.read()
        assert stored == reference(data.slice(i * ROWS, ROWS))
        assert add.size == len(stored) == enc.attrs["bytes"]
        assert add.dataChange is False
        back = pq.read_table(path)
        said = json.loads(add.stats, parse_float=decimal.Decimal)
        assert said["numRecords"] == back.num_rows == ROWS
        for name in back.column_names:
            column = back.column(name)
            found = pc.min_max(column)
            assert said["nullCount"][name] == column.null_count
            assert _as_file_says(said["minValues"][name]) == _as_file_says(
                found["min"].as_py())
            assert _as_file_says(said["maxValues"][name]) == _as_file_says(
                found["max"].as_py())


def test_the_counters_are_cataloged():
    with open(os.path.join(os.path.dirname(obs.__file__), os.pardir,
                           "resources", "metric_names.json")) as f:
        assert {DEALT.name, SERIAL.name} <= set(json.load(f)["counters"])
