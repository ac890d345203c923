"""A table's Parquet encode dealt over the scan pool and stitched under
one footer: the file `pq.write_table(table, sink, compression="snappy")`
writes, byte for byte, made by as many threads as the table has pieces.

Nothing in a column chunk's bytes (page headers, dictionary page, data
pages) names its place in the file; only the footer does. So a row
group of the whole file, and inside it one leaf of a struct column, can
be encoded alone, as a small file of its own: its body (what lies
between the magic and the footer) is the same bytes the whole-file
writer lays down at that place. `encode` cuts the table at
`pq.write_table`'s own row-group boundaries and, inside a row group, a
struct column that holds enough rows into its leaves, each under a
struct that keeps its parents' validity and names; encodes every piece
on `scan_pool()` (Arrow's writer releases the interpreter's lock);
lays the bodies end to end in the file's order and writes one footer:
the whole table's `FileMetaData` (a zero-row encode of the table gives
the schema, the key-value metadata and the writer's name for nothing),
its row groups taken from the pieces' footers with every file position
shifted to where the chunk now lies.

What is dealt is decided by what the input shows, and by nothing else:
a table under the line (`small`: rows by columns) is encoded in one call
as before, and so is one whose footers hold a field this module was not
written to carry (a page index, a bloom filter, an encryption field,
whatever a later pyarrow adds): `pq.write_table`, the same bytes by
definition, and what `encode` hands back beside the bytes says why
(`serial_reason`).

Two writers encode through here, and each says so on a span and a pair
of counters of its own: the checkpoint writer
(`log/checkpointer.py::_encode_parquet`: a classic file, a multipart
part, a V2 sidecar, under `checkpoint.serialize`) and the writer of
data files (`engine/host.py::HostParquetHandler.write_parquet_file`:
every file of an append, a rewrite or an OPTIMIZE, under
`write.encode`). The pieces run on `scan_pool()` and the caller waits
for all of them, so neither may call from a task of that pool.

The thrift here is the compact protocol's writer beside
`log/page_decode.py::_Thrift`, the reader: a footer is read into a tree
that keeps every field's wire type, so that it is written back as it
was read, but for the integers that are positions.
"""
# delta-lint: file-disable=shared-state-race — audited: _Tree is a
# function-local cursor like the _Thrift it extends; the pieces a task
# returns are read by the calling thread only after the task is done.

from __future__ import annotations

import struct
from concurrent.futures import Future
from typing import List, NamedTuple, Optional, Tuple

import pyarrow as pa
import pyarrow._parquet as _parquet
import pyarrow.parquet as pq

from delta_tpu import obs
from delta_tpu.log.page_decode import (
    _CT_BINARY,
    _CT_BYTE,
    _CT_FALSE,
    _CT_I16,
    _CT_I32,
    _CT_I64,
    _CT_LIST,
    _CT_SET,
    _CT_STOP,
    _CT_STRUCT,
    _CT_TRUE,
    _Thrift,
)

_MAGIC = b"PAR1"

# A table of up to `_DEAL_COLUMNS` columns is dealt from this many rows,
# and a struct column cut into its leaves where it holds as many valid
# rows in a row group: under it a second task costs what it saves.
# Measured on a checkpoint's table of this repository's shape (six
# columns, one action a row, so one struct of fifteen leaves does the
# work; medians of 7, 8 cores, PERF.md §6, PR 54): at 50,000 to 80,000
# rows the dealt encode is level with the one call (37 / 32 ms, 47 / 55
# ms), at 100,000 it takes 25 ms of 68, at 400,000 65 of 254.
_DEAL_MIN_ROWS = 100_000
# A column is a piece, so a wider table reaches the line with fewer rows
# in proportion, down to the rows from which a piece is worth a task at
# all (`small`). Measured on a data file's flat table, the benchmark's
# `store_sales` (nine `integer`, a `long`, twelve `decimal(7,2)`), its
# columns taken 6, 22 and 88 at a time (one call / dealt in ms, medians
# of 9 on the chip's host, 13 cores, PERF.md §6, PR 56): 22 columns 7.2
# / 10.9 at 6,000 rows, 14.8 / 10.8 at 12,500, 26.3 / 11.1 at 25,000,
# 98.7 / 20.2 at 100,000; 88 columns 30.3 / 38.3, 56.8 / 40.2, 107.0 /
# 40.5, 466 / 69; six decimals 2.7 / 3.4, 5.2 / 4.1, 10.1 / 5.6, 38.3 /
# 14.0; six integers level to 50,000 rows and 14.9 / 9.3 at 100,000. A
# task costs ~0.4 ms whatever it holds, so no width wins under ~10,000
# rows; at the line (27,273 rows of 22 columns) the deal takes under
# half the one call's time.
_DEAL_COLUMNS = 6
_DEAL_PIECE_SHARE = 8    # a piece holds a `_DEAL_MIN_ROWS` / 8 at least


class StandDown(Exception):
    """Something the stitcher was not written to carry: `reason` is what
    the span says (`footer_field:<struct>.<id>`, `layout`, ...)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


# ------------------------------------------------ thrift compact tree --
# A struct is a list of [field id, wire type, value]; a list or set is
# (element type, [values]); a boolean field's value is in its type. What
# a Parquet footer does not hold (a double, a map, a list of booleans)
# stands the stitcher down like any field it does not know.

class _Tree(_Thrift):
    """`_Thrift` keeping what a writer needs: every field's wire type
    and order."""

    def struct(self) -> list:
        out = []
        fid = 0
        while True:
            head = self.buf[self.pos]
            self.pos += 1
            if head == _CT_STOP:
                return out
            delta, ctype = head >> 4, head & 0x0F
            fid = fid + delta if delta else self.zigzag()
            out.append([fid, ctype, self._value(ctype)])

    def _value(self, ctype: int):
        if ctype in (_CT_TRUE, _CT_FALSE):
            return None
        if ctype in (_CT_I16, _CT_I32, _CT_I64):
            return self.zigzag()
        if ctype == _CT_BINARY:
            n = self.varint()
            v = bytes(self.buf[self.pos:self.pos + n])
            self.pos += n
            return v
        if ctype == _CT_STRUCT:
            return self.struct()
        if ctype in (_CT_LIST, _CT_SET):
            head = self.buf[self.pos]
            self.pos += 1
            size, elem = head >> 4, head & 0x0F
            if size == 15:
                size = self.varint()
            if elem > _CT_FALSE:     # parquet-format has no list of bool
                return (elem, [self._value(elem) for _ in range(size)])
        elif ctype == _CT_BYTE:
            v = self.buf[self.pos]
            self.pos += 1
            return v
        raise StandDown(f"thrift_type:{ctype}")


def _put_varint(out: bytearray, v: int) -> None:
    while v > 0x7F:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)


def _put_value(out: bytearray, ctype: int, value) -> None:
    if ctype in (_CT_I16, _CT_I32, _CT_I64):
        _put_varint(out, (value << 1) ^ (value >> 63))
    elif ctype == _CT_BINARY:
        _put_varint(out, len(value))
        out += value
    elif ctype == _CT_STRUCT:
        _put_struct(out, value)
    elif ctype in (_CT_LIST, _CT_SET):
        elem, values = value
        if len(values) < 15:
            out.append(len(values) << 4 | elem)
        else:
            out.append(0xF0 | elem)
            _put_varint(out, len(values))
        for v in values:
            _put_value(out, elem, v)
    elif ctype == _CT_BYTE:
        out.append(value)
    # a boolean field is its header alone


def _put_struct(out: bytearray, fields: list) -> None:
    last = 0
    for fid, ctype, value in fields:
        delta = fid - last
        if 0 < delta <= 15:
            out.append(delta << 4 | ctype)
        else:
            out.append(ctype)
            _put_varint(out, (fid << 1) ^ (fid >> 15))
        last = fid
        _put_value(out, ctype, value)
    out.append(_CT_STOP)


def read_footer(footer) -> list:
    """A serialized `FileMetaData` as a tree."""
    return _Tree(footer).struct()


def write_footer(tree: list) -> bytes:
    out = bytearray()
    _put_struct(out, tree)
    return bytes(out)


# ------------------------------------------------ the footer's fields --
# parquet-format's field ids. The sets are what this module carries: the
# fields today's writer leaves and those that, like them, hold no place
# in the file other than the positions named below. Anything else
# stands the stitcher down.

_FMD_NUM_ROWS, _FMD_ROW_GROUPS = 3, 4
_FILE_FIELDS = frozenset({
    1,   # version
    2,   # schema
    _FMD_NUM_ROWS,
    _FMD_ROW_GROUPS,
    5,   # key_value_metadata
    6,   # created_by
    7,   # column_orders
})
_RG_COLUMNS, _RG_BYTES, _RG_ROWS = 1, 2, 3
_RG_OFFSET, _RG_COMPRESSED, _RG_ORDINAL = 5, 6, 7
_ROW_GROUP_FIELDS = frozenset({
    _RG_COLUMNS, _RG_BYTES, _RG_ROWS, _RG_OFFSET, _RG_COMPRESSED,
    _RG_ORDINAL})
_CC_OFFSET, _CC_META = 2, 3
_CHUNK_FIELDS = frozenset({_CC_OFFSET, _CC_META})
_CM_COMPRESSED, _CM_DATA_PAGE, _CM_INDEX_PAGE, _CM_DICT_PAGE = 7, 9, 10, 11
_CM_POSITIONS = (_CM_DATA_PAGE, _CM_INDEX_PAGE, _CM_DICT_PAGE)
_CHUNK_META_FIELDS = frozenset({
    1,   # type
    2,   # encodings
    3,   # path_in_schema
    4,   # codec
    5,   # num_values
    6,   # total_uncompressed_size
    _CM_COMPRESSED,
    8,   # key_value_metadata
    _CM_DATA_PAGE, _CM_INDEX_PAGE, _CM_DICT_PAGE,
    12,  # statistics
    13,  # encoding_stats
    16,  # size_statistics
})


def _field(fields: list, fid: int, default=None):
    for f in fields:
        if f[0] == fid:
            return f[2]
    return default


def _known(fields: list, known: frozenset, struct_name: str) -> None:
    for f in fields:
        if f[0] not in known:
            raise StandDown(f"footer_field:{struct_name}.{f[0]}")


def _row_groups(tree: list) -> list:
    """The row groups of a footer, every struct on the way to a file
    position checked against what this module carries."""
    _known(tree, _FILE_FIELDS, "FileMetaData")
    groups = _field(tree, _FMD_ROW_GROUPS, (_CT_STRUCT, []))[1]
    for rg in groups:
        _known(rg, _ROW_GROUP_FIELDS, "RowGroup")
        for chunk in _field(rg, _RG_COLUMNS, (_CT_STRUCT, []))[1]:
            _known(chunk, _CHUNK_FIELDS, "ColumnChunk")
            _known(_field(chunk, _CC_META, []), _CHUNK_META_FIELDS,
                   "ColumnMetaData")
    return groups


def _footer_of(buf) -> Tuple[int, list]:
    """(where the footer starts, its tree) of a Parquet file in memory."""
    view = memoryview(buf).cast("B")   # a pa.Buffer's is of signed bytes
    n = len(view)
    if n < 12 or view[n - 4:] != _MAGIC or view[:4] != _MAGIC:
        raise StandDown("layout")
    at = n - 8 - int.from_bytes(view[n - 8:n - 4], "little")
    if at < 4:
        raise StandDown("layout")
    return at, read_footer(view[at:n - 8])


# ------------------------------------------------------------ the deal --

class _Piece(NamedTuple):
    row_group: int
    column: str              # the column or, of a struct cut up, the leaf
    table: pa.Table          # the rows of the group, that column only
    weight: int


def _narrow(arr: pa.Array, path: Tuple[int, ...]) -> pa.Array:
    """`arr`, a struct, down to the one leaf at `path`: every struct on
    the way keeps its name, its field and its validity, so the leaf's
    definition levels are what they are in the whole."""
    i = path[0]
    fld = arr.type.field(i)
    child = arr.field(i)
    if len(path) > 1:
        child = _narrow(child, path[1:])
        fld = fld.with_type(child.type)
    return pa.StructArray.from_arrays(
        [child], fields=[fld],
        mask=arr.is_null() if arr.null_count else None)


def _leaves(typ: pa.DataType, name: str, at: Tuple[int, ...] = ()):
    """(path, dotted name) of a struct's leaves in the schema's order;
    what is not a struct (a map, a list, a primitive) stays whole."""
    for i, fld in enumerate(typ):
        below = f"{name}.{fld.name}"
        if pa.types.is_struct(fld.type) and fld.type.num_fields:
            yield from _leaves(fld.type, below, at + (i,))
        else:
            yield at + (i,), below


def _plan(table: pa.Table, group_rows: int) -> List[List[_Piece]]:
    """The pieces of `table` by row group, in the file's order: a piece
    a column (a data file's columns, chunked as the caller left them:
    a slice of a chunked column is its chunks' slices), and of a struct
    column that holds `_DEAL_MIN_ROWS` valid rows in the group (a
    checkpoint's `add`) a piece a leaf."""
    groups = []
    for g, start in enumerate(range(0, table.num_rows, group_rows)):
        rows = table.slice(start, group_rows)
        pieces = []
        for c, fld in enumerate(rows.schema):
            col = rows.column(c)
            if (pa.types.is_struct(fld.type) and fld.type.num_fields > 1
                    and len(col) - col.null_count >= _DEAL_MIN_ROWS):
                cut = [(name, pa.chunked_array(
                    [_narrow(chunk, path) for chunk in col.chunks]))
                    for path, name in _leaves(fld.type, fld.name)]
            else:
                cut = [(fld.name, col)]
            for name, part in cut:
                pieces.append(_Piece(g, name, pa.Table.from_arrays(
                    [part], schema=pa.schema([fld.with_type(part.type)])),
                    part.nbytes))
        groups.append(pieces)
    return groups


def _write(table: pa.Table) -> pa.Buffer:
    """The one call this module makes of Arrow's writer, with the
    arguments both callers have always passed (snappy, every other
    setting pyarrow's default)."""
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, compression="snappy")
    return sink.getvalue()


def _encode_piece(piece: _Piece) -> pa.Buffer:
    with obs.span("serialize.piece", row_group=piece.row_group,
                  column=piece.column, rows=piece.table.num_rows) as sp:
        buf = _write(piece.table)
        sp.set_attr("bytes", buf.size)
        return buf


# ---------------------------------------------------------- the stitch --

def _shifted(chunk: list, by: int) -> list:
    """A piece's `ColumnChunk` as the whole file's: its positions moved
    by `by` bytes, everything else as the piece's writer left it."""
    out = []
    for fid, ctype, value in chunk:
        if fid == _CC_OFFSET and value:      # 0 is no position: left so
            value += by
        elif fid == _CC_META:
            value = [[f, t, v + by if f in _CM_POSITIONS and v else v]
                     for f, t, v in value]
        out.append([fid, ctype, value])
    return out


def _chunk_start(meta: list) -> int:
    return _field(meta, _CM_DICT_PAGE) or _field(meta, _CM_DATA_PAGE)


def _stitch(template: list, groups: List[List[_Piece]],
            encoded: List[List[pa.Buffer]], num_rows: int) -> bytes:
    """The whole file from the pieces' files: bodies end to end, one
    footer. `template` is the footer of the table's zero-row encode."""
    parts: list = [_MAGIC]
    at = len(_MAGIC)
    row_groups = []
    for g, (pieces, bufs) in enumerate(zip(groups, encoded)):
        columns: list = []
        raw = compressed = 0
        start = at
        base = None
        for piece, buf in zip(pieces, bufs):
            end, tree = _footer_of(buf)
            of_piece = _row_groups(tree)
            if len(of_piece) != 1 or _field(
                    of_piece[0], _RG_ROWS) != piece.table.num_rows:
                raise StandDown("row_groups")
            rg = of_piece[0]
            base = base or rg
            lies = len(_MAGIC)
            for chunk in _field(rg, _RG_COLUMNS)[1]:
                meta = _field(chunk, _CC_META)
                if _chunk_start(meta) != lies:
                    raise StandDown("layout")
                lies += _field(meta, _CM_COMPRESSED)
                columns.append(_shifted(chunk, at - len(_MAGIC)))
            if lies != end:      # bytes no column chunk accounts for
                raise StandDown("layout")
            raw += _field(rg, _RG_BYTES)
            compressed += _field(rg, _RG_COMPRESSED)
            parts.append(memoryview(buf)[len(_MAGIC):end])
            at += end - len(_MAGIC)
        whole = {_RG_COLUMNS: (_CT_STRUCT, columns), _RG_BYTES: raw,
                 _RG_OFFSET: _field(base, _RG_OFFSET) + start - len(_MAGIC),
                 _RG_COMPRESSED: compressed, _RG_ORDINAL: g}
        row_groups.append([[f, t, whole.get(f, v)] for f, t, v in base])
    whole = {_FMD_NUM_ROWS: num_rows,
             _FMD_ROW_GROUPS: (_CT_STRUCT, row_groups)}
    footer = write_footer([[f, t, whole.get(f, v)] for f, t, v in template])
    parts += [footer, struct.pack("<I", len(footer)), _MAGIC]
    return b"".join(parts)


def _dealt(table: pa.Table, group_rows: int) -> Tuple[bytes, dict]:
    from delta_tpu.utils.threads import (
        default_scan_threads,
        scan_pool,
        settled,
    )

    # the zero-row encode first: where this pyarrow's footer holds what
    # is not carried, no piece is encoded in vain
    _, template = _footer_of(_write(table.slice(0, 0)))
    _row_groups(template)
    groups = _plan(table, group_rows)
    tasks = [p for pieces in groups for p in pieces]
    if len(tasks) < 2:
        raise StandDown("small")
    # the heaviest first: it sets the pace, so it must not queue
    order = sorted(range(len(tasks)), key=lambda i: -tasks[i].weight)
    pool, run = scan_pool(), obs.wrap(_encode_piece)
    futures: List[Optional[Future]] = [None] * len(tasks)
    for i in order:
        futures[i] = pool.submit(run, tasks[i])
    flat = iter(settled(futures))           # type: ignore[arg-type]
    encoded = [[next(flat) for _ in pieces] for pieces in groups]
    with obs.span("serialize.stitch") as sp:
        data = _stitch(template, groups, encoded, table.num_rows)
        sp.set_attr("bytes", len(data))
    return data, {"dealt": True, "row_groups": len(groups),
                  "tasks": len(tasks), "threads": default_scan_threads()}


def small(table: pa.Table) -> bool:
    """Whether `table` is under the line from which `encode` cuts it:
    `_DEAL_MIN_ROWS` rows of up to `_DEAL_COLUMNS` columns, as many rows
    x columns of a wider table, and never under the rows a piece needs."""
    rows, columns = table.num_rows, max(table.num_columns, _DEAL_COLUMNS)
    return (rows * columns < _DEAL_MIN_ROWS * _DEAL_COLUMNS
            or rows * _DEAL_PIECE_SHARE < _DEAL_MIN_ROWS)


def encode(table: pa.Table) -> Tuple[bytes, dict]:
    """`table` as the Parquet file `pq.write_table(table, sink,
    compression="snappy")` writes, and how it was made, for the span
    and the counters of whoever asked: `dealt`, `tasks`, `threads`,
    and `row_groups` where the table was cut, `serial_reason` where it
    was one call after all. Not to be called from a task of
    `scan_pool()`: the caller waits here for that pool."""
    # asked at every call, as `pq.write_table` asks it
    group_rows = getattr(_parquet, "_DEFAULT_ROW_GROUP_SIZE", None)
    if not group_rows:
        reason = "row_group_size"    # a pyarrow that does not say it
    elif small(table):
        reason = "small"
    else:
        try:
            return _dealt(table, group_rows)
        except StandDown as e:
            reason = e.reason
    return _write(table).to_pybytes(), {
        "dealt": False, "tasks": 1, "threads": 1, "serial_reason": reason}
