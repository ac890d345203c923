"""A checkpoint's rows reach the canonical table as they lie: the block
`_extract_file_actions` yields is, row for row, what the plain way gives
(`combine_chunks()`, `filter`, `_canonical_block`: kept here as the
reference), one chunk a column, whichever way the rows were selected;
`scan_chunk` finds the small actions it found when it combined every
column; `_decode_paths` decodes what `unquote` decodes."""

from urllib.parse import unquote

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from delta_tpu import obs
from delta_tpu.replay import columnar
from delta_tpu.replay.columnar import (
    _SmallActionTracker,
    _decode_paths,
    _extract_file_actions,
    _extract_small_rows,
)

CHUNK = 1000          # rows a chunk: runs far longer than the rule's length


@pytest.fixture(autouse=True)
def _spans():
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    yield
    obs.set_trace_mode(None)
    obs.reset_trace_buffer()


def _maps(keys_by_row):
    offsets = np.cumsum([0] + [len(k) for k in keys_by_row]).astype(np.int32)
    flat = [k for row in keys_by_row for k in row]
    return pa.MapArray.from_arrays(
        pa.array(offsets), pa.array(flat, pa.string()),
        pa.array([f"v{k}" for k in flat], pa.string()))


def _structs(kind: str, n: int, rich: bool = False) -> pa.StructArray:
    """`n` add (or remove) structs as a checkpoint holds them; `rich`
    adds the fields few tables have."""
    ids = np.arange(n)
    fields = {
        "path": pa.array([f"part-{i:08d}.parquet" for i in ids]),
        "partitionValues": _maps([["p"] if i % 3 else [] for i in ids]),
        "size": pa.array(ids * 7 + 1, pa.int64()),
        "dataChange": pa.array(ids % 2 == 0),
    }
    if kind == "add":
        fields["modificationTime"] = pa.array(ids + 10, pa.int64())
        fields["stats"] = pa.array(
            ['{"numRecords":%d}' % i if i % 5 else None for i in ids])
    else:
        fields["deletionTimestamp"] = pa.array(ids + 99, pa.int64())
        fields["extendedFileMetadata"] = pa.array(ids % 4 == 0)
    if rich:
        # as the JSON reader infers them: a struct of the keys seen
        fields["tags"] = pa.array(
            [{"ZCUBE_ID": f"z{i}"} if i % 7 else None for i in ids],
            pa.struct([("ZCUBE_ID", pa.string())]))
        has_dv = ids % 11 == 0
        fields["deletionVector"] = pa.StructArray.from_arrays(
            [pa.array(["u"] * n), pa.array([f"dv{i}" for i in ids]),
             pa.array(ids % 5, pa.int32()), pa.array(ids % 9, pa.int32()),
             pa.array(ids, pa.int64())],
            names=["storageType", "pathOrInlineDv", "offset", "sizeInBytes",
                   "cardinality"],
            mask=pa.array(~has_dv))
        fields["baseRowId"] = pa.array(ids * 3, pa.int64())
        if kind == "add":
            fields["stats"] = pa.nulls(n, pa.string())
            fields["stats_parsed"] = pa.StructArray.from_arrays(
                [pa.array(ids + 1, pa.int64()),
                 pa.StructArray.from_arrays([pa.array(ids, pa.int64())],
                                            names=["x"])],
                names=["numRecords", "minValues"])
    return pa.StructArray.from_arrays(list(fields.values()),
                                      names=list(fields))


def _column(kind: str, present: np.ndarray, bounds, rich=False, offset=0):
    """A chunked `kind` column whose row i is there where `present[i]`,
    cut at `bounds`; with `offset`, every chunk is a slice that starts
    `offset` rows into an array of its own."""
    n = len(present)
    whole = _structs(kind, n, rich)
    masked = pa.StructArray.from_arrays(
        [whole.field(i) for i in range(whole.type.num_fields)],
        fields=list(whole.type), mask=pa.array(~present))
    chunks = []
    for lo, hi in zip([0] + list(bounds), list(bounds) + [n]):
        piece = masked.slice(lo, hi - lo)
        if offset:
            pad = pa.nulls(offset, piece.type)
            piece = pa.concat_arrays([pad, piece]).slice(offset)
            assert piece.offset == offset
        else:
            piece = pa.concat_arrays([piece])
        chunks.append(piece)
    return pa.chunked_array(chunks, whole.type)


def _reference(table: pa.Table, col: str, versions, orders):
    """The plain way: every row copied into one chunk, the present ones
    copied out of it by `filter`, their tags gathered by index."""
    arr = table.column(col).combine_chunks()
    valid = pc.is_valid(arr)
    sel = np.nonzero(np.asarray(valid, dtype=bool))[0]
    if sel.size == 0:
        return None
    sub = arr.filter(valid)
    return columnar._canonical_block(sub, len(sub), col == "add",
                                     versions[sel], orders[sel])


def _every(n):
    return np.ones(n, bool)


def _leading_two(n):
    out = np.ones(n, bool)
    out[:2] = False
    return out


def _round_a_boundary(n):
    out = np.ones(n, bool)
    out[[CHUNK - 1, CHUNK, 2 * CHUNK - 1, 3 * CHUNK]] = False
    return out


def _one_inside_each(n):
    out = np.ones(n, bool)
    out[CHUNK // 2::CHUNK] = False
    return out


def _interleaved(n):
    return np.arange(n) % 2 == 0


def _none(n):
    return np.zeros(n, bool)


def _last_chunk_only(n):
    out = np.zeros(n, bool)
    out[3 * CHUNK:] = True
    return out


N = 4 * CHUNK
EVEN = [CHUNK, 2 * CHUNK, 3 * CHUNK]
#        name                  present            bounds                      rich   offset  kept      runs
SHAPES = [
    ("every_row",              _every,            EVEN,                       False, 0,      "view",   1),
    ("two_leading_nulls",      _leading_two,      EVEN,                       False, 0,      "view",   1),
    ("nulls_round_a_boundary", _round_a_boundary, EVEN,                       False, 0,      "view",   4),
    ("one_null_in_each_chunk", _one_inside_each,  EVEN,                       False, 0,      "view",   5),
    ("interleaved_row_by_row", _interleaved,      EVEN,                       False, 0,      "filter", N // 2),
    ("every_row_null",         _none,             EVEN,                       False, 0,      None,     0),
    ("a_zero_length_chunk",    _leading_two,      [CHUNK, CHUNK, 3 * CHUNK],  False, 0,      "view",   1),
    ("a_leading_empty_chunk",  _one_inside_each,  [0, CHUNK, 2 * CHUNK],      False, 0,      "view",   5),
    ("sliced_at_an_offset",    _round_a_boundary, EVEN,                       False, 3,      "view",   4),
    ("one_chunk",              _leading_two,      [],                         False, 0,      "view",   1),
    ("one_chunk_all_there",    _every,            [],                         False, 0,      "view",   1),
    ("last_chunk_only",        _last_chunk_only,  EVEN,                       False, 0,      "view",   1),
    ("the_rare_fields",        _one_inside_each,  EVEN,                       True,  0,      "view",   5),
    ("the_rare_fields_sliced", _interleaved,      EVEN,                       True,  5,      "filter", N // 2),
]
CASES = [(kind, *shape) for shape in SHAPES for kind in ("add", "remove")]


@pytest.mark.parametrize(
    "kind,name,present,bounds,rich,offset,kept,runs", CASES,
    ids=[f"{c[0]}-{c[1]}" for c in CASES])
def test_block_equals_the_plain_way(kind, name, present, bounds, rich, offset,
                                    kept, runs):
    mask = present(N)
    table = pa.table({kind: _column(kind, mask, bounds, rich, offset),
                      "other": pa.chunked_array([pa.nulls(N)])})
    versions = np.arange(N, dtype=np.int64) // 7
    orders = (np.arange(N) % 100).astype(np.int32)
    got = _extract_file_actions(table, kind, versions, orders)
    want = _reference(table, kind, versions, orders)
    if kept is None:
        assert got is None and want is None
        return
    assert got.schema.equals(columnar.CANONICAL_FILE_ACTION_SCHEMA)
    assert got.equals(want)
    got.validate(full=True)
    assert [c.num_chunks for c in got.columns] == [1] * got.num_columns
    assert got.column("version").to_numpy().tolist() == versions[mask].tolist()
    assert got.column("order").to_numpy().tolist() == orders[mask].tolist()
    [span] = [s for s in obs.get_finished_spans()
              if s.name == "canonicalize.filter"]
    assert span.attrs == {"rows": N, "kept": kept, "runs": runs,
                          "rows_kept": int(mask.sum())}
    if rich:      # the less common fields rode through
        assert got.column("dv_id").null_count < got.num_rows
        assert 0 < got.column("tags").null_count < got.num_rows
        if kind == "add":
            assert got.column("stats").null_count == 0


def test_the_rule_reads_the_runs_and_nothing_else():
    """Runs of the rule's own length go as views, one row shorter through
    `filter`: by the mean run of the input, whatever the column's name,
    the table's or the number of chunks."""
    run = columnar._VIEW_MIN_RUN_ROWS
    for length, kept in ((run, "view"), (run - 1, "filter")):
        obs.reset_trace_buffer()
        n = 6 * length
        mask = (np.arange(n) // length) % 2 == 0
        table = pa.table({"remove": _column("remove", mask, [n // 2 + 1])})
        got = _extract_file_actions(table, "remove", np.zeros(n, np.int64),
                                    np.arange(n, dtype=np.int32))
        assert got.equals(_reference(table, "remove", np.zeros(n, np.int64),
                                     np.arange(n, dtype=np.int32)))
        [span] = [s for s in obs.get_finished_spans()
                  if s.name == "canonicalize.filter"]
        assert (span.attrs["kept"], span.attrs["runs"]) == (kept, 3)


# ---------------------------------------------------- the small actions ----

def _small_table(n, rows_by_col, bounds):
    """A parsed chunk of `n` rows, cut at `bounds`, whose small-action
    columns hold `rows_by_col[col] = {row: body}`."""
    types = {
        "protocol": pa.struct([("minReaderVersion", pa.int32()),
                               ("minWriterVersion", pa.int32())]),
        "metaData": pa.struct([("id", pa.string()),
                               ("schemaString", pa.string())]),
        "txn": pa.struct([("appId", pa.string()), ("version", pa.int64()),
                          ("lastUpdated", pa.int64())]),
        "domainMetadata": pa.struct([("domain", pa.string()),
                                     ("configuration", pa.string()),
                                     ("removed", pa.bool_())]),
    }
    cols = {}
    for col, typ in types.items():
        values = [rows_by_col.get(col, {}).get(i) for i in range(n)]
        whole = pa.array(values, typ)
        cols[col] = pa.chunked_array(
            [pa.concat_arrays([whole.slice(lo, hi - lo)])
             for lo, hi in zip([0] + bounds, bounds + [n])], typ)
    cols["add"] = pa.chunked_array([pa.nulls(n)])
    return pa.table(cols)


def _scan_by_combining(table, versions, orders):
    """`scan_chunk` as it was: every column made one chunk first."""
    tracker = _SmallActionTracker()
    for col, handler in (("protocol", tracker._on_protocol),
                         ("metaData", tracker._on_metadata),
                         ("txn", tracker._on_txn),
                         ("domainMetadata", tracker._on_domain)):
        arr = table.column(col).combine_chunks()
        sel = np.nonzero(np.asarray(pc.is_valid(arr), dtype=bool))[0]
        rows = arr.take(pa.array(sel, pa.int64())).to_pylist()
        for i, row in zip(sel, rows):
            handler(int(versions[i]), int(orders[i]),
                    columnar._prune_nones(row))
    return tracker


SMALL = {
    "a_protocol_row_in_the_last_chunk": {
        "protocol": {29: {"minReaderVersion": 1, "minWriterVersion": 2}},
        "metaData": {0: {"id": "t", "schemaString": "{}"}}},
    "a_txn_in_each_of_three_chunks": {
        "txn": {3: {"appId": "a", "version": 1, "lastUpdated": 5},
                14: {"appId": "b", "version": 7, "lastUpdated": None},
                25: {"appId": "a", "version": 2, "lastUpdated": 6}}},
    "the_latest_version_and_order_wins": {
        "protocol": {2: {"minReaderVersion": 3, "minWriterVersion": 7},
                     11: {"minReaderVersion": 1, "minWriterVersion": 2},
                     12: {"minReaderVersion": 2, "minWriterVersion": 5}},
        "domainMetadata": {
            9: {"domain": "d", "configuration": "new", "removed": False},
            21: {"domain": "d", "configuration": "old", "removed": False}}},
    "nothing_there": {},
}


@pytest.mark.parametrize("name", list(SMALL))
def test_scan_chunk_finds_what_it_found_by_combining(name):
    n, bounds = 30, [10, 20]
    table = _small_table(n, SMALL[name], bounds)
    # tags that do not rise with the row: the winner is by tag, not place
    versions = np.array([(i * 7) % 5 for i in range(n)], np.int64)
    orders = np.array([(i * 3) % 11 for i in range(n)], np.int32)
    got = _SmallActionTracker()
    got.scan_chunk(table, versions, orders)
    want = _scan_by_combining(table, versions, orders)
    assert got == want
    others = _extract_small_rows(table, versions, orders)
    replayed = _SmallActionTracker()
    replayed.scan_pylist(others)
    assert replayed == want
    assert len(others) == sum(len(v) for v in SMALL[name].values())
    if name == "the_latest_version_and_order_wins":
        # rows 2, 11, 12 carry (4, 6), (2, 0), (4, 3): row 2 wins
        assert got.protocol[2].minWriterVersion == 7
        assert got.domains["d"][2].configuration == "new"


# ------------------------------------------------------ escaped paths ------

def _by_unquote(arr):
    return [None if p is None else unquote(p) for p in arr.to_pylist()]


PLAIN = [f"part-{i:05d}.parquet" for i in range(5000)]


def _paths(name):
    if name == "no_percent":
        return pa.array(PLAIN), False
    if name == "escapes_in_the_last_row_only":
        return pa.array(PLAIN + ["a%2525b/c%20d.parquet"]), True
    if name == "a_percent_in_a_neighbour_outside_the_slice":
        whole = pa.array(["x%20y"] + PLAIN + ["z%25"])
        return whole.slice(1, len(PLAIN)), False
    if name == "a_percent_in_the_slice_given":
        whole = pa.array(PLAIN + ["x%20y", "tail"])
        return whole.slice(100, len(PLAIN) - 99), True
    if name == "null_paths":
        return pa.array([None, "a b", None, "c%3Dd", None]), True
    if name == "only_null_paths":
        return pa.array([None, None], pa.string()), False
    if name == "large_string":
        return pa.array(PLAIN + ["k%3D1/f.parquet"], pa.large_string()), True
    if name == "large_string_no_percent":
        return pa.array(PLAIN, pa.large_string()).slice(7), False
    if name == "a_chunked_column":
        return pa.chunked_array([pa.array(PLAIN), pa.array([], pa.string()),
                                 pa.array(["q%41"]).slice(0, 1)]), True
    if name == "a_chunked_column_no_percent":
        return pa.chunked_array([pa.array(PLAIN).slice(3, 10),
                                 pa.array(["%"] + PLAIN).slice(1)]), False
    if name == "empty":
        return pa.array([], pa.string()), False
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "no_percent", "escapes_in_the_last_row_only",
    "a_percent_in_a_neighbour_outside_the_slice",
    "a_percent_in_the_slice_given", "null_paths", "only_null_paths",
    "large_string", "large_string_no_percent", "a_chunked_column",
    "a_chunked_column_no_percent", "empty"])
def test_decode_paths_decodes_what_unquote_decodes(name):
    arr, escaped = _paths(name)
    got = _decode_paths(arr)
    assert got.to_pylist() == _by_unquote(arr)
    # untouched means the very object: the scanners' codes hang on it
    assert (got is arr) == (not escaped)


def test_a_percent_under_a_null_slot_only_costs_the_slow_pass():
    """Bytes a null slot still spans can send a part down the exact
    pass; they can never make it skip a decode."""
    whole = pa.array(["a%20b", "plain", "c%25"])
    hidden = pa.Array.from_buffers(
        pa.string(), 3,
        [pa.array([False, True, False]).buffers()[1], *whole.buffers()[1:]])
    assert hidden.to_pylist() == [None, "plain", None]
    assert _decode_paths(hidden).to_pylist() == [None, "plain", None]
    shown = pa.array(["a%20b", None, "c"])
    assert _decode_paths(shown).to_pylist() == ["a b", None, "c"]


def test_the_columns_span_says_whether_a_path_was_escaped():
    for paths, escaped in ((["a", "b"], 0), (["a%20b", "c"], 1)):
        obs.reset_trace_buffer()
        add = pa.StructArray.from_arrays(
            [pa.array(paths), pa.array([1, 2], pa.int64())],
            names=["path", "size"])
        block = _extract_file_actions(
            pa.table({"add": add}), "add", np.zeros(2, np.int64),
            np.arange(2, dtype=np.int32))
        assert block.column("path").to_pylist() == [unquote(p) for p in paths]
        [span] = [s for s in obs.get_finished_spans()
                  if s.name == "canonicalize.columns"]
        assert span.attrs == {"rows": 2, "escaped": escaped}


@pytest.mark.parametrize("offset,length", [(0, 5000), (3, 4000), (8, 64),
                                           (13, 1), (4990, 10), (17, 0)])
def test_present_rows_reads_the_validity_bits_as_they_lie(offset, length):
    present = np.zeros(5000, bool)
    present[[0, 1, 7, 8, 13, 16, 63, 64, 2500, 4989, 4990, 4999]] = True
    whole = pa.array(np.arange(5000), mask=~present)
    piece = whole.slice(offset, length)
    want = np.flatnonzero(np.asarray(pc.is_valid(piece)))
    assert columnar._present_rows(piece).tolist() == want.tolist()
    every = pa.array(np.arange(20)).slice(offset % 7, 9)
    assert columnar._present_rows(every).tolist() == list(range(9))
