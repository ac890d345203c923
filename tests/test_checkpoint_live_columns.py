"""A checkpoint takes its live rows by `SnapshotState.live_columns`: the
nine columns the file holds (`log/checkpointer.py::ADD_COLUMNS`), a
large state's filtered column by column and range by range on the scan
pool, a column that is null on every row held not filtered at all
(`replay/state.py::_dealt_live_columns`). Held here: the files are, byte
for byte, the ones written from `state.add_files_table` (what the writer
asked for until then) on the classic, multipart and V2 routes, on
tables whose optional columns hold values on some rows, none, or all; a
table under the line takes the one call it took; no task filters more
of a column than `_filter_rows` would in one call; the span and the
counters say what happened, and no live table is built. No assertion
here is on wall time."""

import contextlib
import json
import os
import shutil
from unittest import mock

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import obs
from delta_tpu.config import settings
from delta_tpu.engine.host import HostEngine
from delta_tpu.log.checkpointer import ADD_COLUMNS, write_checkpoint
from delta_tpu.replay import state as state_mod
from delta_tpu.replay.columnar import (
    CANONICAL_FILE_ACTION_SCHEMA,
    clear_parse_cache,
)
from delta_tpu.replay.state import SnapshotState
from delta_tpu.table import Table

DEALT = obs.counter("state.live_columns_dealt")
SERIAL = obs.counter("state.live_columns_serial")
LIVE_TABLES = obs.counter("state.live_table_builds")

ROUTES = {"classic": (None, None), "multipart": (None, 700),
          "v2": ("v2", 700)}


@pytest.fixture(autouse=True)
def _fresh():
    old = settings.checkpoint_part_size
    clear_parse_cache()
    yield
    settings.checkpoint_part_size = old
    clear_parse_cache()
    obs.set_trace_mode(None)


@pytest.fixture
def over_the_line(monkeypatch):
    """Tables of a test's size dealt as a state of millions is: the line
    brought down, and a piece so small that a column is several."""
    monkeypatch.setattr(state_mod, "_DEAL_MIN_CELLS", 1)
    monkeypatch.setattr(state_mod, "_DEAL_PIECE_BYTES", 8 << 10)
    monkeypatch.setattr(state_mod, "_DEAL_PIECE_ROWS", 600)


# --------------------------------------------------------------- the logs

def _schema_string(fields) -> str:
    return json.dumps({"type": "struct", "fields": [
        {"name": n, "type": t, "nullable": True, "metadata": {}}
        for n, t in fields]})


def _log(path, commits, fields=(("x", "long"),), partition_columns=()):
    log = os.path.join(str(path), "_delta_log")
    os.makedirs(log, exist_ok=True)
    head = [{"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
            {"metaData": {"id": "live-columns", "format": {
                "provider": "parquet", "options": {}},
                "schemaString": _schema_string(fields),
                "partitionColumns": list(partition_columns),
                "configuration": {}}}]
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
    return {"add": {k: v for k, v in row.items() if v is not None}}


def _remove(i, **more):
    return {"remove": {"path": f"part-{i:06d}.parquet",
                       "deletionTimestamp": 4_000_000_000_000 + i,
                       "dataChange": True, **more}}


def _dv(i):
    return {"storageType": "u", "pathOrInlineDv": f"ab^-aqEH.-t@S}}K{i:06d}",
            "offset": i % 5, "sizeInBytes": 40 + i % 3,
            "cardinality": 1 + i % 9}


def _plain(path):
    """Adds over three commits, removes of every seventh of them after:
    dead rows in the middle of the rows held, tombstones behind the
    adds, and no deletion vector, row tracking or clustering anywhere."""
    return _log(path, [[_add(v * 800 + i) for i in range(800)]
                       for v in range(3)]
                + [[_remove(i) for i in range(0, 2400, 7)]])


def _optional_columns(path):
    """`deletion_vector`, `base_row_id` / `default_row_commit_version`
    and `clustering_provider` on some rows and null on others; stats
    null on some."""
    def row(i):
        return _add(
            i,
            stats=None if i % 13 == 0 else json.dumps({"numRecords": i}),
            deletionVector=_dv(i) if i % 3 == 0 else None,
            baseRowId=i * 10 if i % 4 else None,
            defaultRowCommitVersion=1 + i // 900 if i % 4 else None,
            clusteringProvider="liquid" if i % 5 == 0 else None)

    return _log(path, [[row(v * 900 + i) for i in range(900)]
                       for v in range(3)]
                + [[_remove(i, **({"deletionVector": _dv(i)}
                                  if i % 3 == 0 else {}))
                    for i in range(5, 2700, 11)]])


def _partitioned(path):
    return _log(
        path,
        [[_add(v * 700 + i, path=f"p={i % 7}/part-{v * 700 + i:06d}.parquet",
               partitionValues={"p": str(i % 7) if i % 11 else None})
          for i in range(700)] for v in range(3)]
        + [[{"remove": {"path": f"p={i % 7}/part-{i:06d}.parquet",
                        "partitionValues": {"p": str(i % 7)},
                        "deletionTimestamp": 4_000_000_000_000,
                        "dataChange": True}} for i in range(3, 700, 5)]],
        fields=(("x", "long"), ("p", "string")), partition_columns=("p",))


TABLES = {"plain": _plain, "optional_columns": _optional_columns,
          "partitioned": _partitioned}


# ------------------------------------------------- what a checkpoint wrote

def _from_the_live_table(state, names):
    """What `_write_checkpoint` read until it asked for the columns:
    every column of every live row."""
    return state.add_files_table


def _written(path, route, reference=False):
    """The checkpoint of the log at `path` by `route`: its files' bytes
    in the order of the parts, and the hint less what names a sidecar
    (a fresh uuid) and the store's clock."""
    policy, settings.checkpoint_part_size = ROUTES[route]
    log = os.path.join(str(path), "_delta_log")
    clear_parse_cache()
    eng = HostEngine()
    snap = Table.for_path(str(path), eng).latest_snapshot()
    how = (mock.patch.object(SnapshotState, "live_columns",
                             _from_the_live_table)
           if reference else contextlib.nullcontext())
    with how:
        info = write_checkpoint(eng, snap, policy=policy)
    hint = json.loads(info.to_json())
    parts = (hint.get("partManifest") or {}).get("parts") or []
    for p in parts:
        p.pop("mtime")
    if route == "v2":
        names = [os.path.join("_sidecars", p.pop("name")) for p in parts]
        # the top-level file is named by a uuid and names the others'
        hint.pop("sizeInBytes"), hint.pop("tag")
    else:
        names = sorted(f for f in os.listdir(log) if ".checkpoint" in f)
    files = []
    for name in names:
        with open(os.path.join(log, name), "rb") as f:
            files.append(f.read())
    for f in os.listdir(log):
        if ".checkpoint" in f or f == "_last_checkpoint":
            os.remove(os.path.join(log, f))
    shutil.rmtree(os.path.join(log, "_sidecars"), ignore_errors=True)
    return snap, files, hint


def _spans(name):
    return [s for s in obs.get_finished_spans() if s.name == name]


# ------------------------------------------------------- the same bytes

@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("shape", sorted(TABLES))
def test_the_files_are_the_ones_the_live_table_gave(tmp_path, over_the_line,
                                                    shape, route):
    TABLES[shape](tmp_path)
    _, want, want_hint = _written(tmp_path, route, reference=True)
    dealt, tables = DEALT.value, LIVE_TABLES.value
    snap, got, got_hint = _written(tmp_path, route)
    assert DEALT.value == dealt + 1           # and not by the one call
    assert LIVE_TABLES.value == tables
    assert snap.state._add_table_cache is None
    assert len(got) == len(want) == (1 if route == "classic" else
                                     len(got_hint["partManifest"]["parts"])
                                     + (route == "multipart"))
    assert got == want
    assert got_hint == want_hint              # the parts' fingerprints too


@pytest.mark.parametrize("shape", sorted(TABLES))
def test_the_columns_are_the_live_tables(tmp_path, over_the_line, shape):
    """Same rows, same order, same types, a column one array."""
    TABLES[shape](tmp_path)
    state = Table.for_path(str(tmp_path),
                           HostEngine()).latest_snapshot().state
    got = state.live_columns(list(ADD_COLUMNS))
    want = state.add_files_table.select(list(ADD_COLUMNS))
    assert got.schema == want.schema and got.equals(want)
    assert all(col.num_chunks == 1 for col in got.columns)
    assert got.num_rows == state.num_files > 0


@pytest.mark.parametrize("keep", ["none", "all", "the_last_rows"])
def test_a_piece_that_keeps_no_row_is_no_chunk(tmp_path, over_the_line, keep):
    """A range of rows with no live row among them filters to nothing,
    not to an empty chunk: the columns are whole all the same."""
    _optional_columns(tmp_path)
    state = Table.for_path(str(tmp_path),
                           HostEngine()).latest_snapshot().state
    held = state.file_actions.select(list(ADD_COLUMNS))
    at = np.arange(held.num_rows)
    mask = {"none": at < 0, "all": at >= 0,
            "the_last_rows": at >= held.num_rows - 7}[keep]
    got, how = state_mod._dealt_live_columns(held, mask)
    want = state_mod._filter_rows(held, mask)
    assert got.schema == want.schema and got.equals(want)
    assert all(col.num_chunks == 1 for col in got.columns)
    assert how["tasks"] > 1 and how["null_columns"] == 0


def _strings(n, start, typ=pa.string(), empty_every=0):
    rows = ["" if empty_every and k % empty_every == 0
            else f"value-{k}-" + "x" * (k % 17)
            for k in range(start, start + n)]
    return pa.array([r.encode() for r in rows] if typ == pa.binary() else rows,
                    typ)


@pytest.mark.parametrize("typ", [pa.string(), pa.binary()],
                         ids=["string", "binary"])
def test_strings_laid_out_by_hand_are_concat_arrays_own(monkeypatch, typ):
    """Chunks as a filter leaves them, a slice with an offset, an empty
    chunk and empty values among them, copied into place by several
    tasks: the array `pa.concat_arrays` gives, valid in full."""
    from delta_tpu.utils.threads import scan_pool, settled

    monkeypatch.setattr(state_mod, "_DEAL_PIECE_BYTES", 1 << 10)
    chunks = [_strings(300, 0, typ), _strings(40, 300, typ).slice(7, 21),
              _strings(0, 0, typ), _strings(500, 340, typ, empty_every=3),
              _strings(3, 840, typ), _strings(90, 843, typ).slice(89)]
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    with obs.span("test"):
        tasks, array = state_mod._concat_tasks(scan_pool(), "c", chunks)
        settled(tasks)
    got = array()
    got.validate(full=True)
    assert got.type == typ and got.equals(pa.concat_arrays(chunks))
    assert len(tasks) >= 3 and got.buffers()[0] is None
    copies = _spans("filter_live.concat")
    assert len(copies) == len(tasks)
    assert sum(c.attrs["chunks"] for c in copies) == len(chunks)
    assert sum(c.attrs["bytes"] for c in copies) == got.buffers()[2].size
    assert max(c.attrs["bytes"] for c in copies) <= (1 << 10) + max(
        c.nbytes for c in chunks)


@pytest.mark.parametrize("why", ["a_null", "another_type", "few_bytes"])
def test_any_other_column_is_one_concat_arrays(monkeypatch, why):
    from delta_tpu.utils.threads import scan_pool, settled

    monkeypatch.setattr(state_mod, "_DEAL_PIECE_BYTES", 1 << 10)
    chunks = {
        "a_null": [_strings(300, 0), pa.array(["a", None, "c"]),
                   _strings(300, 300)],
        "another_type": [pa.array(np.arange(5000)), pa.array(np.arange(7))],
        "few_bytes": [_strings(20, 0), _strings(20, 20)],
    }[why]
    tasks, array = state_mod._concat_tasks(scan_pool(), "c", chunks)
    settled(tasks)
    assert len(tasks) == 1 and array().equals(pa.concat_arrays(chunks))


# ----------------------------------------- which way, and what says so

def test_a_table_under_the_line_takes_the_one_call(tmp_path):
    _plain(tmp_path)
    snap = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    held = snap.state.file_actions
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    dealt, serial, tables = DEALT.value, SERIAL.value, LIVE_TABLES.value
    filtered = []
    real = state_mod._filter_rows
    with mock.patch.object(state_mod, "_filter_rows", lambda table, mask: (
            filtered.append(table.column_names) or real(table, mask))), \
            mock.patch.object(state_mod, "_dealt_live_columns",
                              side_effect=AssertionError("dealt")):
        write_checkpoint(HostEngine(), snap)
    assert filtered == [list(ADD_COLUMNS)]    # once, those columns, whole
    assert (DEALT.value, SERIAL.value) == (dealt, serial + 1)
    assert LIVE_TABLES.value == tables        # and no live table
    sp, = _spans("state.filter_live")
    assert sp.attrs["as"] == "columns" and sp.attrs["serial_reason"] == "small"
    assert sp.attrs["rows"] == held.num_rows
    assert sp.attrs["columns"] == len(ADD_COLUMNS)
    assert sp.attrs["live_rows"] == snap.state.num_files
    assert (sp.attrs["tasks"], sp.attrs["threads"]) == (1, 1)
    assert "null_columns" not in sp.attrs
    assert not _spans("filter_live.piece")
    assemble, = _spans("checkpoint.assemble")
    assert sp.parent_id == assemble.span_id


@pytest.mark.parametrize("shape,null_columns", [
    ("plain", 4), ("optional_columns", 0), ("partitioned", 4)])
def test_over_the_line_the_columns_are_dealt(tmp_path, over_the_line, shape,
                                             null_columns):
    TABLES[shape](tmp_path)
    snap = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    held = snap.state.file_actions
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    dealt, serial = DEALT.value, SERIAL.value
    write_checkpoint(HostEngine(), snap)
    assert (DEALT.value, SERIAL.value) == (dealt + 1, serial)
    sp, = _spans("state.filter_live")
    assert sp.attrs["as"] == "columns" and "serial_reason" not in sp.attrs
    assert sp.attrs["null_columns"] == null_columns
    pieces, concats = _spans("filter_live.piece"), _spans("filter_live.concat")
    assert sp.attrs["tasks"] == len(pieces) + len(concats) > 1
    assert sp.attrs["threads"] >= 1
    # a column with a value anywhere is filtered, every row of it once
    filtered = len(ADD_COLUMNS) - null_columns
    assert {p.attrs["column"] for p in pieces} == set(
        [c for c in ADD_COLUMNS
         if held.column(c).null_count < held.num_rows]) and len(
        {p.attrs["column"] for p in pieces}) == filtered
    for name in {p.attrs["column"] for p in pieces}:
        mine = sorted((p.attrs["lo"], p.attrs["rows"]) for p in pieces
                      if p.attrs["column"] == name)
        assert len(mine) > 1 and mine[0][0] == 0
        assert all(lo + n == nxt for (lo, n), (nxt, _)
                   in zip(mine, mine[1:] + [(held.num_rows, 0)]))
    assert all(p.parent_id == sp.span_id for p in pieces + concats)


def test_no_task_filters_more_of_a_column_than_one_call_may(tmp_path,
                                                            monkeypatch):
    """`_filter_rows` walks a column past 1 GiB by slices of ~256 MiB,
    because Arrow sizes a filtered string buffer by the mean length and
    doubles it; cut by bytes, a dealt column's pieces are far smaller
    than that whatever the column holds. A stats column that counts
    340 MiB here (chunks sharing one buffer, few rows live), the piece
    where it stands."""
    _plain(tmp_path)
    state = Table.for_path(str(tmp_path),
                           HostEngine()).latest_snapshot().state
    held = state.file_actions
    chunk = pa.array(['{"numRecords":%d,"pad":"%s"}' % (k, " " * (1 << 17))
                      for k in range(160)], pa.string())
    whole, rest = divmod(held.num_rows, len(chunk))
    fat = pa.chunked_array([chunk] * whole + [chunk.slice(0, rest)])
    assert fat.nbytes > state_mod._FILTER_SLICE_BYTES
    state.file_actions_raw = held.set_column(
        held.schema.get_field_index("stats"), "stats", fat)
    state.live_mask = state.live_mask & (np.arange(held.num_rows) % 97 == 0)
    monkeypatch.setattr(state_mod, "_DEAL_MIN_CELLS", 1)
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    got = state.live_columns(list(ADD_COLUMNS))
    assert got.equals(state_mod._filter_rows(
        state.file_actions.select(list(ADD_COLUMNS)), state.live_mask))
    pieces = [p for p in _spans("filter_live.piece")
              if p.attrs["column"] == "stats"]
    assert len(pieces) >= fat.nbytes // state_mod._DEAL_PIECE_BYTES
    assert sum(p.attrs["bytes"] for p in pieces) >= fat.nbytes
    assert max(p.attrs["bytes"] for p in pieces) < (
        state_mod._FILTER_SLICE_BYTES // 4)


def test_the_line_is_rows_times_columns():
    cells = state_mod._DEAL_MIN_CELLS
    assert state_mod._deal_small(cells // 9 - 1, 9)
    assert not state_mod._deal_small(-(-cells // 9), 9)
    assert state_mod._deal_small(cells - 1, 1)
    assert not state_mod._deal_small(cells, 1)
    assert state_mod._deal_small(0, 9)


def test_a_tasks_error_is_the_callers(tmp_path, over_the_line):
    """The first error, once every task has ended; nothing counted."""
    _plain(tmp_path)
    snap = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    snap.state.file_actions
    real = state_mod._filter_piece

    def fails(name, col, keep, lo, hi):
        if name == "stats" and lo:
            raise pa.ArrowMemoryError("no room")
        return real(name, col, keep, lo, hi)

    dealt = DEALT.value
    with mock.patch.object(state_mod, "_filter_piece", fails), \
            pytest.raises(pa.ArrowMemoryError, match="no room"):
        write_checkpoint(HostEngine(), snap)
    assert DEALT.value == dealt
    log = os.path.join(str(tmp_path), "_delta_log")
    assert not [f for f in os.listdir(log) if "checkpoint" in f]


def test_the_canonical_schema_still_holds_what_a_checkpoint_reads():
    assert set(ADD_COLUMNS) <= set(CANONICAL_FILE_ACTION_SCHEMA.names)
    assert len(set(ADD_COLUMNS)) == len(ADD_COLUMNS) == 9


def test_the_counters_are_cataloged():
    with open(os.path.join(os.path.dirname(obs.__file__), os.pardir,
                           "resources", "metric_names.json")) as f:
        assert {DEALT.name, SERIAL.name} <= set(json.load(f)["counters"])
