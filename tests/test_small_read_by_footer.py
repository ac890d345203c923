"""The small-action read of a checkpoint part goes by the part's footer
(`ParquetHandler.read_parquet_files(..., present_only=True)`): the row
groups whose statistics admit a small action, each up to the last row
the statistics count, and on a local store no byte beside them. Held
here: the `SmallState` is the one the projected read of every row
makes, field for field, on every shape of part; the span and the
counter say what the footer spared; a data file's projected read still
hands back every row."""

import dataclasses
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.config import settings
from delta_tpu.engine import host
from delta_tpu.engine.host import HostEngine, HostParquetHandler
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.storage.logstore import logstore_for_path

GROUP = 1000          # rows a row group of the parts written here
ADDS = 4200           # so five groups, the last one short
SKIPPED = obs.counter("checkpoint.small_row_groups_skipped")
FALLBACKS = obs.counter("snapshot.checkpoint_fallbacks")


class _EveryRow(HostParquetHandler):
    """The handler before the hint: the projected read of every row."""

    def read_parquet_files(self, paths, columns=None, present_only=False):
        return super().read_parquet_files(paths, columns)


def _engine(every_row: bool = False) -> HostEngine:
    eng = HostEngine()
    if every_row:
        eng.parquet = _EveryRow()
    return eng


@pytest.fixture(autouse=True)
def _small_batches(monkeypatch):
    """Batches of 64 rows, so that a group of 1,000 is left early."""
    monkeypatch.setattr(host, "_PRESENT_BATCH_ROWS", 64)
    part_size = settings.checkpoint_part_size
    clear_parse_cache()
    yield
    settings.checkpoint_part_size = part_size
    obs.set_trace_mode(None)
    obs.reset_trace_buffer()
    clear_parse_cache()


def _txn(i):
    return {"txn": {"appId": f"app-{i}", "version": i, "lastUpdated": 7}}


def _domain(i):
    return {"domainMetadata": {"domain": f"d{i}", "configuration": "{}",
                               "removed": False}}


def _add(i):
    return {"add": {"path": f"f{i}.parquet", "partitionValues": [],
                    "size": 1, "modificationTime": 1, "dataChange": False}}


def _checkpointed(path):
    """A table of one commit behind its own classic checkpoint, and a
    second commit after it: (log path, part's name, part's schema,
    its protocol row, its metaData row)."""
    dta.write_table(path, pa.table({"x": pa.array([1], pa.int64())}),
                    mode="error", engine=_engine())
    Table.for_path(path, engine=_engine()).checkpoint()
    dta.write_table(path, pa.table({"x": pa.array([2], pa.int64())}),
                    mode="append", engine=_engine())
    log = f"{path}/_delta_log"
    store = logstore_for_path(log)
    [part] = [f.path for f in store.list_from(f"{log}/0")
              if f.path.endswith(".checkpoint.parquet")]
    own = pq.read_table(pa.BufferReader(store.read(part)))
    rows = own.to_pylist()
    [protocol] = [r for r in rows if r["protocol"] is not None]
    [metadata] = [r for r in rows if r["metaData"] is not None]
    return log, part, own.schema, protocol, metadata


def _rewrite(log, part, rows, schema, **writer):
    """The checkpoint at version 0 rewritten to hold `rows`."""
    sink = pa.BufferOutputStream()
    pq.write_table(pa.Table.from_pylist(rows, schema=schema), sink,
                   row_group_size=GROUP, **writer)
    store = logstore_for_path(log)
    store.write(part, sink.getvalue().to_pybytes(), overwrite=True)
    store.write(f"{log}/_last_checkpoint",
                b'{"version":0,"size":%d}' % len(rows), overwrite=True)
    return len(rows)


def _first_group(path):
    log, part, schema, protocol, metadata = _checkpointed(path)
    rows = [protocol, metadata] + [_add(i) for i in range(ADDS)]
    return _rewrite(log, part, rows, schema)


def _last_group(path):
    log, part, schema, protocol, metadata = _checkpointed(path)
    rows = [_add(i) for i in range(ADDS)] + [metadata, protocol]
    return _rewrite(log, part, rows, schema)


def _spread(path):
    """protocol in the first group, metaData in the fourth, a `txn`
    every 350 rows and two `domainMetadata` in the third group: four of
    five groups hold a small action, the second only a `txn`."""
    log, part, schema, protocol, metadata = _checkpointed(path)
    # three values under the map's repeated leaves, in one row
    metadata = {"metaData": {**metadata["metaData"], "configuration": [
        ("k1", "v1"), ("k2", "v2"), ("k3", "v3")]}}
    rows = [_add(i) for i in range(ADDS)]
    rows[0], rows[3 * GROUP + 500] = protocol, metadata
    for i in range(100, 3 * GROUP, 350):
        rows[i] = _txn(i)
    rows[2 * GROUP + 10], rows[2 * GROUP + 950] = _domain(1), _domain(2)
    return _rewrite(log, part, rows, schema)


def _no_statistics(path):
    log, part, schema, protocol, metadata = _checkpointed(path)
    rows = [protocol, metadata, _txn(1)] + [_add(i) for i in range(ADDS)]
    return _rewrite(log, part, rows, schema, write_statistics=False)


def _lacks_txn_and_domain(path):
    log, part, schema, protocol, metadata = _checkpointed(path)
    narrow = pa.schema([f for f in schema
                        if f.name not in ("txn", "domainMetadata")])
    rows = [_add(i) for i in range(ADDS)]
    rows[GROUP + 5], rows[GROUP + 70] = protocol, metadata
    return _rewrite(log, part, rows, narrow)


def _multipart(path):
    settings.checkpoint_part_size = 4
    for i in range(11):
        dta.write_table(path, pa.table({"x": pa.array([i], pa.int64())}),
                        mode="append" if i else "error", engine=_engine())
    Table.for_path(path, engine=_engine()).checkpoint()
    dta.write_table(path, pa.table({"x": pa.array([99], pa.int64())}),
                    mode="append", engine=_engine())
    return len([f for f in os.listdir(f"{path}/_delta_log")
                if ".checkpoint." in f and f.endswith(".parquet")])


def _truncated(path):
    _spread(path)
    [part] = [f for f in os.listdir(f"{path}/_delta_log")
              if f.endswith(".checkpoint.parquet")]
    part = os.path.join(path, "_delta_log", part)
    with open(part, "rb") as f:
        data = f.read()
    with open(part, "wb") as f:
        f.write(data[:len(data) // 2])


# case -> (builder, on a store that is not the local one,
#          row groups read of the part's, checkpoint fallbacks)
CASES = {
    "protocol_and_metadata_in_the_first_group": (_first_group, False,
                                                 (1, 5), 0),
    "small_actions_in_the_last_group": (_last_group, False, (1, 5), 0),
    "txn_rows_spread_over_four_groups": (_spread, False, (4, 5), 0),
    "a_part_without_statistics": (_no_statistics, False, (5, 5), 0),
    "a_part_lacking_txn_and_domain_metadata": (_lacks_txn_and_domain,
                                               False, (1, 5), 0),
    "a_multipart_checkpoint": (_multipart, False, None, 0),
    "a_store_that_is_not_local": (_spread, True, (4, 5), 0),
    "a_truncated_part": (_truncated, False, None, 1),
}


def _small_state(path, every_row: bool):
    """(the `SmallState` of a cold load, its `checkpoint.read_part`
    spans' attrs, groups skipped, checkpoint fallbacks)."""
    clear_parse_cache()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    before = SKIPPED.value, FALLBACKS.value
    snap = Table.for_path(path, engine=_engine(every_row)).latest_snapshot()
    small = snap._small_state
    reads = [s.to_dict()["attrs"] for s in obs.get_finished_spans()
             if s.name == "checkpoint.read_part"]
    obs.set_trace_mode(None)
    return (small, reads, SKIPPED.value - before[0],
            FALLBACKS.value - before[1])


@pytest.mark.parametrize("case", list(CASES))
def test_the_small_state_is_the_projected_reads(case, tmp_path):
    build, remote, groups, fallbacks = CASES[case]
    path = f"memory://small-read-{case}/t" if remote else str(tmp_path / "t")
    built = build(path)
    want, _, none_skipped, fell_back = _small_state(path, every_row=True)
    got, reads, skipped, fell_back_too = _small_state(path, every_row=False)
    assert dataclasses.asdict(got) == dataclasses.asdict(want)
    assert got.protocol is not None and got.metadata is not None
    assert (none_skipped, fell_back, fell_back_too) == (0, fallbacks,
                                                        fallbacks)
    if case == "txn_rows_spread_over_four_groups":
        assert len(got.set_transactions) == 9
        assert sorted(got.domain_metadata) == ["d1", "d2"]
    if groups is not None:
        [read] = reads
        assert (read["row_groups_read"], read["row_groups"]) == groups
        assert skipped == groups[1] - groups[0]
        assert read["file_rows"] == built
        whole = groups[0] == groups[1]
        assert (read["rows"] == read["file_rows"]) == whole
        assert (read["bytes_read"] == read["bytes"]) == remote
    if case == "a_multipart_checkpoint":
        # every part's footer is read; only the first holds small actions
        assert built >= 3
        assert [r["row_groups_read"] for r in reads] == [1] + [0] * (built - 1)
        assert [r["rows"] for r in reads][1:] == [0] * (built - 1)


def test_the_span_and_the_counter_say_what_the_footer_spared(tmp_path):
    path = str(tmp_path / "t")
    _first_group(path)
    log = os.path.join(path, "_delta_log")
    for name in os.listdir(log):     # no `.crc`: `latest_snapshot` itself
        if name.endswith(".crc"):    # makes the small-action read
            os.remove(os.path.join(log, name))
    [part] = [f for f in os.listdir(log) if f.endswith(".parquet")]
    clear_parse_cache()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    before = SKIPPED.value
    snap = Table.for_path(path, engine=_engine()).latest_snapshot()
    assert snap.num_files == ADDS + 1
    spans = [s.to_dict() for s in obs.get_finished_spans()]
    by_id = {s["span_id"]: s for s in spans}

    def under(span, name):
        while span is not None and span["name"] != name:
            span = by_id.get(span["parent_id"])
        return span is not None

    [first, second] = [s for s in spans
                       if s["name"] == "checkpoint.read_part"]
    assert under(first, "snapshot.load_small")
    assert under(second, "snapshot.load") and not under(
        second, "snapshot.load_small")
    small, full = first["attrs"], second["attrs"]
    size = os.path.getsize(os.path.join(log, part))
    assert small == {"bytes": size, "row_groups": 5, "row_groups_read": 1,
                     "file_rows": ADDS + 2, "rows": 64,
                     "bytes_read": small["bytes_read"]}
    # the footer and five small column chunks (here the footer is most
    # of it: a part of 71 KB)
    assert 0 < small["bytes_read"] < size // 2
    # the full read is as it was: the file's size, every row (a part
    # of 71 KB is read whole: tests/test_checkpoint_decode_dealt.py)
    assert full == {"bytes": size, "rows": ADDS + 2, "row_groups": 5,
                    "decode_tasks": 1, "decode": "whole"}
    assert SKIPPED.value - before == 4


def test_the_footer_counts_rows_not_values(tmp_path):
    """What `_present_counts` reads off the footer of the spread part:
    rows a group holds of each column, from the leaves that do not
    repeat (the three map entries of the one metaData row count one);
    None for every group of a part written without statistics."""
    path = str(tmp_path / "t")
    _spread(path)
    log = os.path.join(path, "_delta_log")
    [part] = [f for f in os.listdir(log) if f.endswith(".parquet")]
    cols = ["protocol", "metaData", "txn", "domainMetadata"]
    with pq.ParquetFile(os.path.join(log, part)) as f:
        assert host._present_counts(f, host._leaves_under(f, cols)) == [
            {"protocol": 1, "metaData": 0, "txn": 3, "domainMetadata": 0},
            {"protocol": 0, "metaData": 0, "txn": 3, "domainMetadata": 0},
            {"protocol": 0, "metaData": 0, "txn": 3, "domainMetadata": 2},
            {"protocol": 0, "metaData": 1, "txn": 0, "domainMetadata": 0},
            {"protocol": 0, "metaData": 0, "txn": 0, "domainMetadata": 0}]
    _no_statistics(str(tmp_path / "u"))
    log = os.path.join(str(tmp_path / "u"), "_delta_log")
    with pq.ParquetFile(os.path.join(log, part)) as f:
        assert host._present_counts(f, host._leaves_under(f, cols)) == [
            dict.fromkeys(cols)] * 5


def test_the_counter_is_cataloged():
    import json

    with open(os.path.join(os.path.dirname(host.__file__), os.pardir,
                           "resources", "metric_names.json")) as f:
        assert SKIPPED.name in json.load(f)["counters"]


def _y_null_in_the_middle_group() -> pa.Table:
    """Three groups' rows of (x, y), y null all through the second."""
    y = np.arange(3 * GROUP, dtype=np.float64)
    return pa.table({"x": pa.array(np.arange(3 * GROUP)),
                     "y": pa.array(y, mask=(y >= GROUP) & (y < 2 * GROUP))})


@pytest.mark.parametrize("hinted", [False, True])
def test_without_the_hint_a_projected_read_hands_back_every_row(
        tmp_path, hinted):
    """`read_parquet_files(paths, columns)` is what it was: every row,
    also of a group in which the projected column is all null. With the
    hint the rows of that group, and only they, may go."""
    table = _y_null_in_the_middle_group()
    path = str(tmp_path / "data.parquet")
    pq.write_table(table, path, row_group_size=GROUP)
    [got] = HostParquetHandler().read_parquet_files(
        [path], columns=["y"], present_only=hinted)
    if hinted:
        assert got.column("y").null_count == 0
        assert got.num_rows == 2 * GROUP
    else:
        assert got.equals(table.select(["y"]))


def test_a_data_files_projected_read_returns_every_row(tmp_path):
    """Through `read/reader.py`: a scan that projects a column which is
    all null in one row group of a data file gets that group's rows."""
    path = str(tmp_path / "t")
    table = _y_null_in_the_middle_group()
    dta.write_table(path, table, mode="error", engine=_engine())
    [data] = [f for f in os.listdir(path) if f.endswith(".parquet")]
    pq.write_table(table, os.path.join(path, data), row_group_size=GROUP)
    meta = pq.ParquetFile(os.path.join(path, data)).metadata
    assert meta.num_row_groups == 3
    nulls = meta.row_group(1).column(1).statistics.null_count
    assert nulls == GROUP        # the footer would rule the group out
    got = dta.read_table(path, columns=["y"], engine=_engine())
    assert got.num_rows == 3 * GROUP
    assert got.column("y").null_count == GROUP
    assert got.column("y").to_pylist() == table.column("y").to_pylist()
