"""Incremental snapshot maintenance: `update()` parity with cold
replay, checkpoint/protocol fallbacks, the parsed-commit cache, and the
post-commit handoff (`SnapshotManagement.getUpdatedLogSegment` /
`updateAfterCommit` semantics)."""

import json

import numpy as np
import pytest

from delta_tpu.engine.host import HostEngine
from delta_tpu.models.actions import AddFile, RemoveFile
from delta_tpu.models.schema import INTEGER, StructField, StructType
from delta_tpu.replay.columnar import clear_parse_cache, parse_cache
from delta_tpu.table import Table


@pytest.fixture(autouse=True)
def _fresh_parse_cache():
    clear_parse_cache()
    yield
    clear_parse_cache()


def _make_table(path, engine=None) -> Table:
    t = Table.for_path(str(path), engine or HostEngine())
    t.create_transaction_builder().with_schema(
        StructType([StructField("x", INTEGER)])).build().commit()
    return t


def _commit(t: Table, i: int, removes=()):
    txn = t.start_transaction()
    txn.add_file(AddFile(
        path=f"p{i}.parquet", partitionValues={}, size=100 + i,
        modificationTime=1000 + i, dataChange=True,
        stats=json.dumps({"numRecords": i})))
    for r in removes:
        txn.remove_file(RemoveFile(
            path=r, deletionTimestamp=2000 + i, dataChange=True))
    txn.commit()


def _state_signature(snap):
    """Everything replay decides, bit-for-bit: per-row masks aligned to
    (path, dv) plus the user-facing aggregates and spliced stats."""
    st = snap.state
    fa = st.file_actions  # forces the stats splice on both sides
    rows = sorted(
        zip(fa.column("path").to_pylist(), fa.column("dv_id").to_pylist(),
            fa.column("version").to_pylist(), fa.column("stats").to_pylist(),
            np.asarray(st.live_mask).tolist(),
            np.asarray(st.tombstone_mask).tolist()))
    return (snap.version, st.num_files, st.size_in_bytes,
            st.metadata.id, rows)


def _cold(path) -> Table:
    clear_parse_cache()
    return Table.for_path(str(path), HostEngine())


# ------------------------------------------------------------------ parity


def test_update_parity_mixed_add_remove(tmp_path):
    t = _make_table(tmp_path)
    for i in range(4):
        _commit(t, i)
    warm = t.update()
    assert warm.version == 4

    other = Table.for_path(str(tmp_path), HostEngine())
    for i in range(4, 9):
        _commit(other, i, removes=[f"p{i - 4}.parquet"])

    inc = t.update()
    assert inc.version == 9
    cold = _cold(tmp_path).latest_snapshot()
    assert _state_signature(inc) == _state_signature(cold)


def test_update_parity_readd_after_remove(tmp_path):
    t = _make_table(tmp_path)
    _commit(t, 0)
    t.update()
    other = Table.for_path(str(tmp_path), HostEngine())
    # remove p0 then re-add it: last-wins must resurrect the file and
    # the superseded prior add row must lose its mask bit
    txn = other.start_transaction()
    txn.remove_file(RemoveFile(path="p0.parquet", deletionTimestamp=5,
                               dataChange=True))
    txn.commit()
    _commit(other, 0)

    inc = t.update()
    cold = _cold(tmp_path).latest_snapshot()
    assert inc.num_files == 1
    assert _state_signature(inc) == _state_signature(cold)


def test_snapshot_update_returns_self_when_current(tmp_path):
    t = _make_table(tmp_path)
    _commit(t, 0)
    snap = t.update()
    assert snap.update() is snap
    assert t.update() is snap


def test_no_change_poll_does_one_list_zero_reads(tmp_path):
    eng = HostEngine()
    t = _make_table(tmp_path, eng)
    _commit(t, 0)
    snap = t.update()
    snap.state  # materialize so polls advance rather than full-load
    fs = eng.fs
    r0, l0 = fs.read_calls, fs.list_calls
    assert t.update() is snap
    assert fs.read_calls - r0 == 0
    assert fs.list_calls - l0 == 1


# --------------------------------------------------------------- fallbacks


def test_update_falls_back_on_checkpoint_boundary(tmp_path):
    t = _make_table(tmp_path)
    for i in range(3):
        _commit(t, i)
    snap = t.update()
    assert snap.version == 3

    other = Table.for_path(str(tmp_path), HostEngine())
    _commit(other, 3)
    other.checkpoint()  # checkpoint at v4 > snap.version

    assert snap.update() is None  # Snapshot-level: no segment to extend
    # Table-level: a state of four rows is not worth a commit's replay,
    # so the table is loaded in full (tests/test_update_across_checkpoint.py
    # has the tables whose state is advanced over the checkpoint)
    latest = t.update()
    assert latest.version == 4
    cold = _cold(tmp_path).latest_snapshot()
    assert _state_signature(latest) == _state_signature(cold)


def test_update_falls_back_on_protocol_change(tmp_path):
    from delta_tpu.models.actions import Protocol

    t = _make_table(tmp_path)
    _commit(t, 0)
    snap = t.update()
    snap.state

    other = Table.for_path(str(tmp_path), HostEngine())
    txn = other.start_transaction()
    txn.update_protocol(Protocol(minReaderVersion=1, minWriterVersion=4))
    txn.commit()

    assert snap.update() is None
    latest = t.update()
    assert latest.version == 2
    assert latest.protocol.minWriterVersion == 4


def test_advanced_with_blobs_rejects_version_gap(tmp_path):
    t = _make_table(tmp_path)
    _commit(t, 0)
    snap = t.update()
    snap.state
    blob = b'{"add":{"path":"q.parquet","partitionValues":{},"size":1,' \
           b'"modificationTime":1,"dataChange":true}}\n'
    assert snap._advanced_with_blobs([(snap.version + 2, blob)]) is None


# ------------------------------------------------------ post-commit handoff


def test_commit_advances_cache_without_rereading_own_commit(tmp_path):
    eng = HostEngine()
    t = _make_table(tmp_path, eng)
    _commit(t, 0)
    t.update().state
    fs = eng.fs
    r0 = fs.read_calls
    _commit(t, 1)  # notify_commit hands the bytes over
    snap = t.update()
    assert snap.version == 2
    assert snap.num_files == 2
    # the two commits this process wrote were never read back (the only
    # permitted reads are crc/_last_checkpoint probes, which are not
    # commit files)
    # and the advanced state matches a cold replay exactly
    cold = _cold(tmp_path).latest_snapshot()
    assert _state_signature(snap) == _state_signature(cold)
    assert fs.read_calls - r0 <= 2  # checksum-chain reads at most


# ------------------------------------------------------- parsed-commit cache


def test_full_reload_after_polls_reparses_nothing(tmp_path):
    t = _make_table(tmp_path)
    for i in range(5):
        _commit(t, i)
    # cold full load populates the cache
    clear_parse_cache()
    t2 = Table.for_path(str(tmp_path), HostEngine())
    t2.latest_snapshot().state
    cache = parse_cache()
    assert cache is not None
    misses_after_load = cache.miss_files
    assert cache.hit_files == 0

    # a second full load from scratch: every commit file served from the
    # cache, zero re-parses
    t3 = Table.for_path(str(tmp_path), HostEngine())
    snap = t3.latest_snapshot()
    snap.state  # state is lazy; force the columnarize
    assert cache.miss_files == misses_after_load
    assert cache.hit_files > 0
    cold_sig = None
    try:
        cold_sig = _state_signature(snap)
    finally:
        clear_parse_cache()
    fresh = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    assert cold_sig == _state_signature(fresh)


def test_incremental_then_full_reload_hits_cache_for_new_commits(tmp_path):
    t = _make_table(tmp_path)
    _commit(t, 0)
    t.update().state
    other = Table.for_path(str(tmp_path), HostEngine())
    for i in range(1, 4):
        _commit(other, i)
    t.update()  # incremental: parses commits 2..4, caching the span
    cache = parse_cache()
    misses = cache.miss_files
    # a cold Table full load re-parses nothing: the incremental span's
    # stat-deferred keys match the full listing's
    t4 = Table.for_path(str(tmp_path), HostEngine())
    snap = t4.latest_snapshot()
    assert snap.version == 4
    assert cache.miss_files == misses


def test_parse_cache_budget_zero_disables(tmp_path, monkeypatch):
    monkeypatch.setenv("DELTA_TPU_PARSE_CACHE_BYTES", "0")
    clear_parse_cache()
    assert parse_cache() is None
    t = _make_table(tmp_path)
    _commit(t, 0)
    snap = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    assert snap.num_files == 1  # loads still work, just uncached


def test_parse_cache_eviction_keeps_budget(tmp_path, monkeypatch):
    monkeypatch.setenv("DELTA_TPU_PARSE_CACHE_BYTES", "20000")
    clear_parse_cache()
    t = _make_table(tmp_path)
    for i in range(3):
        _commit(t, i)
        Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    cache = parse_cache()
    assert cache is not None
    assert cache.cached_bytes <= 20000 or len(cache._spans) <= 1


# ------------------------------------------------------------------ hooks


def test_checkpoint_hook_runs_off_incremental_state(tmp_path):
    t = Table.for_path(str(tmp_path), HostEngine())
    (t.create_transaction_builder()
     .with_schema(StructType([StructField("x", INTEGER)]))
     .with_table_properties({"delta.checkpointInterval": "4"})
     .build().commit())
    t.update().state
    for i in range(4):
        _commit(t, i)  # v4 triggers the checkpoint hook
    import os

    cps = [f for f in os.listdir(tmp_path / "_delta_log")
           if ".checkpoint" in f and f.endswith(".parquet")]
    assert cps, "checkpoint hook did not run"
    cold = _cold(tmp_path).latest_snapshot()
    assert cold.version == 4
    assert cold.num_files == 4


def test_a_wide_live_table_is_filtered_by_slices_and_equals_one_filter(
        monkeypatch):
    """`state.filter_live` copies the rows kept of a table with a column
    past 1 GiB a slice at a time, so that no buffer of that column
    doubles past what Arrow's pool keeps (replay/state.py::_filter_rows);
    the rows are the same, and a narrower table takes `Table.filter`."""
    import numpy as np
    import pyarrow as pa

    from delta_tpu.replay import state

    n = 20_000
    table = pa.table({
        "path": pa.chunked_array([
            pa.array([f"part-{i:010d}" for i in range(n - 5)]),
            pa.array(list("abcde")), pa.array([], pa.string())]),
        "size": pa.chunked_array([pa.array(range(n - 5)), pa.array(range(5)),
                                  pa.array([], pa.int64())])})
    mask = np.random.default_rng(7).random(n) < 0.9
    want = table.filter(pa.array(mask))
    whole = state._filter_rows(table, mask)
    assert whole.equals(want)
    assert [len(c) for c in whole.column("path").chunks] == [
        len(c) for c in want.column("path").chunks]
    # the same table, were its widest column past the pool's half block
    wide = table.column("path").nbytes
    monkeypatch.setattr(state, "_FILTER_WHOLE_BYTES", wide - 1)
    monkeypatch.setattr(state, "_FILTER_SLICE_BYTES", wide // 8)
    sliced = state._filter_rows(table, mask)
    assert sliced.equals(want)
    chunks = [len(c) for c in sliced.column("path").chunks]
    assert len(chunks) >= 8 and max(chunks) <= n // 8 + 1
    empty = state._filter_rows(table.slice(0, 0), np.zeros(0, bool))
    assert empty.num_rows == 0 and empty.schema == table.schema
