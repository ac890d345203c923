"""`Table.update()` where a checkpoint has landed past the held version:
the held state is advanced over the commits between and the segment is
listed anew, as a cold load would list it; nothing of the checkpoint is
read. The full load stays for a state that cannot be advanced, has rows
enough to shed, or is further behind than its rows are worth
(docs/incremental_update.md)."""

import contextlib
import gc
import json
import os
import time

import numpy as np
import pytest

import delta_tpu.table as table_mod
from delta_tpu import obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.expressions import col, lit
from delta_tpu.log.segment import (
    _IncrementalUnavailable,
    build_log_segment,
    list_commits_after,
)
from delta_tpu.models.actions import AddFile, Protocol, RemoveFile
from delta_tpu.models.schema import INTEGER, StructField, StructType
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.table import Table

ENGINES = pytest.mark.parametrize("engine", [HostEngine, TpuEngine],
                                  ids=["host", "tpu"])
BASE = 9_000        # files of the table's first commit: 25 commits' worth
NOW_MS = int(time.time() * 1000)


@pytest.fixture(autouse=True)
def _fresh_parse_cache():
    clear_parse_cache()
    yield
    clear_parse_cache()


def _add(name: str, x: int) -> AddFile:
    """A file whose one column `x` holds x*10 .. x*10+9."""
    return AddFile(
        path=f"{name}.parquet", partitionValues={}, size=100 + x,
        modificationTime=1000 + x, dataChange=True,
        stats=json.dumps({"numRecords": 10, "minValues": {"x": x * 10},
                          "maxValues": {"x": x * 10 + 9},
                          "nullCount": {"x": 0}}))


def _make_table(path, engine, files=BASE, properties=None) -> Table:
    t = Table.for_path(str(path), engine)
    b = t.create_transaction_builder().with_schema(
        StructType([StructField("x", INTEGER)]))
    if properties:
        b = b.with_table_properties(properties)
    b.build().commit()
    txn = t.start_transaction()
    for i in range(files):
        txn.add_file(_add(f"b{i}", i))
    txn.commit()
    return t


def _writer(path) -> Table:
    return Table.for_path(str(path), HostEngine())


def _land(path, i: int, deleted_at=NOW_MS, checkpoint=False) -> None:
    """Another writer's commit `i`: one file more, base file `i` removed
    (so one row superseded and one tombstone a commit), and the
    checkpoint of that version if asked."""
    w = _writer(path)
    txn = w.start_transaction()
    txn.add_file(_add(f"p{i}", BASE + i))
    txn.remove_file(RemoveFile(path=f"b{i}.parquet",
                               deletionTimestamp=deleted_at,
                               dataChange=True))
    txn.commit()
    if checkpoint:
        w.checkpoint()


def _held(path, engine, **kw):
    t = _make_table(path, engine(), **kw)
    snap = t.update()
    snap.state          # replayed, so there is something to advance
    return t, snap


def _cold(path, engine=HostEngine):
    clear_parse_cache()
    return Table.for_path(str(path), engine()).latest_snapshot()


def _rows(table, *key):
    """The rows, less what a checkpoint writer resets: the commit that
    brought an action and its place there, and `dataChange` (false in
    every checkpoint, PROTOCOL.md)."""
    rows = table.drop_columns(["version", "order", "data_change"]).to_pylist()
    return sorted(rows, key=lambda r: tuple(r[k] or "" for k in key))


def _signature(snap):
    """What a reader can ask of a snapshot, and what replay decides."""
    st = snap.state
    return (snap.version, st.num_files, st.size_in_bytes, st.protocol,
            st.metadata, st.set_transactions, st.domain_metadata,
            st.timestamp_ms, snap.timestamp_ms,
            _rows(st.add_files_table, "path", "dv_id"))


def _superseded(state) -> int:
    return int((~state.live_mask & ~state.tombstone_mask).sum())


@contextlib.contextmanager
def recording(out):
    """Spans and counter deltas of what is done inside, into `out`."""
    def counters():
        return dict(obs.metrics_snapshot()["counters"])

    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    before = counters()
    try:
        yield out
    finally:
        out["spans"] = [s.to_dict() for s in obs.get_finished_spans()]
        out["counters"] = {k: v - before.get(k, 0)
                           for k, v in counters().items()
                           if v != before.get(k, 0)
                           and k.startswith("snapshot.")}
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()


def _attrs(out, name):
    [s] = [s for s in out["spans"] if s["name"] == name]
    return s["attrs"]


def _names(out):
    return [s["name"] for s in out["spans"]]


# ------------------------------------------------- (a) state and segment


@ENGINES
@pytest.mark.parametrize("commits,checkpoints", [(1, (1,)), (25, (10, 20))],
                         ids=["one-commit", "25-commits-two-checkpoints"])
def test_a_crossing_equals_a_cold_load(tmp_path, engine, commits,
                                       checkpoints):
    t, held = _held(tmp_path, engine)
    for i in range(1, commits + 1):
        _land(tmp_path, i, checkpoint=i in checkpoints)
    out = {}
    with recording(out):
        crossed = t.update()
    cold = _cold(tmp_path, engine)
    assert crossed is not held and crossed.version == held.version + commits
    assert crossed.log_segment == cold.log_segment
    assert crossed.log_segment.checkpoint_version == 1 + checkpoints[-1]
    assert _signature(crossed) == _signature(cold)
    got = _attrs(out, "table.update")
    assert (got["outcome"], got["crossed"], got["commits"]) == (
        "advanced", "checkpoint", commits)
    assert "reason" not in got
    # every remove is young: the tombstones are the cold load's
    assert (_rows(crossed.state.tombstones_table, "path")
            == _rows(cold.state.tombstones_table, "path"))
    assert t.update() is crossed        # and it is what the table holds


def test_a_crossed_state_keeps_only_expired_tombstones_more(tmp_path):
    t, _ = _held(tmp_path, HostEngine)
    _land(tmp_path, 1, deleted_at=5)            # 1970: past any retention
    _land(tmp_path, 2, checkpoint=True)
    crossed, cold = t.update(), _cold(tmp_path)
    assert _signature(crossed) == _signature(cold)
    mine = _rows(crossed.state.tombstones_table, "path")
    theirs = _rows(cold.state.tombstones_table, "path")
    assert [r["path"] for r in theirs] == ["b2.parquet"]
    assert [r for r in mine if r not in theirs] == [
        r for r in mine if r["deletion_timestamp"] == 5]
    assert [r["path"] for r in mine] == ["b1.parquet", "b2.parquet"]


# ------------------------------------------ (b) what a crossing reads


@ENGINES
def test_a_crossing_opens_no_checkpoint(tmp_path, engine):
    t, held = _held(tmp_path, engine)
    for i in range(1, 4):
        _land(tmp_path, i, checkpoint=i == 3)
    fs = t.engine.fs
    r0, l0 = fs.read_calls, fs.list_calls
    out = {}
    with recording(out):
        crossed = t.update()
        crossed.num_files
    assert crossed.version == held.version + 3
    # the listing that gives up, the commits past the held version, the
    # segment from the hinted checkpoint; one file read singly, the hint
    # (the three commits go through the bulk reader)
    assert fs.list_calls - l0 == 3
    assert fs.read_calls - r0 == 1
    assert _attrs(out, "log.columnarize")["num_commit_files"] == 3
    names = _names(out)
    assert "snapshot.load" not in names and "checkpoint.read_part" not in names
    assert "table.latest_snapshot" not in names
    assert names.count("update.advance") == 1
    assert _attrs(out, "update.advance")["delta_rows"] == 6
    assert _attrs(out, "log.list_commits")["new_commits"] == 3
    # `Snapshot.update()` itself still cannot extend its segment there
    assert out["counters"] == {"snapshot.update_fallbacks.checkpoint": 1,
                               "snapshot.checkpoint_crossings": 1}


# --------------------------------------- (c) the plan after a crossing


def _plan(snap, lo, hi):
    pred = (col("x") >= lit(lo)) & (col("x") < lit(hi))
    return sorted(snap.scan(filter=pred).file_paths())


def _oracle(snap, lo, hi):
    """Min/max intersection over the live files' stats strings."""
    keep = []
    for r in snap.state.add_files_table.select(["path", "stats"]).to_pylist():
        s = json.loads(r["stats"])
        if s["maxValues"]["x"] >= lo and s["minValues"]["x"] < hi:
            keep.append(r["path"])
    return sorted(keep)


@ENGINES
def test_the_plan_after_a_crossing_appends_to_the_index(tmp_path, engine):
    t, held = _held(tmp_path, engine)
    assert len(_plan(held, 100, 200)) == 10         # builds the index
    for i in range(1, 13):
        _land(tmp_path, i, checkpoint=i == 10)
    appends = obs.counter("scan.stats_index_appends")
    builds = obs.counter("scan.stats_index_builds")
    a0, b0 = appends.value, builds.value
    out = {}
    ranges = [(50, 250), ((BASE - 3) * 10, (BASE + 20) * 10)]
    with recording(out):
        crossed = t.update()
        got = [_plan(crossed, lo, hi) for lo, hi in ranges]
    assert crossed.log_segment.checkpoint_version == 11
    advance = _attrs(out, "update.advance")
    assert (advance["stats_index"], advance["stats_index_seed"]) == (
        "released", "kept")
    build = _attrs(out, "stats.index_build")
    assert build["mode"] == "append" and "reason" not in build
    assert (build["rows"], build["dropped"]) == (12, 12)
    assert (appends.value - a0, builds.value - b0) == (1, 0)
    cold = _cold(tmp_path, engine)
    for (lo, hi), paths in zip(ranges, got):
        assert paths == _oracle(cold, lo, hi) == _plan(cold, lo, hi)
    assert "b5.parquet" not in got[0] and "b13.parquet" in got[0]
    assert got[1][-12:] == sorted(f"p{i}.parquet" for i in range(1, 13))


# ------------------------- (d) a crossing that cannot advance the state


def _hole(tmp_path, t):
    for i in range(1, 4):
        _land(tmp_path, i, checkpoint=i == 3)
    os.remove(os.path.join(t.log_path, f"{3:020d}.json"))   # clean-up raced
    return "checkpoint", "gap"


def _protocol(tmp_path, t):
    _land(tmp_path, 1)
    txn = _writer(tmp_path).start_transaction()
    txn.update_protocol(Protocol(minReaderVersion=1, minWriterVersion=4))
    txn.commit()
    _land(tmp_path, 3, checkpoint=True)
    return "checkpoint", "protocol"


def _no_state(tmp_path, t):
    t._cached_snapshot = None
    t.latest_snapshot()                 # held, never replayed
    _land(tmp_path, 1, checkpoint=True)
    return "checkpoint", "no_state"


def _coordinated(tmp_path, t):
    _land(tmp_path, 1, checkpoint=True)
    t._coordinated = True               # as `_merge_unbackfilled` learns it
    return "coordinated", None


@pytest.mark.parametrize("way", [_hole, _protocol, _no_state, _coordinated])
def test_a_crossing_that_cannot_advance_loads_in_full(tmp_path, way):
    t, held = _held(tmp_path, HostEngine)
    reason, why = way(tmp_path, t)
    out = {}
    with recording(out):
        fresh = t.update()
        fresh.state
    got = _attrs(out, "table.update")
    assert (got["outcome"], got["reason"]) == ("full_load", reason)
    assert got.get("not_advanced") == why and "crossed" not in got
    names = _names(out)
    assert names.count("snapshot.load") == 1 and "update.advance" not in names
    assert "snapshot.checkpoint_crossings" not in out["counters"]
    assert "snapshot.checkpoint_crossing_reloads" not in out["counters"]
    cold = _cold(tmp_path)
    assert fresh.log_segment == cold.log_segment
    assert _signature(fresh) == _signature(cold)
    assert _superseded(fresh.state) == 0


# ------------------------------------ (e) where the load is the better way


def _many_superseded(tmp_path, t):
    """Re-adds of one path: each supersedes the row before it."""
    w = _writer(tmp_path)
    for i in range(58):
        txn = w.start_transaction()
        txn.add_file(_add("b0", i))
        txn.commit()
        t.update()                      # plain refreshes: nothing is shed
    state = t.update().state
    assert (_superseded(state), state.live_mask.size) == (58, 458)
    _land(tmp_path, 1, checkpoint=True)     # 58 * 8 > 458


def _far_behind(tmp_path, t):
    for i in (1, 2):                    # two commits are worth 666 rows
        _land(tmp_path, i, checkpoint=i == 2)


@pytest.mark.parametrize("how,reason", [
    (_many_superseded, "superseded_rows"), (_far_behind, "commits_behind")])
def test_a_crossing_takes_the_full_load_where_it_is_the_better_way(
        tmp_path, how, reason):
    t, _ = _held(tmp_path, HostEngine, files=400)
    how(tmp_path, t)
    out = {}
    with recording(out):
        fresh = t.update()
        fresh.state
    got = _attrs(out, "table.update")
    assert (got["outcome"], got["reason"]) == ("full_load", reason)
    assert "not_advanced" not in got and "crossed" not in got
    assert out["counters"]["snapshot.checkpoint_crossing_reloads"] == 1
    assert "snapshot.checkpoint_crossings" not in out["counters"]
    assert _names(out).count("snapshot.load") == 1
    cold = _cold(tmp_path)
    assert _signature(fresh) == _signature(cold)
    # what the load is for: the checkpoint's chunks (its adds, its
    # removes), and no row a cold load lacks
    assert fresh.state.file_actions_raw.column("path").num_chunks == 2
    assert _superseded(fresh.state) == 0
    assert fresh.state.file_actions_raw.num_rows == (
        cold.state.file_actions_raw.num_rows)


# ----------------------- (f) a commit that lands between the two listings


@pytest.mark.parametrize("with_checkpoint", [False, True],
                         ids=["commit", "commit-and-checkpoint"])
def test_a_commit_between_the_listings_is_the_next_updates(
        tmp_path, monkeypatch, with_checkpoint):
    t, held = _held(tmp_path, HostEngine)
    _land(tmp_path, 1)
    _land(tmp_path, 2, checkpoint=True)
    real = table_mod.build_log_segment
    landed = []

    def racing(*args, **kw):
        if not landed:
            landed.append(3)
            _land(tmp_path, 3, checkpoint=with_checkpoint)
        return real(*args, **kw)

    monkeypatch.setattr(table_mod, "build_log_segment", racing)
    crossed = t.update()
    monkeypatch.undo()
    assert landed and crossed.version == held.version + 2
    assert crossed.log_segment == build_log_segment(
        HostEngine().fs, t.log_path, target_version=crossed.version)
    assert crossed.log_segment.checkpoint_version == crossed.version
    assert crossed.num_files == BASE
    after = t.update()
    assert after.version == crossed.version + 1
    assert _signature(after) == _signature(_cold(tmp_path))
    assert after.log_segment == _cold(tmp_path).log_segment


# ----------------------------- (g) a checkpoint written from either state


@ENGINES
def test_the_hook_writes_the_same_checkpoint_from_a_crossed_state(
        tmp_path, engine):
    """Commits 2..19 and the checkpoint at 10 land under a reader that
    holds the table from version 1 on; a second reader loads a copy of
    the table at 19. Each then commits 20, and the hook checkpoints."""
    import shutil

    t, _ = _held(tmp_path / "crossed", engine,
                 properties={"delta.checkpointInterval": "10"})
    for i in range(2, 20):
        # every third remove is long expired
        _land(tmp_path / "crossed", i,
              deleted_at=5 if i % 3 == 0 else NOW_MS)
    shutil.copytree(tmp_path / "crossed", tmp_path / "cold")
    written, rows = [], []
    crossings = obs.counter("snapshot.checkpoint_crossings")
    c0 = crossings.value
    for table in (t, Table.for_path(str(tmp_path / "cold"), engine())):
        snap = table.update()
        assert crossings.value - c0 == 1    # the first reader's
        assert snap.version == 19
        assert snap.log_segment.checkpoint_version == 10
        rows.append(snap.state.file_actions_raw.num_rows)
        txn = table.start_transaction()
        txn.add_file(_add("p20", BASE + 20))
        txn.commit()
        with open(os.path.join(
                table.log_path, f"{20:020d}.checkpoint.parquet"), "rb") as f:
            written.append(f.read())
    assert written[0] == written[1]
    # from a state that holds what the checkpoint at 10 had shed
    assert rows[0] > rows[1]


# ------------------------------------- (h) what a long-lived reader holds


def test_forty_crossings_hold_one_state_one_snapshot_one_index(tmp_path):
    """Of every snapshot, state and index the reader was handed, only
    the last stays alive (by weak reference, not by a census of the
    process: the writer that lands the checkpoints lives in it too)."""
    import weakref

    t, snap = _held(tmp_path, HostEngine, files=1_000)
    _plan(snap, 0, 100)
    crossings = obs.counter("snapshot.checkpoint_crossings")
    c0 = crossings.value
    handed = []
    for i in range(1, 41):
        _land(tmp_path, i, checkpoint=True)
        snap = t.update()
        assert _plan(snap, 0, 100) == ["b0.parquet"] + [
            f"b{j}.parquet" for j in range(i + 1, 10)]
        assert snap.log_segment.checkpoint_version == snap.version == i + 1
        handed.append([weakref.ref(o) for o in (
            snap, snap.state, snap.state.stats_index)])
    assert crossings.value - c0 == 40
    del snap
    gc.collect()
    alive = [[ref() is not None for ref in refs] for refs in handed]
    assert alive == [[False] * 3] * 39 + [[True] * 3]
    assert _signature(t.update()) == _signature(_cold(tmp_path))


# ------------------------------------------------ the listing of commits


def test_list_commits_after_lists_singles_past_checkpoints(tmp_path):
    t, held = _held(tmp_path, HostEngine, files=10)
    for i in range(1, 5):
        _land(tmp_path, i, checkpoint=i == 2)
    fs = t.engine.fs
    commits = list_commits_after(fs, held.log_segment)
    assert [os.path.basename(f.path) for f in commits] == [
        f"{v:020d}.json" for v in range(2, 6)]
    # stat-deferred, as a full listing's: the parse cache's keys match
    cold = build_log_segment(fs, t.log_path)
    assert commits[-2:] == cold.deltas
    assert list_commits_after(fs, cold) == []


def test_list_commits_after_names_a_gap(tmp_path):
    t, held = _held(tmp_path, HostEngine, files=10)
    for i in range(1, 4):
        _land(tmp_path, i)
    os.remove(os.path.join(t.log_path, f"{3:020d}.json"))
    with pytest.raises(_IncrementalUnavailable) as e:
        list_commits_after(t.engine.fs, held.log_segment)
    assert e.value.reason == "gap"


def test_snapshot_update_still_cannot_extend_past_a_checkpoint(tmp_path):
    t, held = _held(tmp_path, HostEngine)
    _land(tmp_path, 1, checkpoint=True)
    assert held.update() is None        # the segment cannot be extended
    assert t.update().version == held.version + 1   # the state can
    assert np.array_equal(held.state.live_mask[:BASE], np.ones(BASE, bool))
