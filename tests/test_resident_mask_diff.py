"""The masks of a refresh on the resident lanes (PR 52): an append
patches the last append's masks where the winner words changed, and
rebuilds them over every slot only with nothing to diff against. Held
here on the 8-emulated-device mesh: the diff equals the full rebuild of
the same words and the host route's masks over the same rows, bit for
bit, over chains of deltas of every shape a commit can have; a
snapshot's masks are never written to once handed out; which appends
rebuild; and that establishment unpacks nothing."""

import types

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import obs

pytestmark = pytest.mark.sharded8

COMMITS, FILES = 8, 1040    # ~1,041 rows a shard: m = 2,048, half free


def make_table(path, n_commits, files_per_commit):
    """`tests/test_sharded_replay.py::_tpu_table`: commit `i` adds
    `p{i}_{j}.parquet` and removes `p{i-1}_0.parquet`; under
    `delta.checkpointInterval`, so a load replays JSON and keeps its
    lanes."""
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.models.actions import AddFile, RemoveFile
    from delta_tpu.models.schema import INTEGER, StructField, StructType
    from delta_tpu.table import Table

    t = Table.for_path(str(path), TpuEngine(replay_shards=8))
    t.create_transaction_builder().with_schema(
        StructType([StructField("x", INTEGER)])).build().commit()
    for i in range(n_commits):
        txn = t.start_transaction()
        for j in range(files_per_commit):
            txn.add_file(AddFile(
                path=f"p{i}_{j}.parquet", partitionValues={}, size=100 + j,
                modificationTime=1000 + i, dataChange=True))
        if i > 0:
            txn.remove_file(RemoveFile(
                path=f"p{i - 1}_0.parquet", deletionTimestamp=2000 + i,
                dataChange=True))
        txn.commit()
    return t


def loaded(path):
    """(snapshot, its resident state) of a fresh load: a case fails, it
    does not skip, where the load kept no lanes."""
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.table import Table

    snap = Table.for_path(
        str(path), TpuEngine(replay_shards=8)).latest_snapshot()
    _ = snap.state.live_mask  # force replay
    res = snap._state.resident
    assert res is not None, "sharded load did not establish residency"
    return snap, res


@pytest.fixture(scope="module")
def base_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("base")
    make_table(path, COMMITS, FILES)
    return path


def delta_table(rows, version):
    """One commit's file actions, `rows` = [(path, is_add), ...] in the
    order they take effect."""
    return pa.table({
        "path": pa.array([p for p, _ in rows], pa.string()),
        "dv_id": pa.array([None] * len(rows), pa.string()),
        "version": pa.array([version] * len(rows), pa.int64()),
        "order": pa.array(range(len(rows)), pa.int32()),
        "is_add": pa.array([a for _, a in rows], pa.bool_()),
    })


def full_rebuild(res, words):
    """Both masks from nothing over every slot, as every append made
    them until PR 52."""
    winner = np.unpackbits(
        words.view(np.uint8).reshape(res.n_shards, -1),
        axis=1, bitorder="little")[:, :res.m].astype(bool)
    valid = res.scatter >= 0
    live = np.zeros(res.n, bool)
    tomb = np.zeros(res.n, bool)
    live[res.scatter[valid]] = (winner & res.add)[valid]
    tomb[res.scatter[valid]] = (winner & ~res.add)[valid]
    return live, tomb


def base(i, j):
    return f"p{i}_{j}.parquet"


def adds(names):
    return [(n, True) for n in names]


def removes(names):
    return [(n, False) for n in names]


NEW = [f"new_{k}.parquet" for k in range(2500)]
# a chain of deltas a case: the first append of a load rebuilds, so the
# shape under test is the second delta's or a later one's
CHAINS = {
    "adds_only": [adds(NEW[:80]), adds(NEW[80:160]), adds(NEW[160:161])],
    "removes_of_base_paths": [
        adds(NEW[:5]),
        removes([base(2, 3), base(3, 4), base(7, 0)]),
        removes([base(i, 7) for i in range(COMMITS)])],
    "remove_of_a_path_an_earlier_delta_added": [
        adds(NEW[:40]), removes(NEW[10:20]),
        adds(NEW[40:50]) + removes([NEW[45], NEW[0]])],
    "re_add_of_a_removed_path": [
        removes([base(2, 3)]),
        adds([base(2, 3), base(0, 0)]),     # removed just now, and by the base
        removes([base(2, 3)]), adds([base(2, 3)])],
    "one_path_twice_in_one_delta": [
        adds(NEW[:3]),
        adds(["twice.parquet", "twice.parquet"])
        + [("gone.parquet", True), ("gone.parquet", False)]
        + [(base(4, 4), False), (base(4, 4), True)]
        + [(NEW[1], False), (NEW[1], False)]],
    "removes_only_of_2000_paths": [
        adds(NEW[:1000]),
        removes(NEW[:600] + [base(i, j) for i in range(4)
                             for j in range(1, 351)])],
    "every_row_superseded_inside_the_delta": [
        adds(NEW[:3]),
        # nothing held changes: each path comes and goes in the delta
        [(n, a) for n in NEW[100:140] for a in (True, False)]],
    "a_delta_of_no_rows": [adds(NEW[:3]), [], adds(NEW[3:6])],
}


@pytest.mark.parametrize("case", CHAINS)
def test_a_diff_append_equals_the_full_rebuild_and_the_host_route(
        base_dir, case):
    from delta_tpu.replay.state import _advance_masks_host

    snap, res = loaded(base_dir)
    state = snap._state
    # the host route's chain beside the lanes': the rows held and both
    # masks, advanced by `_advance_masks_host` alone
    host = types.SimpleNamespace(
        file_actions_raw=state.file_actions_raw.select(["path", "dv_id"]),
        live_mask=np.asarray(state.live_mask),
        tombstone_mask=np.asarray(state.tombstone_mask))
    diffs, rebuilds = (obs.counter("replay.resident_mask_diffs"),
                       obs.counter("replay.resident_mask_rebuilds"))
    diffs0, rebuilds0 = diffs.value, rebuilds.value

    for k, rows in enumerate(CHAINS[case]):
        delta = delta_table(rows, version=100 + k)
        n_prev = res.n
        got = res.append(delta, n_prev=n_prev)
        assert got is not None, f"delta {k} fell back"
        live, tomb = got
        assert live.dtype == tomb.dtype == bool
        assert len(live) == len(tomb) == n_prev + len(rows)

        want_live, want_tomb = full_rebuild(res, res._last[0])
        np.testing.assert_array_equal(live, want_live)
        np.testing.assert_array_equal(tomb, want_tomb)

        host_live, host_tomb = _advance_masks_host(host, delta, True)
        np.testing.assert_array_equal(live, host_live)
        np.testing.assert_array_equal(tomb, host_tomb)
        host = types.SimpleNamespace(
            file_actions_raw=pa.concat_tables(
                [host.file_actions_raw, delta.select(["path", "dv_id"])]),
            live_mask=host_live, tombstone_mask=host_tomb)

    # the shape under test went through the diff, not the base case
    assert rebuilds.value - rebuilds0 == 1
    assert diffs.value - diffs0 == len(CHAINS[case]) - 1
    # and the chain ends where a replay of all of it ends
    paths = host.file_actions_raw.column("path").to_pylist()
    assert not (live & tomb).any()
    last = {}
    for p, a in [r for rows in CHAINS[case] for r in rows]:
        last[p] = a
    held = {p for p, on in zip(paths, live) if on}
    for p, a in last.items():
        assert (p in held) == a, p


def test_a_snapshots_masks_are_never_written_to(tmp_path):
    """A reader that holds version k while the writer lands k + 1 sees
    what it saw: the arrays append k returned are unchanged, and not the
    same objects, after append k + 1 (through `Table.update`, the route
    a refresh takes)."""
    from delta_tpu.models.actions import AddFile, RemoveFile

    # four commits and four more: under `delta.checkpointInterval`
    t = make_table(tmp_path, 4, 20)
    snap = t.latest_snapshot()
    _ = snap.state.live_mask
    assert snap._state.resident is not None, \
        "sharded load did not establish residency"
    appends = obs.counter("replay.resident_appends")
    held = []
    for k in range(4):
        before = appends.value
        txn = t.start_transaction()
        for j in range(10):
            txn.add_file(AddFile(
                path=f"inc{k}_{j}.parquet", partitionValues={}, size=50,
                modificationTime=5000 + k, dataChange=True))
        # each commit takes a base path and the commit before's first add
        txn.remove_file(RemoveFile(path=f"p3_{k + 1}.parquet",
                                   deletionTimestamp=6000, dataChange=True))
        if k:
            txn.remove_file(RemoveFile(path=f"inc{k - 1}_0.parquet",
                                       deletionTimestamp=6000,
                                       dataChange=True))
        txn.commit()
        new = t.update()
        assert appends.value == before + 1, f"refresh {k} left the lanes"
        st = new._state
        for old, live_was, tomb_was in held:
            assert old.live_mask is not st.live_mask
            assert old.tombstone_mask is not st.tombstone_mask
            assert not np.shares_memory(old.live_mask, st.live_mask)
            assert not np.shares_memory(old.tombstone_mask,
                                        st.tombstone_mask)
            np.testing.assert_array_equal(old.live_mask, live_was)
            np.testing.assert_array_equal(old.tombstone_mask, tomb_was)
        # the masks changed under the rows the older snapshots hold, so
        # a write in place would have shown
        if held:
            old = held[-1][0]
            n = len(old.live_mask)
            assert (st.live_mask[:n] != old.live_mask).any()
        held.append((st, st.live_mask.copy(), st.tombstone_mask.copy()))


def traced(fn):
    """(`fn()`'s result, the spans it finished, as dicts): verbose,
    because a table of a test's size is under `obs.PHASE_SPAN_ROWS`."""
    obs.set_trace_mode("verbose")
    obs.reset_trace_buffer()
    try:
        result = fn()
        return result, [s.to_dict() for s in obs.get_finished_spans()]
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()


def test_the_first_append_rebuilds_and_every_later_one_diffs(base_dir):
    """The base case is decided from what the state holds: nothing to
    diff against after establishment, and after the kept words were
    dropped; a last mask of another length likewise."""
    _, res = loaded(base_dir)
    assert res._last is None        # establishment kept and unpacked nothing
    diffs, rebuilds = (obs.counter("replay.resident_mask_diffs"),
                       obs.counter("replay.resident_mask_rebuilds"))
    slots = res.n_shards * res.m

    def append(k, rows):
        d0, r0 = diffs.value, rebuilds.value
        masks, spans = traced(
            lambda: res.append(delta_table(rows, 100 + k), n_prev=res.n))
        assert masks is not None
        [span] = [s for s in spans if s["name"] == "resident.masks"]
        return span["attrs"], diffs.value - d0, rebuilds.value - r0

    attrs, d, r = append(0, adds(NEW[:10]))
    assert attrs == {"slots": slots, "mode": "full"} and (d, r) == (0, 1)
    attrs, d, r = append(1, adds(NEW[10:20]) + removes([base(1, 1)]))
    # ten new winners, a remove that wins and the add it beat
    assert attrs["mode"] == "diff" and (d, r) == (1, 0)
    assert attrs["slots"] == slots and attrs["changed_slots"] == 12
    assert 1 <= attrs["changed_words"] <= 12
    attrs, d, r = append(2, removes([NEW[0]]))
    assert (attrs["mode"], attrs["changed_slots"]) == ("diff", 2)

    res._last = None                    # the kept words dropped
    attrs, d, r = append(3, adds(NEW[20:30]))
    assert attrs == {"slots": slots, "mode": "full"} and (d, r) == (0, 1)
    attrs, d, r = append(4, adds(NEW[30:40]))
    assert attrs["mode"] == "diff" and (d, r) == (1, 0)

    words, live, tomb = res._last       # last masks that are not n_prev long
    res._last = (words, live[:-1], tomb[:-1])
    attrs, d, r = append(5, adds(NEW[40:50]))
    assert attrs["mode"] == "full" and (d, r) == (0, 1)
    res._last = (res._last[0][:, :-1],) + res._last[1:]   # words of another m
    attrs, d, r = append(6, adds(NEW[50:60]))
    assert attrs["mode"] == "full" and (d, r) == (0, 1)
    attrs, d, r = append(7, adds(NEW[60:70]))
    assert attrs["mode"] == "diff" and (d, r) == (1, 0)


def test_establishment_unpacks_no_winner_words(base_dir):
    """A load that is never refreshed pays for nothing of this: its span
    tree has `replay.resident_establish` and no `resident.masks`, and
    the state it keeps holds no words and no masks."""
    rebuilds = obs.counter("replay.resident_mask_rebuilds")
    diffs = obs.counter("replay.resident_mask_diffs")
    before = rebuilds.value, diffs.value
    (_, res), spans = traced(lambda: loaded(base_dir))
    names = {s["name"] for s in spans}
    assert "replay.resident_establish" in names
    assert not {n for n in names if n.startswith("resident.")}
    assert res._last is None
    assert (rebuilds.value, diffs.value) == before
