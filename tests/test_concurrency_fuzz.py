"""Conflict-matrix pair races + randomized multi-writer fuzz.

Reference analogue: `OptimizeConflictSuite` / `ConflictChecker.scala`'s
taxonomy driven through the phase-locking fuzzer
(`fuzzer/OptimisticTransactionPhases.scala`). The pair tests park one
writer at a precise phase (including the new `after_prepare` boundary),
let the other win, and assert the loser's exact outcome per the conflict
matrix. The randomized fuzz runs 4 writers with a seeded release
schedule and checks global invariants: contiguous unique versions, only
taxonomy errors, no double-delete of any file in the committed log, and
engine/oracle agreement on the final state.
"""

import json
import os
import random
import threading

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu.concurrency import PhaseLockingObserver, run_txn_async
from delta_tpu.errors import (
    ConcurrentAppendError,
    ConcurrentDeleteDeleteError,
    ConcurrentDeleteReadError,
    ConcurrentModificationError,
    ConcurrentTransactionError,
    MetadataChangedError,
    ProtocolChangedError,
)
from delta_tpu.models.actions import AddFile
from delta_tpu.table import Table

TAXONOMY = (
    ConcurrentAppendError, ConcurrentDeleteDeleteError,
    ConcurrentDeleteReadError, ConcurrentTransactionError,
    MetadataChangedError, ProtocolChangedError,
)


def _batch(start, n):
    return pa.table({"id": pa.array(np.arange(start, start + n,
                                              dtype=np.int64))})


def _add(path, size=10, data_change=True):
    return AddFile(path=path, size=size, modificationTime=1,
                   dataChange=data_change)


def _optimize_txn(table, victims, out_name):
    """Emulate OPTIMIZE's transaction shape: read the table, remove the
    compacted inputs (dataChange=False), add the coalesced output."""
    txn = table.start_transaction("OPTIMIZE")
    txn.scan_files()
    for f in victims:
        txn.remove_file(f.remove(deletion_timestamp=1, data_change=False))
    txn.add_file(_add(out_name, size=sum(f.size for f in victims),
                      data_change=False))
    return txn


def _delete_txn(table, victim):
    txn = table.start_transaction("DELETE")
    txn.remove_file(victim.remove(deletion_timestamp=2))
    return txn


# ------------------------------------------------------------ matrix pairs


def test_optimize_loses_to_delete_of_same_file(tmp_table_path):
    """delete x optimize: the winner deleted a file the optimizer READ
    (its compaction input) -> ConcurrentDeleteReadError — the read-set
    check fires before the remove-set check, exactly the reference's
    `ConflictChecker.scala:584` ordering for OptimizeConflictSuite."""
    dta.write_table(tmp_table_path, _batch(0, 10), target_rows_per_file=5)
    table = Table.for_path(tmp_table_path)
    files = table.latest_snapshot().state.add_files()
    assert len(files) >= 2

    obs = PhaseLockingObserver(block_after_prepare=True)
    opt = _optimize_txn(table, files[:2], "compacted-a.parquet")
    opt.observer = obs
    thread = run_txn_async(opt.commit)
    obs.after_prepare_barrier.wait_for_arrival()  # fully prepared, unwritten

    _delete_txn(table, files[0]).commit()

    obs.after_prepare_barrier.unblock()
    with pytest.raises(ConcurrentDeleteReadError):
        thread.join_result()


def test_delete_loses_to_optimize_of_same_file(tmp_table_path):
    dta.write_table(tmp_table_path, _batch(0, 10), target_rows_per_file=5)
    table = Table.for_path(tmp_table_path)
    files = table.latest_snapshot().state.add_files()

    obs = PhaseLockingObserver(block_before_commit=True)
    dele = _delete_txn(table, files[0])
    dele.observer = obs
    thread = run_txn_async(dele.commit)
    obs.before_commit_barrier.wait_for_arrival()

    _optimize_txn(table, files, "compacted-b.parquet").commit()

    obs.before_commit_barrier.unblock()
    with pytest.raises(ConcurrentDeleteDeleteError):
        thread.join_result()


def test_optimize_survives_concurrent_append(tmp_table_path):
    """append x optimize: disjoint files -> the optimizer rebases and
    commits (appends don't invalidate a compaction's inputs under
    WriteSerializable)."""
    dta.write_table(tmp_table_path, _batch(0, 10), target_rows_per_file=5)
    table = Table.for_path(tmp_table_path)
    files = table.latest_snapshot().state.add_files()

    obs = PhaseLockingObserver(block_after_prepare=True)
    opt = _optimize_txn(table, files, "compacted-c.parquet")
    opt.observer = obs
    thread = run_txn_async(opt.commit)
    obs.after_prepare_barrier.wait_for_arrival()

    txn_b = table.start_transaction()
    txn_b.add_file(_add("fresh.parquet"))
    res_b = txn_b.commit()

    obs.after_prepare_barrier.unblock()
    res = thread.join_result()
    assert res.version == res_b.version + 1
    paths = set(table.latest_snapshot().state.add_files_table
                .column("path").to_pylist())
    assert "compacted-c.parquet" in paths and "fresh.parquet" in paths
    assert not any(f.path in paths for f in files)


def test_metadata_change_beats_optimize(tmp_table_path):
    import dataclasses

    dta.write_table(tmp_table_path, _batch(0, 10), target_rows_per_file=5)
    table = Table.for_path(tmp_table_path)
    files = table.latest_snapshot().state.add_files()

    obs = PhaseLockingObserver(block_before_commit=True)
    opt = _optimize_txn(table, files, "compacted-d.parquet")
    opt.observer = obs
    thread = run_txn_async(opt.commit)
    obs.before_commit_barrier.wait_for_arrival()

    txn_m = table.start_transaction("SET TBLPROPERTIES")
    meta = txn_m.metadata()
    txn_m.update_metadata(dataclasses.replace(
        meta, configuration={**meta.configuration, "foo": "bar"}))
    txn_m.commit()

    obs.before_commit_barrier.unblock()
    with pytest.raises(MetadataChangedError):
        thread.join_result()


def test_backfill_phase_hook_fires_for_coordinated_commits(coordinated_path):
    table = Table.for_path(coordinated_path)
    obs = PhaseLockingObserver()  # all barriers pass-through; events record
    txn = table.start_transaction()
    txn.add_file(_add("cc.parquet"))
    txn.observer = obs
    txn.commit()
    kinds = [k for k, _ in obs.events]
    assert kinds == ["attempt", "prepared", "backfilled", "committed"]


# --------------------------------------------------------- randomized fuzz


@pytest.mark.parametrize("seed", [7, 21, 1234])
def test_multi_writer_fuzz(tmp_table_path, seed):
    """4 writers, randomized release order, mixed op types. Invariants:
    contiguous unique versions; every failure is a taxonomy error; no
    file removed twice in the committed log without an interleaving
    re-add; both engines agree with the independent oracle at the end."""
    rng = random.Random(seed)
    dta.write_table(tmp_table_path, _batch(0, 40), target_rows_per_file=5)
    table = Table.for_path(tmp_table_path)
    base_files = table.latest_snapshot().state.add_files()
    assert len(base_files) == 8

    def writer(kind, i):
        t = Table.for_path(tmp_table_path)  # fresh snapshot per writer
        if kind == "append":
            txn = t.start_transaction()
            txn.add_file(_add(f"app-{i}.parquet"))
        elif kind == "delete":
            txn = _delete_txn(t, base_files[i % len(base_files)])
        elif kind == "optimize":
            fs = t.latest_snapshot().state.add_files()
            victims = [f for f in fs if f.path.startswith("part-")][:2]
            if not victims:
                txn = t.start_transaction()
                txn.add_file(_add(f"app-x{i}.parquet"))
            else:
                txn = _optimize_txn(t, victims, f"opt-{i}.parquet")
        elif kind == "metadata":
            import dataclasses

            txn = t.start_transaction("SET TBLPROPERTIES")
            meta = txn.metadata()
            txn.update_metadata(dataclasses.replace(
                meta,
                configuration={**meta.configuration, f"k{i}": str(i)}))
        else:  # txn
            txn = t.start_transaction("STREAMING UPDATE")
            txn.set_transaction(f"app{i % 2}", i)
            txn.add_file(_add(f"stream-{i}.parquet"))
        obs = PhaseLockingObserver(block_before_commit=True)
        txn.observer = obs
        return txn, obs

    kinds = ["append", "delete", "optimize", "metadata", "txn"]
    picks = [rng.choice(kinds) for _ in range(4)]
    txns = [writer(k, i) for i, k in enumerate(picks)]
    threads = [run_txn_async(txn.commit) for txn, _ in txns]
    for _, obs in txns:
        obs.before_commit_barrier.wait_for_arrival()
    order = list(range(4))
    rng.shuffle(order)
    for j in order:
        txns[j][1].before_commit_barrier.unblock()

    outcomes = []
    for th in threads:
        try:
            outcomes.append(("ok", th.join_result(timeout=120)))
        except ConcurrentModificationError as e:
            assert isinstance(e, TAXONOMY), type(e)
            outcomes.append(("conflict", e))

    committed = sorted(r.version for s, r in outcomes if s == "ok")
    assert len(set(committed)) == len(committed), "duplicate commit version"
    if committed:
        assert committed == list(range(committed[0], committed[-1] + 1)), \
            "committed versions not contiguous"

    # raw-log invariant: a path is never removed twice without a re-add
    log = os.path.join(tmp_table_path, "_delta_log")
    state = {}
    for name in sorted(os.listdir(log)):
        if not name.endswith(".json") or "." in name[:-5]:
            continue
        with open(os.path.join(log, name)) as f:
            for ln in f:
                if not ln.strip():
                    continue
                act = json.loads(ln)
                if "add" in act:
                    state[act["add"]["path"]] = "live"
                elif "remove" in act:
                    p = act["remove"]["path"]
                    assert state.get(p) != "removed", \
                        f"{p} removed twice in the committed log"
                    state[p] = "removed"

    # final state: engines agree with the independent oracle
    from delta_tpu.engine.host import HostEngine
    from delta_tpu.engine.tpu import TpuEngine

    from chipbench.reference.oracle import read_table_state

    oracle = read_table_state(tmp_table_path).summary()
    for eng in (HostEngine(), TpuEngine()):
        snap = Table.for_path(tmp_table_path, eng).latest_snapshot()
        mine = sorted(snap.state.add_files_table.column("path").to_pylist())
        theirs = sorted(k.split("|")[0] for k in oracle["live_keys"])
        assert mine == theirs
