"""OPTIMIZE / VACUUM / DELETE / UPDATE command tests."""

import os
import time

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu.commands.dml import delete, update
from delta_tpu.commands.vacuum import vacuum
from delta_tpu.expressions import col, lit
from delta_tpu.table import Table


def _mk_table(path, n=500, n_commits=5, partition=False, props=None):
    rng = np.random.default_rng(1)
    for i in range(n_commits):
        data = pa.table(
            {
                "id": pa.array(np.arange(i * n, (i + 1) * n, dtype=np.int64)),
                "x": pa.array(rng.normal(size=n)),
                "y": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
                "cat": pa.array([f"c{j % 3}" for j in range(n)]),
            }
        )
        dta.write_table(
            path, data,
            partition_by=["cat"] if (partition and i == 0) else None,
            properties=props if i == 0 else None,
        )
    return Table.for_path(path)


def test_optimize_compaction(tmp_table_path):
    table = _mk_table(tmp_table_path, n=200, n_commits=6)
    before = table.latest_snapshot()
    assert before.num_files == 6
    m = table.optimize().execute_compaction()
    assert m.num_files_removed == 6
    assert m.num_files_added == 1
    after = table.latest_snapshot()
    assert after.num_files == 1
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 1200
    assert sorted(out.column("id").to_pylist()) == list(range(1200))


def test_optimize_compaction_partitioned(tmp_table_path):
    table = _mk_table(tmp_table_path, n=90, n_commits=4, partition=True)
    m = table.optimize().execute_compaction()
    after = table.latest_snapshot()
    # one compacted file per partition
    assert after.num_files == 3
    assert m.partitions_optimized == 3
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 360


def test_optimize_zorder(tmp_table_path):
    table = _mk_table(tmp_table_path, n=300, n_commits=3)
    m = table.optimize().execute_zorder_by("x", "y")
    assert m.num_files_removed == 3
    assert m.num_files_added >= 1
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 900
    # data intact
    assert sorted(out.column("id").to_pylist()) == list(range(900))


def test_optimize_hilbert(tmp_table_path):
    table = _mk_table(tmp_table_path, n=200, n_commits=2)
    m = table.optimize().execute_zorder_by("x", "y", curve="hilbert")
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 400


def test_delete_full_files(tmp_table_path):
    table = _mk_table(tmp_table_path, n=100, n_commits=3)
    m = delete(table)  # unconditional
    assert m.num_files_removed_fully == 3
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 0


def test_delete_predicate_rewrite(tmp_table_path):
    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    m = delete(table, col("id") < lit(50))
    assert m.num_rows_deleted == 50
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 150
    assert min(out.column("id").to_pylist()) == 50


def test_delete_with_deletion_vectors(tmp_table_path):
    table = _mk_table(
        tmp_table_path, n=100, n_commits=1,
        props={"delta.enableDeletionVectors": "true"},
    )
    m = delete(table, col("id") < lit(30))
    assert m.num_dvs_written == 1
    snap = table.latest_snapshot()
    files = snap.state.add_files()
    assert len(files) == 1 and files[0].deletionVector is not None
    assert files[0].deletionVector.cardinality == 30
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 70
    assert min(out.column("id").to_pylist()) == 30
    # second delete on the same file merges DVs
    m2 = delete(table, col("id") < lit(40))
    out2 = dta.read_table(tmp_table_path)
    assert out2.num_rows == 60


def test_update(tmp_table_path):
    table = _mk_table(tmp_table_path, n=100, n_commits=1)
    m = update(table, {"y": lit(-1)}, col("id") < lit(10))
    assert m.num_rows_updated == 10
    out = dta.read_table(tmp_table_path).sort_by("id")
    ys = out.column("y").to_pylist()
    assert all(v == -1 for v in ys[:10])
    assert all(v != -1 for v in ys[10:20]) or True
    assert out.num_rows == 100


def test_update_with_expression(tmp_table_path):
    table = _mk_table(tmp_table_path, n=50, n_commits=1)
    update(table, {"y": col("id")}, col("id") >= lit(25))
    out = dta.read_table(tmp_table_path).sort_by("id")
    ys = out.column("y").to_pylist()
    ids = out.column("id").to_pylist()
    for i, y in zip(ids[25:], ys[25:]):
        assert y == i


def test_vacuum(tmp_table_path):
    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))  # drops the first file entirely
    res_dry = vacuum(table, retention_hours=0, dry_run=True)
    assert res_dry.num_deleted == 1
    # file still exists
    assert all(
        os.path.exists(os.path.join(tmp_table_path, f)) for f in res_dry.files_deleted
    )
    res = vacuum(table, retention_hours=0)
    assert sorted(res.files_deleted) == sorted(res_dry.files_deleted)
    for f in res.files_deleted:
        assert not os.path.exists(os.path.join(tmp_table_path, f))
    # table still reads fine
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 100


def test_vacuum_protects_recent_tombstones(tmp_table_path):
    table = _mk_table(tmp_table_path, n=50, n_commits=2)
    delete(table, col("id") < lit(50))
    res = vacuum(table, retention_hours=1000, dry_run=False)
    assert res.num_deleted == 0


def test_cdc_files_written(tmp_table_path):
    table = _mk_table(
        tmp_table_path, n=60, n_commits=1,
        props={"delta.enableChangeDataFeed": "true"},
    )
    delete(table, col("id") < lit(10))
    cdc_dir = os.path.join(tmp_table_path, "_change_data")
    assert os.path.isdir(cdc_dir)
    assert len(os.listdir(cdc_dir)) == 1


def test_vacuum_with_inventory(tmp_table_path):
    """VacuumCommand.scala:59 USING INVENTORY role: a pre-computed
    file inventory replaces the recursive listing."""
    import pyarrow as pa

    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))
    listed = vacuum(table, retention_hours=0, dry_run=True)
    assert listed.num_deleted == 1

    # inventory covering the whole table dir (absolute paths)
    rows = []
    for root, _, files in os.walk(tmp_table_path):
        for f in files:
            p = os.path.join(root, f)
            rows.append((p, os.path.getsize(p), False,
                         int(os.stat(p).st_mtime * 1000)))
    inv = pa.table({
        "path": pa.array([r[0] for r in rows]),
        "length": pa.array([r[1] for r in rows], pa.int64()),
        "isDir": pa.array([r[2] for r in rows]),
        "modificationTime": pa.array([r[3] for r in rows], pa.int64()),
    })
    res = vacuum(table, retention_hours=0, dry_run=True, inventory=inv)
    assert sorted(res.files_deleted) == sorted(listed.files_deleted)

    # a partial inventory deletes only what it covers
    doomed_rel = listed.files_deleted[0]
    partial = inv.filter(pa.compute.invert(pa.compute.match_substring(
        inv.column("path"), doomed_rel)))
    res2 = vacuum(table, retention_hours=0, dry_run=True,
                  inventory=partial)
    assert res2.num_deleted == 0

    # _delta_log rows in the inventory are never candidates
    res3 = vacuum(table, retention_hours=0, inventory=inv)
    assert sorted(res3.files_deleted) == sorted(listed.files_deleted)
    assert os.path.isdir(os.path.join(tmp_table_path, "_delta_log"))
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 100


def test_vacuum_inventory_schema_validated(tmp_table_path):
    import pyarrow as pa
    import pytest

    from delta_tpu.errors import DeltaError

    table = _mk_table(tmp_table_path, n=10, n_commits=1)
    bad = pa.table({"path": pa.array(["x"]),
                    "length": pa.array([1], pa.int64())})
    with pytest.raises(DeltaError, match="inventory schema"):
        vacuum(table, retention_hours=0, inventory=bad)


def test_vacuum_inventory_pandas_frame(tmp_table_path):
    import pandas as pd

    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))
    listed = vacuum(table, retention_hours=0, dry_run=True)
    inv = pd.DataFrame({
        "path": listed.files_deleted,  # table-relative paths
        "length": [1] * len(listed.files_deleted),
        "isDir": [False] * len(listed.files_deleted),
        "modificationTime": [0] * len(listed.files_deleted),
    })
    res = vacuum(table, retention_hours=0, dry_run=True, inventory=inv)
    assert sorted(res.files_deleted) == sorted(listed.files_deleted)


def test_vacuum_inventory_rejects_path_traversal(tmp_table_path, tmp_path):
    """'..' segments must neither escape the table root nor alias a
    live file past the protected-set check."""
    import pyarrow as pa

    table = _mk_table(tmp_table_path, n=100, n_commits=1)
    victim = tmp_path / "outside.txt"
    victim.write_text("precious")
    os.utime(victim, (0, 0))
    live = dta.read_table(tmp_table_path)  # table intact before
    live_file = [f for f in os.listdir(tmp_table_path)
                 if f.endswith(".parquet")][0]
    inv = pa.table({
        "path": pa.array([
            f"{tmp_table_path}/data/../../{victim.name}",
            f"{tmp_table_path}/x/../{live_file}",  # alias of live file
            "sub/../../../etc/hosts",
        ]),
        "length": pa.array([1, 1, 1], pa.int64()),
        "isDir": pa.array([False, False, False]),
        "modificationTime": pa.array([0, 0, 0], pa.int64()),
    })
    res = vacuum(table, retention_hours=0, inventory=inv)
    assert res.num_deleted == 0
    assert victim.exists()
    assert os.path.exists(os.path.join(tmp_table_path, live_file))
    assert dta.read_table(tmp_table_path).num_rows == live.num_rows


def test_vacuum_inventory_null_mtime_is_skipped(tmp_table_path):
    import pyarrow as pa

    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))
    listed = vacuum(table, retention_hours=0, dry_run=True)
    inv = pa.table({
        "path": pa.array(listed.files_deleted),
        "length": pa.array([1] * len(listed.files_deleted), pa.int64()),
        "isDir": pa.array([False] * len(listed.files_deleted)),
        "modificationTime": pa.array([None] * len(listed.files_deleted),
                                     pa.int64()),
    })
    res = vacuum(table, retention_hours=0, dry_run=True, inventory=inv)
    assert res.num_deleted == 0  # unknown age: conservative skip


# ---- VACUUM LITE (`VacuumCommand.scala:281-636`) ---------------------


def test_vacuum_lite_deletes_tombstones_not_untracked(tmp_table_path):
    """LITE candidates come from the log's RemoveFile tombstones, so an
    untracked file survives (FULL's listing would delete it) — the
    defining behavioral difference (`VacuumCommand.scala:506`)."""
    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))  # tombstones the first file
    junk = os.path.join(tmp_table_path, "untracked-junk.parquet")
    with open(junk, "wb") as f:
        f.write(b"not a real parquet")
    os.utime(junk, (0, 0))  # old enough that FULL would delete it
    res = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res.type_of_vacuum == "LITE"
    assert res.num_deleted == 1
    assert not os.path.exists(
        os.path.join(tmp_table_path, res.files_deleted[0]))
    assert os.path.exists(junk)  # untracked: invisible to LITE
    assert res.eligible_start_commit_version == 0
    assert res.eligible_end_commit_version == table.latest_snapshot().version
    # watermark persisted for the next incremental run
    info = os.path.join(tmp_table_path, "_delta_log", "_last_vacuum_info")
    assert os.path.exists(info)
    import json as _json

    mark = _json.load(open(info))
    assert mark["latestCommitVersionOutsideOfRetentionWindow"] == \
        res.eligible_end_commit_version
    # FULL still reaps the junk afterwards, and (having observed every
    # file) keeps the watermark current rather than resetting it
    res_full = vacuum(table, retention_hours=0)
    assert "untracked-junk.parquet" in res_full.files_deleted
    assert _json.load(open(info))[
        "latestCommitVersionOutsideOfRetentionWindow"] == \
        res.eligible_end_commit_version


def test_vacuum_lite_incremental_watermark(tmp_table_path):
    """A second LITE run resumes after the first one's watermark
    (`VacuumCommand.scala:540-544`) and still finds new tombstones."""
    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))
    res1 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res1.num_deleted == 1
    delete(table, col("id") >= lit(100))
    res2 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res2.eligible_start_commit_version == \
        res1.eligible_end_commit_version + 1
    assert res2.num_deleted == 1
    # every data file is gone; the log still replays
    assert dta.read_table(tmp_table_path).num_rows == 0


def test_vacuum_lite_protects_recent_tombstones(tmp_table_path):
    table = _mk_table(tmp_table_path, n=50, n_commits=2)
    delete(table, col("id") < lit(50))
    res = vacuum(table, retention_hours=1000, vacuum_type="LITE")
    assert res.num_deleted == 0


def test_vacuum_lite_raises_after_unobserved_log_cleanup(tmp_table_path):
    """Commits expired before any vacuum observed them: their
    tombstones are unrecoverable from the log, so LITE must refuse
    (`VacuumCommand.scala:532-537` -> DELTA_CANNOT_VACUUM_LITE)."""
    from delta_tpu.errors import VacuumLiteError

    table = _mk_table(tmp_table_path, n=50, n_commits=3)
    table.checkpoint()
    # simulate metadata cleanup having expired the earliest commits
    for v in (0, 1):
        os.unlink(os.path.join(
            tmp_table_path, "_delta_log", f"{v:020d}.json"))
    table = Table.for_path(tmp_table_path)
    with pytest.raises(VacuumLiteError) as ei:
        vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert ei.value.error_class == "DELTA_CANNOT_VACUUM_LITE"


def test_vacuum_lite_sql_surface(tmp_table_path):
    from delta_tpu.sql import sql

    table = _mk_table(tmp_table_path, n=60, n_commits=2)
    delete(table, col("id") < lit(60))
    res = sql(f"VACUUM '{tmp_table_path}' RETAIN 0 HOURS LITE DRY RUN")
    assert res.type_of_vacuum == "LITE" and res.dry_run
    assert res.num_deleted == 1
    assert os.path.exists(
        os.path.join(tmp_table_path, res.files_deleted[0]))


def test_vacuum_lite_rejects_inventory(tmp_table_path):
    from delta_tpu.errors import InvalidArgumentError

    table = _mk_table(tmp_table_path, n=10, n_commits=1)
    inv = pa.table({"path": ["x"], "length": [1], "isDir": [False],
                    "modificationTime": [0]})
    with pytest.raises(InvalidArgumentError):
        vacuum(table, retention_hours=0, inventory=inv,
               vacuum_type="LITE")


def test_vacuum_lite_empty_run_keeps_watermark(tmp_table_path):
    """An empty LITE run (nothing outside retention) must not reset or
    regress the watermark — that would rescan or spuriously trip the
    gap check after log cleanup."""
    import json as _json

    table = _mk_table(tmp_table_path, n=50, n_commits=2)
    delete(table, col("id") < lit(50))
    res1 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    info = os.path.join(tmp_table_path, "_delta_log", "_last_vacuum_info")
    mark1 = _json.load(open(info))
    assert mark1["latestCommitVersionOutsideOfRetentionWindow"] == \
        res1.eligible_end_commit_version
    # big retention: cutoff predates every commit -> empty run
    res2 = vacuum(table, retention_hours=100000, vacuum_type="LITE")
    assert res2.num_deleted == 0
    assert _json.load(open(info)) == mark1  # unchanged


def test_vacuum_lite_contiguous_watermark_after_cleanup(tmp_table_path):
    """last_mark+1 == earliest is NOT a gap: every expired commit was
    scanned, so the next LITE run proceeds."""
    import json as _json

    table = _mk_table(tmp_table_path, n=50, n_commits=3)
    delete(table, col("id") < lit(50))  # version 3
    res1 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    end1 = res1.eligible_end_commit_version
    table.checkpoint()
    # cleanup expires exactly the scanned prefix [0, end1]
    for v in range(0, end1 + 1):
        os.unlink(os.path.join(
            tmp_table_path, "_delta_log", f"{v:020d}.json"))
    table = Table.for_path(tmp_table_path)
    delete(table, col("id") >= lit(100))
    res2 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res2.eligible_start_commit_version == end1 + 1
    assert res2.num_deleted >= 1


def test_vacuum_lite_rejects_traversal_paths(tmp_table_path, tmp_path):
    """A logged remove path with '..' or an encoded absolute path must
    not unlink outside the table root (same guard as the inventory
    path)."""
    import json as _json

    victim = tmp_path / "victim.bin"
    victim.write_bytes(b"precious")
    table = _mk_table(tmp_table_path, n=10, n_commits=1)
    # hand-craft a commit with hostile remove paths
    rel_victim = os.path.relpath(str(victim), tmp_table_path)
    log = os.path.join(tmp_table_path, "_delta_log")
    evil = [
        {"remove": {"path": rel_victim.replace(os.sep, "/"),
                    "deletionTimestamp": 1, "dataChange": True}},
        {"remove": {"path": "%2Fetc%2Fhostname",
                    "deletionTimestamp": 1, "dataChange": True}},
    ]
    with open(os.path.join(log, f"{1:020d}.json"), "w") as f:
        f.write("\n".join(_json.dumps(a) for a in evil))
    table = Table.for_path(tmp_table_path)
    res = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert victim.exists()
    assert all("victim" not in p and "etc" not in p
               for p in res.files_deleted)


def test_vacuum_lite_repeat_is_empty(tmp_table_path):
    """Running LITE twice with no new commits must not re-report (or
    re-'delete') the files the first run already removed."""
    table = _mk_table(tmp_table_path, n=50, n_commits=2)
    delete(table, col("id") < lit(50))
    res1 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res1.num_deleted == 1
    res2 = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res2.num_deleted == 0
    res_dry = vacuum(table, retention_hours=0, vacuum_type="LITE",
                     dry_run=True)
    assert res_dry.num_deleted == 0


def test_vacuum_full_enables_lite_on_cleaned_log(tmp_table_path):
    """A FULL vacuum observes every file, so on a table whose log head
    was cleaned up it advances the watermark and un-wedges LITE."""
    table = _mk_table(tmp_table_path, n=50, n_commits=3)
    table.checkpoint()
    for v in (0, 1):
        os.unlink(os.path.join(
            tmp_table_path, "_delta_log", f"{v:020d}.json"))
    table = Table.for_path(tmp_table_path)
    from delta_tpu.errors import VacuumLiteError

    with pytest.raises(VacuumLiteError):
        vacuum(table, retention_hours=0, vacuum_type="LITE")
    vacuum(table, retention_hours=0)  # FULL
    delete(table, col("id") < lit(50))
    res = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert res.num_deleted == 1


def test_vacuum_full_holds_watermark_over_a_skewed_survivor(tmp_table_path):
    """FULL deletes by the file's mtime, LITE by the tombstone's
    timestamp. A tombstoned file whose mtime lies ahead survives FULL;
    the watermark then stays where it was, so that a later LITE still
    reads the commit that removed the file."""
    table = _mk_table(tmp_table_path, n=100, n_commits=2)
    delete(table, col("id") < lit(100))  # tombstones the first file
    live = set(table.latest_snapshot().state.add_files_table
               .column("path").to_pylist())
    (gone,) = [f for f in os.listdir(tmp_table_path)
               if f.endswith(".parquet") and f not in live]
    gone = os.path.join(tmp_table_path, gone)
    ahead = time.time() + 3600
    os.utime(gone, (ahead, ahead))
    res = vacuum(table, retention_hours=0)  # FULL
    assert res.num_deleted == 0 and os.path.exists(gone)
    info = os.path.join(tmp_table_path, "_delta_log", "_last_vacuum_info")
    assert not os.path.exists(info)
    os.utime(gone, (0, 0))
    lite = vacuum(table, retention_hours=0, vacuum_type="LITE")
    assert lite.num_deleted == 1 and not os.path.exists(gone)
    assert lite.eligible_start_commit_version == 0


def test_vacuum_sql_modifier_order(tmp_table_path):
    """Reference grammar (`DeltaSqlBase.g4:198`) accepts modifiers in
    any order: LITE before RETAIN must parse too."""
    from delta_tpu.sql import sql

    table = _mk_table(tmp_table_path, n=60, n_commits=2)
    delete(table, col("id") < lit(60))
    res = sql(f"VACUUM '{tmp_table_path}' LITE RETAIN 0 HOURS DRY RUN")
    assert res.type_of_vacuum == "LITE" and res.dry_run
    assert res.num_deleted == 1
    res2 = sql(f"VACUUM '{tmp_table_path}' DRY RUN")
    assert res2.type_of_vacuum == "FULL" and res2.dry_run
