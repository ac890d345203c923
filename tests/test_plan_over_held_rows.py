"""A scan with a filter plans over the rows its state holds: the live
rows by number, the survivors read straight out of `file_actions`, the
live table never built for it (docs/incremental_update.md, "What a
state holds"). Held to the plan as it was made before, over
`state.add_files_table` with one boolean filter, row for row."""

import json

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import obs
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.expressions import col, lit
from delta_tpu.expressions.eval import evaluate_predicate_host
from delta_tpu.expressions.tree import split_conjuncts
from delta_tpu.models.actions import AddFile, RemoveFile
from delta_tpu.models.schema import INTEGER, STRING, StructField, StructType
from delta_tpu.replay import state as state_mod
from delta_tpu.replay.columnar import (CANONICAL_FILE_ACTION_SCHEMA,
                                       clear_parse_cache)
from delta_tpu.replay.state import gather_rows
from delta_tpu.stats.partition import partition_values_to_columns
from delta_tpu.stats.skipping import skipping_mask
from delta_tpu.table import Table

BUILDS = obs.counter("state.live_table_builds")
FROM_HELD = obs.counter("scan.plans_from_held_rows")


@pytest.fixture(autouse=True)
def _fresh_parse_cache():
    clear_parse_cache()
    yield
    clear_parse_cache()


def _add(name: str, x: int) -> AddFile:
    """A file of partition a or b whose column `x` holds x*10 .. x*10+9."""
    return AddFile(
        path=f"{name}.parquet", partitionValues={"p": "ab"[x % 2]},
        size=100 + x, modificationTime=1000 + x, dataChange=True,
        stats=json.dumps({"numRecords": 10, "minValues": {"x": x * 10},
                          "maxValues": {"x": x * 10 + 9},
                          "nullCount": {"x": 0}}))


def _make_table(path, engine, files=40) -> Table:
    """No checkpoint lands by itself: a reader this small would load
    each in full, and hold no state over more than nine advances."""
    t = Table.for_path(str(path), engine)
    t.create_transaction_builder().with_schema(StructType(
        [StructField("x", INTEGER), StructField("p", STRING)])
    ).with_partition_columns(["p"]).with_table_properties(
        {"delta.checkpointInterval": "100000"}).build().commit()
    txn = t.start_transaction()
    for i in range(files):
        txn.add_file(_add(f"b{i}", i))
    txn.commit()
    return t


def _land(path, i: int, base: int, checkpoint=False) -> None:
    """Another writer's commit `i`: one file more and base file `i`
    removed; every third commit brings the file removed before it back
    (last wins: the prior add row stays dead, a new row lands)."""
    w = Table.for_path(str(path), HostEngine())
    txn = w.start_transaction()
    txn.add_file(_add(f"p{i}", base + i))
    txn.remove_file(RemoveFile(path=f"b{i}.parquet", deletionTimestamp=5,
                               dataChange=True))
    if i % 3 == 2:
        txn.add_file(_add(f"b{i - 1}", i - 1))
    txn.commit()
    if checkpoint:
        w.checkpoint()


def _held(path, engine=HostEngine, files=40):
    t = _make_table(path, engine(), files)
    snap = t.update()
    snap.state          # replayed, so there is something to advance
    return t, snap


def _plan_over_the_live_table(snapshot, predicate):
    """The plan as it was made before this path: partition values and
    stats of the state's live table (the stats by the Arrow ladder, no
    index), then one boolean filter of that table. Returns the table,
    the rows the partition conjuncts pruned and those the stats did."""
    files = snapshot.state.add_files_table
    parts = set(snapshot.partition_columns)
    keep = np.ones(files.num_rows, dtype=bool)
    data = []
    for c in split_conjuncts(predicate):
        if all(r[0] in parts for r in c.references()):
            keep &= evaluate_predicate_host(c, partition_values_to_columns(
                files.column("partition_values"), snapshot.metadata))
        else:
            data.append(c)
    pruned = int((~keep).sum())
    skipped = 0
    if data:
        stats_keep = skipping_mask(files, data, snapshot.metadata)
        skipped = int((keep & ~stats_keep).sum())
        keep &= stats_keep
    return files.filter(pa.array(keep)), pruned, skipped


def _assert_same_table(got: pa.Table, want: pa.Table) -> None:
    assert got.schema.equals(want.schema, check_metadata=True)
    assert got.num_rows == want.num_rows
    for name in want.column_names:      # every column, in the same order
        assert got.column(name).to_pylist() == want.column(name).to_pylist()
    assert got.equals(want)


WINDOW = (col("x") >= lit(120)) & (col("x") < lit(460))


def _fresh(path):
    return _held(path)[1], WINDOW


def _advanced(n):
    def build(path):
        t, snap = _held(path)
        for i in range(n):
            _land(path, i, base=40)
            snap = t.update()
            if i % 4 == 0:      # an index to seed the next one from
                snap.scan(filter=WINDOW).add_files_table()
        assert snap.version == 1 + n
        return snap, WINDOW
    return build


def _across_a_checkpoint(path):
    t, snap = _held(path, files=1200)
    crossings = obs.counter("snapshot.checkpoint_crossings")
    before = crossings.value
    _land(path, 0, base=1200)
    _land(path, 1, base=1200, checkpoint=True)
    snap = t.update()
    assert crossings.value == before + 1
    return snap, (col("x") >= lit(11_000)) | (col("x") < lit(300))


def _partition_conjunct(path):
    snap, _ = _advanced(5)(path)
    return snap, (col("p") == lit("b")) & WINDOW


def _partition_conjunct_alone(path):
    snap, _ = _advanced(2)(path)
    return snap, col("p") == lit("a")


def _deletion_vectors(path):
    from delta_tpu.commands.dml import delete

    engine = HostEngine()
    dta.write_table(str(path), pa.table(
        {"x": pa.array(np.arange(600, dtype=np.int64))}),
        mode="error", engine=engine, target_rows_per_file=100,
        properties={"delta.enableDeletionVectors": "true"})
    t = Table.for_path(str(path), engine)
    t.update().state
    delete(Table.for_path(str(path), HostEngine()),
           predicate=(col("x") >= lit(150)) & (col("x") < lit(320)))
    snap = t.update()
    dv = snap.state.file_actions.column("dv_id")
    assert dv.null_count < len(dv)          # rows with a vector are held
    return snap, (col("x") >= lit(100)) & (col("x") < lit(400))


def _no_survivor(path):
    snap, _ = _advanced(3)(path)
    return snap, col("x") < lit(-5)


def _every_row_kept(path):
    snap, _ = _advanced(3)(path)
    return snap, col("x") >= lit(0)


def _stats_still_pending(path):
    t, snap = _held(path, TpuEngine)
    _land(path, 0, base=40)
    snap = t.update()
    if snap.state.stats_thunk is None:
        pytest.skip("native lazy scan unavailable in this environment")
    return snap, WINDOW


CASES = {
    "no-advance": _fresh,
    "one-advance": _advanced(1),
    "thirty-advances-with-removes-and-re-adds": _advanced(30),
    "across-a-checkpoint": _across_a_checkpoint,
    "partition-conjunct": _partition_conjunct,
    "partition-conjunct-alone": _partition_conjunct_alone,
    "deletion-vectors": _deletion_vectors,
    "no-survivor": _no_survivor,
    "every-row-kept": _every_row_kept,
    "stats-still-pending": _stats_still_pending,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_filtered_plan_equals_the_live_table_filtered(tmp_path, case):
    snapshot, predicate = CASES[case](tmp_path)
    state = snapshot.state
    built, served = BUILDS.value, FROM_HELD.value
    scan = snapshot.scan(filter=predicate)
    got = scan.add_files_table()
    assert state._add_table_cache is None and BUILDS.value == built
    assert FROM_HELD.value == served + 1
    want, pruned, skipped = _plan_over_the_live_table(snapshot, predicate)
    _assert_same_table(got, want)
    assert (scan.partition_pruned, scan.skipped_by_stats) == (pruned, skipped)
    assert state.stats_thunk is None        # spliced by then, either way
    assert (case == "no-survivor") == (got.num_rows == 0)
    assert (case == "every-row-kept") == (got.num_rows == state.num_files)
    assert ("partition" in case) == (scan.partition_pruned > 0)
    # the same again from a reader that has held nothing
    clear_parse_cache()
    cold = Table.for_path(snapshot.table_path, HostEngine()).latest_snapshot()
    assert sorted(got.column("path").to_pylist()) == sorted(
        cold.scan(filter=predicate).add_files_table()
        .column("path").to_pylist())


# ---- the gather ----

def _held_like_table(chunks: int, rng) -> pa.Table:
    """`chunks` chunks of the canonical schema (strings, a map, a
    struct, nulls), one empty chunk among them."""
    def chunk(n, at):
        ids = np.arange(at, at + n)
        cols = {
            "path": pa.array([f"f{i}.parquet" for i in ids]),
            "dv_id": pa.array([None if i % 7 else f"dv{i}" for i in ids],
                              pa.string()),
            "partition_values": pa.array(
                [[("p", str(i % 3))] for i in ids],
                CANONICAL_FILE_ACTION_SCHEMA.field("partition_values").type),
            "size": pa.array(ids * 3),
            "stats": pa.array([None if i % 5 == 0 else
                               json.dumps({"numRecords": int(i)})
                               for i in ids], pa.string()),
            "deletion_vector": pa.array(
                [None if i % 7 else {"storageType": "u",
                                     "pathOrInlineDv": f"dv{i}",
                                     "offset": 1, "sizeInBytes": 4,
                                     "cardinality": int(i),
                                     "maxRowIndex": None} for i in ids],
                CANONICAL_FILE_ACTION_SCHEMA.field("deletion_vector").type),
            "is_add": pa.array(ids % 4 != 0),
            "version": pa.array(ids // 10),
        }
        return pa.Table.from_arrays(
            [cols[f.name] if f.name in cols else pa.nulls(n, f.type)
             for f in CANONICAL_FILE_ACTION_SCHEMA],
            schema=CANONICAL_FILE_ACTION_SCHEMA)

    sizes = [int(s) for s in rng.integers(1, 9, size=chunks)]
    sizes.insert(chunks // 2, 0)
    parts, at = [], 0
    for n in sizes:
        parts.append(chunk(n, at))
        at += n
    return pa.concat_tables(parts)


ROWS = {
    "scattered": lambda n, rng: np.flatnonzero(rng.random(n) < 0.3),
    "runs": lambda n, rng: np.flatnonzero(
        np.repeat(rng.random(n // 4 + 1) < 0.4, 4)[:n]),
    "none": lambda n, rng: np.zeros(0, np.int64),
    "all": lambda n, rng: np.arange(n),
    "first-and-last": lambda n, rng: np.unique([0, n - 1]),
}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("chunks", [1, 2, 1000])
def test_the_gather_equals_a_take_of_the_combined_table(chunks, rows):
    rng = np.random.default_rng(chunks + len(rows))
    table = _held_like_table(chunks, rng)
    assert table.column("path").num_chunks == chunks + 1
    picked = ROWS[rows](table.num_rows, rng)
    _assert_same_table(gather_rows(table, picked),
                       table.combine_chunks().take(pa.array(picked, pa.int64())))


# ---- what a refresh builds, and what the state keeps ----

def test_a_refresh_and_a_filtered_plan_build_no_live_table(tmp_path):
    t, snap = _held(tmp_path)
    snap.scan(filter=WINDOW).add_files_table()
    built, served = BUILDS.value, FROM_HELD.value
    for i in range(3):
        _land(tmp_path, i, base=40)
        snap = t.update()
        plan = snap.scan(filter=WINDOW).add_files_table()
        assert snap.state._add_table_cache is None
    assert (BUILDS.value, FROM_HELD.value) == (built, served + 3)
    assert snap.state.stats_index is not None       # made from the seed
    # whoever wants the whole live table still gets it, once a state
    live = snap.state.add_files_table
    assert BUILDS.value == built + 1 and snap.state.add_files_table is live
    clear_parse_cache()
    cold = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    key = [("path", "ascending")]
    _assert_same_table(
        live.drop_columns(["version", "order"]).sort_by(key),
        cold.state.add_files_table.drop_columns(
            ["version", "order"]).sort_by(key))
    assert live.column("path").to_pylist() == [
        p for p, alive in zip(
            snap.state.file_actions.column("path").to_pylist(),
            snap.state.live_mask) if alive]
    # and a scan with no filter is that table
    assert snap.scan().add_files_table() is live
    _assert_same_table(plan, _plan_over_the_live_table(snap, WINDOW)[0])


def _chunks(table: pa.Table) -> int:
    return max(c.num_chunks for c in table.columns)


def test_a_held_table_keeps_tens_of_chunks_over_200_advances(tmp_path):
    t, snap = _held(tmp_path)
    base_chunks = _chunks(snap.state.file_actions)
    unmerged = [snap.state.file_actions]
    most = 0
    for i in range(200):
        _land(tmp_path, i, base=40)
        before = snap.state.file_actions.num_rows
        snap = t.update()
        unmerged.append(snap.state.file_actions.slice(before))
        most = max(most, _chunks(snap.state.file_actions))
        if i % 50 == 0:
            snap.scan(filter=WINDOW).add_files_table()
    assert state_mod._MAX_SMALL_CHUNKS < 200    # so a merge has happened
    assert most <= base_chunks + state_mod._MAX_SMALL_CHUNKS
    held = snap.state.file_actions
    assert _chunks(held) < most                 # and the last one stuck
    # the rows are those that landed, in the order they landed
    _assert_same_table(held, pa.concat_tables(unmerged))
    first = unmerged[0]
    assert held.column("path").chunk(0).buffers()[2].address \
        == first.column("path").chunk(0).buffers()[2].address   # not copied
    clear_parse_cache()
    cold = Table.for_path(str(tmp_path), HostEngine()).latest_snapshot()
    assert sorted(snap.scan(filter=WINDOW).file_paths()) \
        == sorted(cold.scan(filter=WINDOW).file_paths())
    assert snap.state.num_files == cold.state.num_files
