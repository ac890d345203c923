"""Row tracking, identity columns, generated columns, schema merge on write."""

import json

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu.colgen import generated_field, identity_field
from delta_tpu.errors import DeltaError, InvariantViolationError
from delta_tpu.models.schema import BOOLEAN, DOUBLE, LONG, STRING, StructField, StructType
from delta_tpu.rowtracking import ROW_TRACKING_DOMAIN, current_high_watermark
from delta_tpu.table import Table


def _data(n=100, start=0):
    return pa.table(
        {
            "id": pa.array(np.arange(start, start + n, dtype=np.int64)),
            "v": pa.array(np.full(n, 1.0)),
        }
    )


# -- row tracking -----------------------------------------------------------


def test_row_tracking_assignment(tmp_table_path):
    dta.write_table(
        tmp_table_path, _data(100),
        properties={"delta.enableRowTracking": "true"},
        target_rows_per_file=40,
    )
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    assert "rowTracking" in snap.protocol.writer_feature_set()
    files = sorted(snap.state.add_files(), key=lambda f: f.baseRowId)
    assert [f.baseRowId for f in files] == [0, 40, 80]
    assert all(f.defaultRowCommitVersion == 0 for f in files)
    assert current_high_watermark(snap) == 99
    # append advances the watermark
    dta.write_table(tmp_table_path, _data(10, 100))
    snap2 = Table.for_path(tmp_table_path).latest_snapshot()
    assert current_high_watermark(snap2) == 109
    new_file = [f for f in snap2.state.add_files() if f.defaultRowCommitVersion == 1]
    assert new_file[0].baseRowId == 100


def test_row_tracking_concurrent_writers(tmp_table_path):
    from delta_tpu.concurrency import PhaseLockingObserver, run_txn_async
    from delta_tpu.write.writer import write_data_files

    dta.write_table(
        tmp_table_path, _data(50),
        properties={"delta.enableRowTracking": "true"},
    )
    table = Table.for_path(tmp_table_path)

    def writer(tbl, n, start):
        txn = tbl.start_transaction()
        meta = txn.metadata()
        adds = write_data_files(
            engine=tbl.engine, table_path=tbl.path, data=_data(n, start),
            schema=meta.schema, partition_columns=[],
            configuration=meta.configuration,
        )
        txn.add_files(adds)
        return txn

    txn_a = writer(table, 20, 1000)
    obs = PhaseLockingObserver(block_before_commit=True)
    txn_a.observer = obs
    thread = run_txn_async(txn_a.commit)
    obs.before_commit_barrier.wait_for_arrival()

    txn_b = writer(Table.for_path(tmp_table_path), 30, 2000)
    txn_b.commit()

    obs.before_commit_barrier.unblock()
    thread.join_result()
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    # watermark covers all three writes; id ranges must not overlap
    assert current_high_watermark(snap) == 99
    ranges = sorted(
        (f.baseRowId, f.baseRowId + (f.num_records() or 0) - 1)
        for f in snap.state.add_files()
    )
    for (s1, e1), (s2, e2) in zip(ranges, ranges[1:]):
        assert e1 < s2


# -- identity columns -------------------------------------------------------


def test_identity_column_allocation(tmp_table_path):
    schema = StructType(
        [
            identity_field("pk", start=10, step=5),
            StructField("name", STRING),
        ]
    )
    data = pa.table({"name": pa.array(["a", "b", "c"])})
    dta.write_table(tmp_table_path, data, schema=schema)
    out = dta.read_table(tmp_table_path).sort_by("pk")
    assert out.column("pk").to_pylist() == [10, 15, 20]
    # next write continues from the watermark
    dta.write_table(tmp_table_path, pa.table({"name": pa.array(["d"])}))
    out = dta.read_table(tmp_table_path).sort_by("pk")
    assert out.column("pk").to_pylist() == [10, 15, 20, 25]


def test_identity_rejects_explicit(tmp_table_path):
    schema = StructType([identity_field("pk"), StructField("name", STRING)])
    data = pa.table({"name": pa.array(["a"])})
    dta.write_table(tmp_table_path, data, schema=schema)
    explicit = pa.table(
        {"pk": pa.array([99], pa.int64()), "name": pa.array(["x"])}
    )
    with pytest.raises(DeltaError):
        dta.write_table(tmp_table_path, explicit)


def test_merge_update_all_rejects_identity_column(tmp_table_path):
    """`UPDATE SET *` assigns every same-named source column, so it is
    refused like an explicit assignment when the source carries the
    identity column."""
    from delta_tpu.commands.merge import merge
    from delta_tpu.expressions import col

    schema = StructType([identity_field("pk"), StructField("name", STRING),
                         StructField("n", LONG)])
    dta.write_table(tmp_table_path, pa.table({
        "name": pa.array(["a", "b"]), "n": pa.array([1, 2], pa.int64())}),
        schema=schema)
    table = Table.for_path(tmp_table_path)
    on = col("target.name") == col("source.name")
    with_pk = pa.table({"pk": pa.array([7], pa.int64()),
                        "name": pa.array(["a"]),
                        "n": pa.array([10], pa.int64())})
    with pytest.raises(DeltaError) as e:
        merge(table, with_pk, on=on).when_matched_update_all().execute()
    assert e.value.error_class == \
        "DELTA_IDENTITY_COLUMNS_UPDATE_NOT_SUPPORTED"
    out = dta.read_table(tmp_table_path).sort_by("pk")
    assert out.column("pk").to_pylist() == [1, 2]
    assert out.column("n").to_pylist() == [1, 2]


# -- generated columns ------------------------------------------------------


def test_generated_column_computed_and_validated(tmp_table_path):
    schema = StructType(
        [
            StructField("id", LONG),
            generated_field("is_small", BOOLEAN, "id < 10"),
        ]
    )
    data = pa.table({"id": pa.array([1, 5, 20], pa.int64())})
    dta.write_table(tmp_table_path, data, schema=schema)
    out = dta.read_table(tmp_table_path).sort_by("id")
    assert out.column("is_small").to_pylist() == [True, True, False]
    # explicit-but-wrong values rejected
    bad = pa.table(
        {
            "id": pa.array([100], pa.int64()),
            "is_small": pa.array([True]),
        }
    )
    with pytest.raises(InvariantViolationError):
        dta.write_table(tmp_table_path, bad)


# -- merge schema -----------------------------------------------------------


def test_merge_schema_on_write(tmp_table_path):
    dta.write_table(tmp_table_path, _data(5))
    newdata = _data(5, 100).append_column("extra", pa.array(["e"] * 5))
    from delta_tpu.errors import SchemaMismatchError

    with pytest.raises(SchemaMismatchError):
        dta.write_table(tmp_table_path, newdata)
    dta.write_table(tmp_table_path, newdata, merge_schema=True)
    snap = Table.for_path(tmp_table_path).latest_snapshot()
    assert "extra" in snap.schema
    out = dta.read_table(tmp_table_path)
    assert out.num_rows == 10
    extras = out.column("extra").to_pylist()
    assert extras.count(None) == 5 and extras.count("e") == 5
