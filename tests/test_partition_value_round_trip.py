"""A partition value is written from the Arrow column's own type and
read back by the same library: a nullable `integer`, `long` or `date`
partition column with nulls round-trips through `read_scan` and through
a partition predicate (a nullable column used to pass through pandas,
which made 2450816 into "2450816.0", and the reader refused that)."""

import datetime

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import Table
from delta_tpu.expressions import col, lit
from delta_tpu.write.writer import _partition_groups

DAY0 = datetime.date(1998, 1, 2)
N = 600

CASES = {
    "integer": (pa.int32(), lambda k: 2_450_816 + k),
    "long": (pa.int64(), lambda k: (1 << 40) + k),
    "date": (pa.date32(), lambda k: DAY0 + datetime.timedelta(days=k)),
}


def _table(kind: str, with_nulls: bool = True) -> pa.Table:
    arrow_type, value = CASES[kind]
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 5, N)
    part = [value(int(k)) for k in keys]
    if with_nulls:
        part = [None if i % 11 == 0 else v for i, v in enumerate(part)]
    return pa.table({"id": pa.array(np.arange(N, dtype=np.int64)),
                     "p": pa.array(part, arrow_type)})


def _rows(table: pa.Table):
    return sorted(zip(table.column("id").to_pylist(),
                      table.column("p").to_pylist()))


@pytest.mark.parametrize("kind", sorted(CASES))
def test_a_nullable_partition_column_round_trips(tmp_path, kind):
    data = _table(kind)
    path = str(tmp_path / kind)
    dta.write_table(path, data, partition_by=["p"])
    snap = Table.for_path(path).latest_snapshot()
    values = [dict(pv)["p"] for pv in snap.state.add_files_table.column(
        "partition_values").to_pylist()]
    assert len(values) == 6 and values.count(None) == 1
    assert not any(v is not None and v.endswith(".0") for v in values)
    assert _rows(dta.read_table(path)) == _rows(data)


@pytest.mark.parametrize("kind", sorted(CASES))
def test_a_partition_predicate_reads_what_was_written(tmp_path, kind):
    data = _table(kind)
    path = str(tmp_path / kind)
    dta.write_table(path, data, partition_by=["p"])
    wanted = CASES[kind][1](3)
    scan = Table.for_path(path).latest_snapshot().scan(
        filter=col("p") == lit(wanted))
    got = scan.to_arrow()
    assert scan.partition_pruned == 5       # four values and the null
    assert _rows(got) == [r for r in _rows(data) if r[1] == wanted]


def test_groups_keep_first_appearance_order_and_every_row():
    data = pa.table({
        "a": pa.array([2, None, 1, 2, None, 1, 3], pa.int32()),
        "b": pa.array(["x", "y", None, "x", "y", "z", None]),
        "v": pa.array(range(7), pa.int64())})
    groups = _partition_groups(data, ["a", "b"])
    assert [pv for pv, _ in groups] == [
        {"a": "2", "b": "x"}, {"a": None, "b": "y"}, {"a": "1", "b": None},
        {"a": "1", "b": "z"}, {"a": "3", "b": None}]
    assert [g.column("v").to_pylist() for _, g in groups] == [
        [0, 3], [1, 4], [2], [5], [6]]
