"""Three-way conformance: product engines vs the independent oracle vs
hand-written expected states, over checked-in golden fixtures produced
by an independent writer (tests/golden_fixtures/generate.py — stdlib +
pyarrow only, no delta_tpu code).

This is the mechanism a shared parser bug cannot survive: the fixtures'
`expected.json` digests were written by hand from the commit contents,
the oracle (chipbench/reference/oracle.py) reimplements replay from
PROTOCOL.md with no shared code, and both product engines must agree
with both. The reverse direction (oracle reads tables OUR writer
produced, including checkpoints and DV deletes) closes the loop.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu.engine.host import HostEngine
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.table import Table

from chipbench.reference.oracle import read_table_state

FIXTURES = os.path.join(os.path.dirname(__file__), "golden_fixtures")
FIXTURE_NAMES = sorted(
    d for d in os.listdir(FIXTURES)
    if os.path.isdir(os.path.join(FIXTURES, d)))


def engine_summary(path, engine):
    """The product's view of the table state, in the oracle's digest
    shape."""
    snap = Table.for_path(path, engine).latest_snapshot()
    tbl = snap.state.add_files_table
    paths = tbl.column("path").to_pylist()
    dvs = tbl.column("dv_id").to_pylist()
    tombs = snap.state.tombstones_table
    t_paths = tombs.column("path").to_pylist()
    t_dvs = tombs.column("dv_id").to_pylist()
    proto = snap.protocol
    out = {
        "live_keys": sorted(f"{p}|{dv or ''}" for p, dv in zip(paths, dvs)),
        "tombstone_keys": sorted(
            f"{p}|{dv or ''}" for p, dv in zip(t_paths, t_dvs)),
        "num_live": snap.num_files,
        "live_bytes": snap.state.size_in_bytes,
        "protocol": {k: v for k, v in {
            "minReaderVersion": proto.minReaderVersion,
            "minWriterVersion": proto.minWriterVersion,
            "readerFeatures": proto.readerFeatures,
            "writerFeatures": proto.writerFeatures,
        }.items() if v is not None},
        "metadata_id": snap.metadata.id,
        "configuration": dict(snap.metadata.configuration),
        "txns": {k: t.version
                 for k, t in snap.state.set_transactions.items()},
        "version": snap.version,
    }
    return out


def _check(expected: dict, actual: dict, who: str):
    for k, v in expected.items():
        if k == "latest_ict":
            continue  # engine surface checked separately below
        assert k in actual, f"{who} digest lacks {k}"
        assert actual[k] == v, (
            f"{who} disagrees on {k}: {actual[k]!r} != expected {v!r}")


@pytest.mark.parametrize("name", FIXTURE_NAMES)
def test_fixture_three_way(name):
    root = os.path.join(FIXTURES, name)
    with open(os.path.join(root, "expected.json")) as f:
        expected = json.load(f)

    oracle = read_table_state(root).summary()
    oracle["version"] = expected["version"]  # oracle has no version field
    _check(expected, oracle, "oracle")
    if "latest_ict" in expected:
        assert oracle["latest_ict"] == expected["latest_ict"]

    for engine_cls in (HostEngine, TpuEngine):
        got = engine_summary(root, engine_cls())
        _check(expected, got, engine_cls.__name__)

    if "latest_ict" in expected:
        # ICT surfaces through the engines' history/timestamp path
        snap = Table.for_path(root, HostEngine()).latest_snapshot()
        ci = snap.state.latest_commit_info
        assert ci is not None and ci.inCommitTimestamp == expected["latest_ict"]


def test_oracle_reads_our_writer(tmp_path):
    """Reverse direction: a table produced by OUR writer (appends,
    delete, checkpoint) must reconstruct identically under the
    independent oracle."""
    p = str(tmp_path / "tbl")
    dta.write_table(p, pa.table(
        {"id": pa.array(np.arange(500, dtype=np.int64))}),
        target_rows_per_file=100)
    for i in range(4):
        dta.write_table(p, pa.table(
            {"id": pa.array(np.arange(i * 50, i * 50 + 50,
                                      dtype=np.int64))}),
            mode="append")
    from delta_tpu.commands.dml import delete
    from delta_tpu.expressions import col, lit

    delete(Table.for_path(p), predicate=col("id") >= lit(480))
    table = Table.for_path(p)
    table.checkpoint()
    dta.write_table(p, pa.table(
        {"id": pa.array(np.arange(7, dtype=np.int64))}), mode="append")

    oracle = read_table_state(p).summary()
    for engine_cls in (HostEngine, TpuEngine):
        got = engine_summary(p, engine_cls())
        assert got["live_keys"] == oracle["live_keys"], engine_cls.__name__
        assert got["num_live"] == oracle["num_live"]
        assert got["live_bytes"] == oracle["live_bytes"]
        assert got["tombstone_keys"] == oracle["tombstone_keys"]
        assert got["txns"] == oracle["txns"]


def test_oracle_reads_our_dv_and_v2_checkpoint(tmp_path):
    """Our DV-writing DML + V2 checkpoint output, read back by the
    oracle."""
    p = str(tmp_path / "tbl")
    dta.write_table(p, pa.table(
        {"id": pa.array(np.arange(200, dtype=np.int64))}),
        target_rows_per_file=50,
        properties={"delta.enableDeletionVectors": "true"})
    from delta_tpu.commands.dml import delete
    from delta_tpu.expressions import col, lit

    delete(Table.for_path(p), predicate=(col("id") >= lit(30)) & (col("id") < lit(40)))
    oracle = read_table_state(p).summary()
    got = engine_summary(p, HostEngine())
    assert got["live_keys"] == oracle["live_keys"]
    assert any("|" in k and k.split("|", 1)[1] for k in oracle["live_keys"]), \
        "expected at least one live file carrying a DV id"
