"""Cross-version compatibility matrix (the reference's
`connectors/oss-compatibility-tests/` role, adapted to a single
implementation): tables written under every protocol generation the
spec defines — legacy (1,2), intermediate legacy features, and
feature-vector (3,7) with feature combinations — must read, append,
upgrade, and checkpoint consistently, and the written logs must stay
within what the DECLARED protocol permits (a v2 table's log must be
readable by a reader that knows nothing of table features).

Each case also round-trips through the independent oracle parser
(chipbench/reference/oracle.py) so conformance is not self-certified."""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu.models.actions import actions_from_commit_bytes
from delta_tpu.table import Table
from chipbench.reference.oracle import read_table_state


def _batch(start=0, n=10):
    return pa.table({"id": pa.array(np.arange(start, start + n,
                                              dtype=np.int64))})


# protocol generations: (properties, expected (reader, writer) floor)
MATRIX = [
    ("legacy_v2", {}, (1, 2)),
    ("legacy_checks", {"delta.constraints.c1": "id >= 0"}, (1, 3)),
    ("legacy_cdf", {"delta.enableChangeDataFeed": "true"}, (1, 4)),
    ("column_mapping", {"delta.columnMapping.mode": "name"}, (2, 5)),
    ("feature_dv", {"delta.enableDeletionVectors": "true"}, (3, 7)),
    ("feature_ict", {"delta.enableInCommitTimestamps": "true"}, (1, 7)),
    ("feature_rowtracking", {"delta.enableRowTracking": "true"}, (1, 7)),
    ("feature_multi", {"delta.enableDeletionVectors": "true",
                       "delta.enableInCommitTimestamps": "true",
                       "delta.appendOnly": "true"}, (3, 7)),
]


@pytest.mark.parametrize("name,props,floor",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_protocol_generation_round_trip(tmp_path, name, props, floor):
    path = str(tmp_path / name)
    dta.write_table(path, _batch(0), properties=props)
    dta.write_table(path, _batch(10), mode="append")

    t = Table.for_path(path)
    snap = t.latest_snapshot()
    proto = snap.protocol
    assert (proto.minReaderVersion, proto.minWriterVersion) == floor, \
        (proto.minReaderVersion, proto.minWriterVersion)

    # read back the full data
    out = dta.read_table(path)
    assert out.num_rows == 20

    # the independent oracle parser agrees on the live-file set
    oracle = read_table_state(path)
    ours = set(snap.state.add_files_table.column("path").to_pylist())
    assert {p for p, _dv in oracle.live} == ours

    # checkpoint + reload stays identical
    t.checkpoint()
    dta.write_table(path, _batch(20), mode="append")
    snap2 = Table.for_path(path).latest_snapshot()
    assert snap2.num_files == snap.num_files + 1


@pytest.mark.parametrize("name,props,floor",
                         MATRIX, ids=[m[0] for m in MATRIX])
def test_log_respects_declared_protocol(tmp_path, name, props, floor):
    """A log must not smuggle in actions the DECLARED protocol does not
    permit: feature-vector-only fields (reader/writerFeatures) only at
    (3,7); rowtracking/DV metadata only when their features are on —
    this is what keeps an old reader able to consume a v2 table."""
    path = str(tmp_path / name)
    dta.write_table(path, _batch(0), properties=props)
    log = os.path.join(path, "_delta_log")
    reader_v = writer_v = None
    features = set()
    for f in sorted(os.listdir(log)):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(log, f), "rb") as fh:
            for a in actions_from_commit_bytes(fh.read()):
                kind = type(a).__name__
                if kind == "Protocol":
                    reader_v = a.minReaderVersion
                    writer_v = a.minWriterVersion
                    features |= set(a.reader_feature_set())
                    features |= set(a.writer_feature_set())
    assert (reader_v, writer_v) == floor
    if writer_v < 7:
        assert not features, (
            f"feature vectors on a pre-(x,7) protocol: {features}")
    if writer_v >= 7:
        # every active feature implied by the properties is declared
        for key, feat in (("delta.enableDeletionVectors",
                           "deletionVectors"),
                          ("delta.enableInCommitTimestamps",
                           "inCommitTimestamp"),
                          ("delta.enableRowTracking", "rowTracking")):
            if props.get(key) == "true":
                assert feat in features, (feat, features)


def test_upgrade_path_v2_to_features(tmp_path):
    """The forward-compat story: a legacy (1,2) table upgrades through
    legacy writer versions to the feature-vector protocol without
    rewriting data, stays readable at every step, and folds the
    implied legacy features into the vector on the final hop."""
    from delta_tpu.commands.alter import upgrade_protocol

    path = str(tmp_path / "up")
    dta.write_table(path, _batch(0))
    t = Table.for_path(path)
    assert t.latest_snapshot().protocol.minWriterVersion == 2

    upgrade_protocol(t, min_reader=1, min_writer=4)
    assert dta.read_table(path).num_rows == 10

    upgrade_protocol(t, feature="deletionVectors")
    snap = t.latest_snapshot()
    proto = snap.protocol
    assert proto.minReaderVersion == 3 and proto.minWriterVersion == 7
    assert "deletionVectors" in proto.reader_feature_set()
    # legacy capabilities survive as implied/explicit features: the
    # table still accepts appends + reads after the hop
    dta.write_table(path, _batch(10), mode="append")
    assert dta.read_table(path).num_rows == 20

    # the oracle parser still replays the upgraded log
    oracle = read_table_state(path)
    ours = set(t.latest_snapshot().state.add_files_table
               .column("path").to_pylist())
    assert {p for p, _dv in oracle.live} == ours


def test_checkpoint_formats_cross_read(tmp_path):
    """Classic, multipart, and V2 checkpoints of the SAME state load
    identically (the cross-implementation checkpoint matrix)."""
    from delta_tpu.log.checkpointer import write_checkpoint

    base = str(tmp_path / "base")
    for i in range(4):
        dta.write_table(base, _batch(i * 10), mode="append" if i else "error")
    t = Table.for_path(base)
    snap = t.latest_snapshot()
    expected = sorted(snap.state.add_files_table.column("path")
                      .to_pylist())

    import shutil

    from delta_tpu.config import settings

    for policy, part_size in (("classic", None), ("multipart", 2),
                              ("v2", None)):
        p = str(tmp_path / f"cp_{policy}")
        shutil.copytree(base, p)
        tt = Table.for_path(p)
        saved = settings.checkpoint_part_size
        settings.checkpoint_part_size = part_size
        try:
            write_checkpoint(
                tt.engine, tt.latest_snapshot(),
                policy="classic" if policy == "multipart" else policy)
        finally:
            settings.checkpoint_part_size = saved
        if policy == "multipart":
            import glob

            parts = glob.glob(os.path.join(
                p, "_delta_log", "*.checkpoint.0*.parquet"))
            assert len(parts) > 1, "multipart did not split"
        got = sorted(Table.for_path(p).latest_snapshot()
                     .state.add_files_table.column("path").to_pylist())
        assert got == expected, policy
