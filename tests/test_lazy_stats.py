"""Deferred stats decode: pure metadata loads never pay for stats; any
consumer touching the table gets the complete column transparently."""

import json

import numpy as np
import pyarrow as pa

import delta_tpu.api as dta
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.table import Table


def _mk(path, n=500, files=5):
    dta.write_table(path, pa.table(
        {"id": pa.array(np.arange(n, dtype=np.int64))}),
        target_rows_per_file=n // files)


def test_aggregates_do_not_materialize_stats(tmp_table_path):
    _mk(tmp_table_path)
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    state = snap.state
    # the load itself plus aggregates leave the decode pending...
    assert snap.num_files == 5
    assert state.size_in_bytes > 0
    if state.stats_thunk is None:
        import pytest
        pytest.skip("native lazy scan unavailable in this environment")
    # ...and the first table access splices the real column in
    tbl = state.add_files_table
    assert state.stats_thunk is None
    stats = [s for s in tbl.column("stats").to_pylist() if s]
    assert len(stats) == 5
    for s in stats:
        assert json.loads(s)["numRecords"] == 100


def test_skipping_works_after_lazy_load(tmp_table_path):
    from delta_tpu.expressions import col, lit

    _mk(tmp_table_path)
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    scan = snap.scan(filter=(col("id") >= lit(0)) & (col("id") < lit(100)))
    assert scan.add_files_table().num_rows == 1  # stats pruned 4/5 files
    assert scan.to_arrow().num_rows == 100


def test_checkpoint_written_after_lazy_load_roundtrips(tmp_table_path):
    _mk(tmp_table_path)
    table = Table.for_path(tmp_table_path, TpuEngine())
    table.checkpoint()
    # reload goes through the checkpoint (eager stats path) and the
    # stats strings must have survived the deferred decode
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    stats = [s for s in snap.state.add_files_table.column("stats").to_pylist()
             if s]
    assert len(stats) == 5
    assert all(json.loads(s)["numRecords"] == 100 for s in stats)


def test_oracle_agreement_after_lazy_load(tmp_table_path):
    from chipbench.reference.oracle import read_table_state

    _mk(tmp_table_path)
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    oracle = read_table_state(tmp_table_path).summary()
    mine = sorted(snap.state.add_files_table.column("path").to_pylist())
    assert mine == sorted(k.split("|")[0] for k in oracle["live_keys"])


def test_concurrent_table_access_is_safe(tmp_table_path):
    """Many threads hitting the deferred splice at once must not race
    the native materialization (ctypes drops the GIL)."""
    import threading

    _mk(tmp_table_path, n=2000, files=20)
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    results, errors = [], []

    def hit():
        try:
            t = snap.state.add_files_table
            results.append(sorted(s for s in t.column("stats").to_pylist()
                                  if s))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:1]
    assert all(r == results[0] for r in results)
    assert len(results[0]) == 20


def test_corrupt_stats_surfaces_typed_error(tmp_path):
    """A stats string whose escapes pass the structural scan but fail
    decode raises the catalogued CorruptStatsError at materialization."""
    import os

    import pytest

    from delta_tpu.errors import CorruptStatsError

    log = tmp_path / "tbl" / "_delta_log"
    os.makedirs(log)
    lines = [
        '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}',
        '{"metaData":{"id":"x","format":{"provider":"parquet","options":{}},'
        '"schemaString":"{\\"type\\":\\"struct\\",\\"fields\\":[]}",'
        '"partitionColumns":[],"configuration":{}}}',
        # \\q is structurally a pair but not a legal JSON escape
        '{"add":{"path":"a.parquet","partitionValues":{},"size":1,'
        '"modificationTime":1,"dataChange":true,"stats":"bad\\qescape"}}',
    ]
    with open(log / "00000000000000000000.json", "w") as f:
        f.write("\n".join(lines) + "\n")
    snap = Table.for_path(str(tmp_path / "tbl"), TpuEngine()).latest_snapshot()
    if snap.state.stats_thunk is None:
        pytest.skip("lazy native scan unavailable")
    assert snap.num_files == 1  # metadata unaffected
    with pytest.raises(CorruptStatsError):
        snap.state.add_files_table


def test_deferred_sizes_resolve_without_native(tmp_table_path):
    """The generic read path (native scanner disabled) resolves the fast
    listing's deferred sizes through fs.file_status."""
    import delta_tpu.native as nat

    _mk(tmp_table_path)
    old_lib, old_tried = nat._LIB, nat._TRIED
    nat._LIB, nat._TRIED = None, True
    try:
        snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
        assert snap.num_files == 5
        assert snap.state.size_in_bytes > 0
    finally:
        nat._LIB, nat._TRIED = old_lib, old_tried
