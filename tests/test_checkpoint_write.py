"""Checkpoint WRITE path (`log/checkpointer.py`, `write/ckpt_pipeline.py`,
`ops/stats.py`): write→read digest parity across checkpoint policy ×
stats mode × full/incremental, part-reuse correctness (only the changed
tail is rewritten), torn-multipart abort + orphan cleanup, the pipeline
profitability gate both ways, stats-kernel host/device parity, and DV
device-packing byte equality."""

import json
import os

import numpy as np
import pytest

from delta_tpu import obs
from delta_tpu.config import settings
from delta_tpu.engine.host import HostEngine
from delta_tpu.log.checkpointer import write_checkpoint
from delta_tpu.log.last_checkpoint import read_last_checkpoint
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.resilience.chaos import ChaosError, ChaosSchedule, ChaosStore
from delta_tpu.storage import InMemoryLogStore
from delta_tpu.table import Table
from delta_tpu.write import ckpt_pipeline

PROTOCOL = {"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}}
METADATA = {
    "metaData": {
        "id": "ckpt-write-test-table",
        "format": {"provider": "parquet", "options": {}},
        "schemaString": json.dumps(
            {"type": "struct",
             "fields": [{"name": "x", "type": "long", "nullable": True,
                         "metadata": {}}]}),
        "partitionColumns": [],
        "configuration": {},
    }
}

PARTS_WRITTEN = obs.counter("checkpoint.parts_written")
PARTS_REUSED = obs.counter("checkpoint.parts_reused")
ABORTED = obs.counter("checkpoint.aborted_writes")


@pytest.fixture(autouse=True)
def _fresh():
    old_part_size = settings.checkpoint_part_size
    clear_parse_cache()
    yield
    settings.checkpoint_part_size = old_part_size
    clear_parse_cache()


def _add(path, size=100):
    return {"add": {"path": path, "partitionValues": {}, "size": size,
                    "modificationTime": 1000, "dataChange": True,
                    "stats": json.dumps({"numRecords": size // 10})}}


def _commit_actions(v, per=5):
    return [_add(f"part-{v:04d}-{i}.parquet", size=100 + v + i)
            for i in range(per)]


def _write_commit_local(log, v, actions):
    os.makedirs(log, exist_ok=True)
    with open(os.path.join(log, f"{v:020d}.json"), "w") as f:
        for a in actions:
            f.write(json.dumps(a) + "\n")


def _build_local_log(path, ncommits, per=5):
    log = os.path.join(str(path), "_delta_log")
    _write_commit_local(log, 0, [PROTOCOL, METADATA])
    for v in range(1, ncommits + 1):
        _write_commit_local(log, v, _commit_actions(v, per))
    return log


def _append_local(log, versions, per=5):
    for v in versions:
        _write_commit_local(log, v, _commit_actions(v, per))


def _digest(path, eng=None):
    """Everything a checkpoint must preserve: the live file set with
    stats, P&M, txns, and domains (per-row replay versions are
    deliberately excluded — a checkpoint collapses them)."""
    clear_parse_cache()
    snap = Table.for_path(str(path), eng or HostEngine()).latest_snapshot()
    st = snap.state
    at = st.add_files_table
    rows = sorted(zip(at.column("path").to_pylist(),
                      at.column("size").to_pylist(),
                      at.column("stats").to_pylist()))
    return (snap.version, st.num_files,
            (snap.protocol.minReaderVersion, snap.protocol.minWriterVersion),
            snap.metadata.id,
            sorted((k, t.version) for k, t in st.set_transactions.items()),
            sorted((k, d.configuration, d.removed)
                   for k, d in st.domain_metadata.items()),
            rows)


def _drop_commits(log, through_version):
    for v in range(through_version + 1):
        p = os.path.join(log, f"{v:020d}.json")
        if os.path.exists(p):
            os.remove(p)


# --------------------------------------------- write→read parity matrix


@pytest.mark.parametrize("policy,part_size", [
    ("classic", None),
    ("multipart", 12),
    ("v2", 12),
])
@pytest.mark.parametrize("device_stats", ["0", "1"])
@pytest.mark.parametrize("incremental", [False, True])
def test_digest_parity_matrix(tmp_path, monkeypatch, policy, part_size,
                              device_stats, incremental):
    """Reloading purely from the checkpoint reproduces the live state,
    for every policy × stats-mode × full/incremental combination."""
    monkeypatch.setenv("DELTA_TPU_DEVICE_CKPT_STATS", device_stats)
    log = _build_local_log(tmp_path, 10)
    settings.checkpoint_part_size = part_size
    eng = HostEngine()
    write_policy = "v2" if policy == "v2" else None

    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    write_checkpoint(eng, snap, policy=write_policy)
    version = 10
    if incremental:
        _append_local(log, [11, 12])
        snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
        prev = read_last_checkpoint(eng.fs, log)
        write_checkpoint(eng, snap, policy=write_policy, prev_info=prev)
        version = 12

    live = _digest(tmp_path, eng)
    _drop_commits(log, version)
    reloaded = _digest(tmp_path, eng)
    assert reloaded == live
    assert reloaded[0] == version and reloaded[1] == 5 * version


@pytest.mark.parametrize("policy,part_size", [
    ("classic", None),
    ("multipart", 120),
    ("v2", 120),
])
def test_digest_parity_on_a_dealt_encode(tmp_path, monkeypatch, policy,
                                         part_size):
    """The same reload, of files whose encode was dealt over the scan
    pool and stitched (`log/parquet_stitch.py`): several row groups a
    file, `add` cut into its leaves, every part over the line."""
    import pyarrow._parquet as _parquet

    from delta_tpu.log import parquet_stitch

    monkeypatch.setattr(_parquet, "_DEFAULT_ROW_GROUP_SIZE", 50)
    monkeypatch.setattr(parquet_stitch, "_DEAL_MIN_ROWS", 40)
    dealt = obs.counter("checkpoint.encodes_dealt")
    log = _build_local_log(tmp_path, 60)
    settings.checkpoint_part_size = part_size
    eng = HostEngine()
    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    before = dealt.value
    write_checkpoint(eng, snap, policy="v2" if policy == "v2" else None)
    # 300 adds: the one file, or three parts of 120, 120 and 60 rows
    assert dealt.value - before == (1 if policy == "classic" else 3)

    live = _digest(tmp_path, eng)
    _drop_commits(log, 60)
    reloaded = _digest(tmp_path, eng)
    assert reloaded == live
    assert reloaded[0] == 60 and reloaded[1] == 300


def test_host_device_checkpoints_byte_identical(tmp_path, monkeypatch):
    """Stat mode is telemetry only: flipping it may not change a single
    checkpoint byte (host and device aggregates are bit-identical and
    neither enters the fingerprints)."""
    log = _build_local_log(tmp_path, 6)
    settings.checkpoint_part_size = 12
    eng = HostEngine()

    def ckpt_bytes(mode):
        monkeypatch.setenv("DELTA_TPU_DEVICE_CKPT_STATS", mode)
        snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
        write_checkpoint(eng, snap)
        out = {}
        for f in sorted(os.listdir(log)):
            if ".checkpoint." in f:
                with open(os.path.join(log, f), "rb") as fh:
                    out[f] = fh.read()
                os.remove(os.path.join(log, f))
        os.remove(os.path.join(log, "_last_checkpoint"))
        return out

    host = ckpt_bytes("0")
    dev = ckpt_bytes("1")
    assert set(host) == set(dev)
    for name in host:
        assert host[name] == dev[name], name


# ------------------------------------------------------ incremental reuse


def test_multipart_reuse_only_tail_rewritten(tmp_path):
    """Append-only growth: full earlier chunks byte-copy from the
    previous checkpoint; only the small-actions part and the changed
    tail chunk are re-serialized."""
    log = _build_local_log(tmp_path, 10)  # 50 files
    settings.checkpoint_part_size = 12    # chunks: 12,12,12,12,2
    eng = HostEngine()

    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    w0, r0 = PARTS_WRITTEN.value, PARTS_REUSED.value
    info1 = write_checkpoint(eng, snap)
    assert PARTS_WRITTEN.value - w0 == 6  # small-actions + 5 file chunks
    assert PARTS_REUSED.value - r0 == 0
    assert info1.partManifest is not None
    assert len(info1.partManifest["parts"]) == 5

    _append_local(log, [11, 12])          # 60 files -> chunks: 12 x 5
    snap2 = Table.for_path(str(tmp_path), eng).latest_snapshot()
    prev = read_last_checkpoint(eng.fs, log)
    assert prev is not None and prev.partManifest is not None
    w1, r1 = PARTS_WRITTEN.value, PARTS_REUSED.value
    info2 = write_checkpoint(eng, snap2, prev_info=prev)
    # 4 full chunks unchanged (fixed boundaries), tail chunk grew
    assert PARTS_REUSED.value - r1 == 4
    assert PARTS_WRITTEN.value - w1 == 6  # byte-copies still materialize
    fp1 = {e["fp"] for e in info1.partManifest["parts"]}
    fp2 = {e["fp"] for e in info2.partManifest["parts"]}
    assert len(fp1 & fp2) == 4

    _drop_commits(log, 12)
    assert _digest(tmp_path, eng)[1] == 60


def test_v2_sidecar_reuse_rereferences_in_place(tmp_path):
    """V2 reuse writes nothing: fingerprint-matched sidecars are
    pointed at again, so consecutive checkpoints share sidecar files."""
    log = _build_local_log(tmp_path, 10)
    settings.checkpoint_part_size = 12
    eng = HostEngine()

    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    info1 = write_checkpoint(eng, snap, policy="v2")
    sidecar_dir = os.path.join(log, "_sidecars")
    first = set(os.listdir(sidecar_dir))
    assert len(first) == 5

    _append_local(log, [11, 12])
    snap2 = Table.for_path(str(tmp_path), eng).latest_snapshot()
    prev = read_last_checkpoint(eng.fs, log)
    w1, r1 = PARTS_WRITTEN.value, PARTS_REUSED.value
    info2 = write_checkpoint(eng, snap2, policy="v2", prev_info=prev)
    assert PARTS_REUSED.value - r1 == 4
    assert PARTS_WRITTEN.value - w1 == 1  # only the changed tail sidecar
    names2 = {e["name"] for e in info2.partManifest["parts"]}
    assert len(names2 & first) == 4       # re-referenced, not copied
    assert len(set(os.listdir(sidecar_dir))) == 6

    _drop_commits(log, 12)
    assert _digest(tmp_path, eng)[1] == 60


def test_config_change_invalidates_reuse(tmp_path):
    """A different part size produces a different writer fingerprint —
    the old manifest must be ignored, never misapplied."""
    log = _build_local_log(tmp_path, 10)
    settings.checkpoint_part_size = 12
    eng = HostEngine()
    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    write_checkpoint(eng, snap)

    settings.checkpoint_part_size = 10
    _append_local(log, [11])
    snap2 = Table.for_path(str(tmp_path), eng).latest_snapshot()
    prev = read_last_checkpoint(eng.fs, log)
    r0 = PARTS_REUSED.value
    write_checkpoint(eng, snap2, prev_info=prev)
    assert PARTS_REUSED.value == r0
    _drop_commits(log, 11)
    assert _digest(tmp_path, eng)[1] == 55


# ---------------------------------------------- torn writes / abort path


def _chaos_engine(seed, **rates):
    store = ChaosStore(InMemoryLogStore(), ChaosSchedule(seed, **rates),
                       sleep=lambda s: None)
    return HostEngine(store_resolver=lambda path: store), store


def _build_mem_log(store, table_path, ncommits, per=5):
    log = f"{table_path}/_delta_log"
    store.enabled = False
    data = "\n".join(json.dumps(a) for a in [PROTOCOL, METADATA]) + "\n"
    store.write(f"{log}/{0:020d}.json", data.encode())
    for v in range(1, ncommits + 1):
        data = "\n".join(
            json.dumps(a) for a in _commit_actions(v, per)) + "\n"
        store.write(f"{log}/{v:020d}.json", data.encode())
    store.enabled = True
    return log


def test_torn_multipart_aborts_cleans_up_and_keeps_table_readable(tmp_path):
    """A torn part upload fails the whole checkpoint: orphans are
    deleted, `_last_checkpoint` is never written, the aborted-writes
    counter moves, and the table still loads from the commit log."""
    eng, store = _chaos_engine(seed=3, error_rate=0.0, torn_write_rate=1.0)
    table_path = "mem://ckpt-torn"
    log = _build_mem_log(store, table_path, 10)
    settings.checkpoint_part_size = 12

    snap = Table.for_path(table_path, eng).latest_snapshot()
    a0 = ABORTED.value
    with pytest.raises(Exception) as exc_info:
        write_checkpoint(eng, snap)
    assert isinstance(exc_info.value,
                      (ckpt_pipeline.CheckpointWriteError, ChaosError))
    assert ABORTED.value == a0 + 1
    assert store.fault_counts.get("torn_write", 0) >= 1

    store.enabled = False
    assert read_last_checkpoint(eng.fs, log) is None
    leftovers = [s.path for s in store.list_from(f"{log}/")
                 if ".checkpoint" in s.path]
    assert leftovers == []  # every torn/created part was deleted
    clear_parse_cache()
    snap2 = Table.for_path(table_path, eng).latest_snapshot()
    assert snap2.version == 10 and snap2.state.num_files == 50


def test_torn_v2_top_level_cleans_fresh_sidecars_only(tmp_path):
    """When the V2 top-level write tears, this attempt's fresh sidecars
    are deleted but sidecars re-referenced from the previous checkpoint
    survive (they belong to the still-active checkpoint)."""
    eng, store = _chaos_engine(seed=5, error_rate=0.0, torn_write_rate=0.0)
    table_path = "mem://ckpt-v2-torn"
    log = _build_mem_log(store, table_path, 10)
    settings.checkpoint_part_size = 12

    snap = Table.for_path(table_path, eng).latest_snapshot()
    store.enabled = False
    write_checkpoint(eng, snap, policy="v2")
    prev = read_last_checkpoint(eng.fs, log)
    sidecars_before = {s.path for s in store.list_from(f"{log}/_sidecars/")}
    hint_before = store.read(f"{log}/_last_checkpoint")

    store.enabled = False
    _ = [store.write(f"{log}/{v:020d}.json",
                     ("\n".join(json.dumps(a)
                                for a in _commit_actions(v)) + "\n").encode())
         for v in (11, 12)]
    clear_parse_cache()
    snap2 = Table.for_path(table_path, eng).latest_snapshot()
    # tear only top-level checkpoint files, not sidecars
    store.schedule.torn_write_rate = 1.0
    store.torn_pred = lambda path: "_sidecars" not in path
    store.enabled = True
    a0 = ABORTED.value
    with pytest.raises(Exception):
        write_checkpoint(eng, snap2, policy="v2", prev_info=prev)
    assert ABORTED.value == a0 + 1

    store.enabled = False
    sidecars_after = {s.path for s in store.list_from(f"{log}/_sidecars/")}
    assert sidecars_before <= sidecars_after  # reused sidecars survived
    assert len(sidecars_after) == len(sidecars_before)  # fresh one deleted
    tops = [s.path for s in store.list_from(f"{log}/")
            if ".checkpoint" in s.path and "_sidecars" not in s.path]
    # the version-10 checkpoint survives; the torn version-12 top-level
    # (and any retry half-file) was deleted
    assert tops and all(f"{10:020d}.checkpoint" in p for p in tops)
    assert store.read(f"{log}/_last_checkpoint") == hint_before


# ------------------------------------------------------ profitability gate


def test_gate_stands_down_locally_engages_remote(tmp_path, monkeypatch):
    monkeypatch.delenv("DELTA_TPU_CKPT_PIPELINE", raising=False)
    local_eng = HostEngine()
    log = _build_local_log(tmp_path, 3)
    # local store: the pool fan-out already saturates the disk
    assert ckpt_pipeline.profitable(local_eng, log, 5) is False
    # single artifact: nothing to overlap, even remotely
    mem_eng, _store = _chaos_engine(seed=1, error_rate=0.0)
    assert ckpt_pipeline.profitable(mem_eng, "mem://t/_delta_log", 1) is False
    # non-local store: upload latency is what the pipeline hides
    assert ckpt_pipeline.profitable(mem_eng, "mem://t/_delta_log", 5) is True
    # off kills it everywhere; force engages it everywhere
    monkeypatch.setenv("DELTA_TPU_CKPT_PIPELINE", "off")
    assert ckpt_pipeline.profitable(mem_eng, "mem://t/_delta_log", 5) is False
    monkeypatch.setenv("DELTA_TPU_CKPT_PIPELINE", "force")
    assert ckpt_pipeline.profitable(local_eng, log, 1) is True


def test_forced_pipeline_parity_and_stall_accounting(tmp_path, monkeypatch):
    """Forcing the pipeline on a local store must not change the
    resulting state, and the stall counters must account the overlap."""
    log = _build_local_log(tmp_path, 10)
    settings.checkpoint_part_size = 12
    eng = HostEngine()
    live = _digest(tmp_path, eng)

    monkeypatch.setenv("DELTA_TPU_CKPT_PIPELINE", "force")
    s0 = obs.counter("checkpoint.upload_stall_ns").value
    snap = Table.for_path(str(tmp_path), eng).latest_snapshot()
    write_checkpoint(eng, snap)
    assert obs.counter("checkpoint.upload_stall_ns").value > s0

    _drop_commits(log, 10)
    assert _digest(tmp_path, eng) == live


# --------------------------------------------------- device kernel parity


def _random_lanes(rng, n, n_parts):
    lanes, valids = [], []
    for _ in range(3):
        lanes.append(rng.integers(-2**40, 2**40, size=n))
        valids.append(rng.random(n) > 0.2)
    codes = rng.integers(0, 5, size=n)
    lanes.append(codes)
    valids.append(np.ones(n, bool))
    part_of = rng.integers(0, n_parts, size=n).astype(np.int32)
    return lanes, valids, part_of


@pytest.mark.parametrize("n,n_parts", [(0, 1), (7, 1), (1000, 9)])
def test_stats_block_host_device_parity(n, n_parts):
    from delta_tpu.ops import stats as ckstats

    rng = np.random.default_rng(n + n_parts)
    lanes, valids, part_of = _random_lanes(rng, n, n_parts)
    host = ckstats.host_stats_block(lanes, valids, part_of, n_parts, 5)
    dev = ckstats.checkpoint_stats_block(lanes, valids, part_of, n_parts, 5)
    assert host.dtype == dev.dtype == np.int64
    assert np.array_equal(host, dev)


def test_dv_device_pack_byte_parity(monkeypatch):
    from delta_tpu.dv.roaring import RoaringBitmapArray

    rng = np.random.default_rng(11)
    vals = np.unique(np.concatenate([
        rng.choice(65536, size=30000, replace=False),            # bitmap
        65536 + rng.choice(65536, size=500, replace=False),      # array
        2 * 65536 + rng.choice(65536, size=60000, replace=False),  # bitmap
        (1 << 32) + rng.choice(65536, size=5000, replace=False),  # bitmap
    ]).astype(np.uint64))
    bm = RoaringBitmapArray(values=vals)
    monkeypatch.delenv("DELTA_TPU_DEVICE_DV_PACK", raising=False)
    host = bm.serialize_delta()
    monkeypatch.setenv("DELTA_TPU_DEVICE_DV_PACK", "1")
    dev = bm.serialize_delta()
    assert host == dev
    rt = RoaringBitmapArray.deserialize_delta(dev)
    assert np.array_equal(rt.values, vals)
