"""HBM resident ledger (`obs.hbm`): handle lifecycle, leak detection
via owner finalizers, the strict reconciliation audit over real
snapshot loads, serve-cache eviction accounting, and the `delta-hbm`
CLI round-trip.

Everything runs on CPU (the conftest mesh emulates 8 devices); the
integration tests drive the real resident replay / stats-index /
checkpoint-handoff owners through their production registration sites
and assert the ledger reconciles byte-exactly — zero drift, zero
leaks — across load, advance, and eviction."""

import gc
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from delta_tpu import obs
from delta_tpu.obs import hbm
from delta_tpu.tools import hbm_cli


@pytest.fixture(autouse=True)
def _clean_hbm_obs():
    """Every test starts and ends with an empty ledger and the mode
    re-read from the (test-runner) env — and, critically, with stale
    finalizers from earlier tests' owners detached so their GC can't
    report leaks into this test's epoch."""
    obs.reset_hbm_obs()
    obs.set_hbm_obs_mode("on")
    yield
    obs.set_hbm_obs_mode(None)
    obs.reset_hbm_obs()


def _counter_value(name):
    return obs.counter(name).value


class _Owner:
    """A minimal weakref-able artifact owner."""


# ------------------------------------------------------- lifecycle ----------


def test_register_touch_grow_release_lifecycle():
    arr = jnp.arange(256, dtype=jnp.int32)
    owner = _Owner()
    with hbm.table_scope("/tables/alpha"):
        h = hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS, version=7,
                         arrays=(arr,), rebuild_cost_class="expensive")
    assert h.nbytes == arr.nbytes
    assert h.table_path == "/tables/alpha"     # ambient scope resolved
    assert h.version == 7
    led = hbm.ledger()
    assert led.total_bytes() == arr.nbytes
    assert led.artifact_count() == 1
    assert led.kind_bytes(hbm.KIND_REPLAY_KEYS) == arr.nbytes

    before = h.last_access
    time.sleep(0.002)
    h.touch()
    assert h.last_access > before

    grown = jnp.arange(1024, dtype=jnp.int32)
    h.grow(arrays=(grown,))
    assert h.nbytes == grown.nbytes
    assert led.total_bytes() == grown.nbytes
    assert led.peak_bytes() == grown.nbytes

    h.release()
    h.release()                                # idempotent
    assert led.total_bytes() == 0
    assert led.artifact_count() == 0
    assert led.peak_bytes() == grown.nbytes    # peak survives release
    del owner


def test_explicit_table_path_outranks_scope():
    owner = _Owner()
    with hbm.table_scope("/tables/ambient"):
        h = hbm.register(owner, kind=hbm.KIND_STATS_INDEX,
                         table_path="/tables/explicit", nbytes=64)
    assert h.table_path == "/tables/explicit"
    h.release()


def test_rollup_both_dimensions():
    owners = [_Owner() for _ in range(3)]
    hbm.register(owners[0], kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/a", nbytes=100)
    hbm.register(owners[1], kind=hbm.KIND_STATS_INDEX,
                 table_path="/t/a", nbytes=10)
    hbm.register(owners[2], kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/b", nbytes=1000)
    by_table = hbm.rollup(by="table")
    assert by_table["/t/a"] == {
        "nbytes": 110, "artifacts": 2,
        "by_kind": {hbm.KIND_REPLAY_KEYS: 100, hbm.KIND_STATS_INDEX: 10}}
    by_kind = hbm.rollup(by="kind")
    assert by_kind[hbm.KIND_REPLAY_KEYS]["nbytes"] == 1100
    assert by_kind[hbm.KIND_REPLAY_KEYS]["by_table"] == {
        "/t/a": 100, "/t/b": 1000}
    with pytest.raises(ValueError):
        hbm.rollup(by="color")
    del owners


def test_gauges_are_ledger_derived():
    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS, nbytes=2048)
    assert obs.gauge("hbm.resident_bytes").read() == 2048
    assert obs.gauge("hbm.resident_artifacts").read() == 1
    assert obs.gauge("hbm.resident_bytes_peak").read() == 2048
    # the subsumed pre-ledger names stay live, per-kind
    assert obs.gauge("replay.resident_hbm_bytes").read() == 2048
    assert obs.gauge("scan.stats_index_hbm_bytes").read() == 0


# ---------------------------------------------------- disabled path ---------


def test_off_mode_returns_shared_noop_handle():
    obs.set_hbm_obs_mode("off")
    a = hbm.register(_Owner(), kind=hbm.KIND_REPLAY_KEYS, nbytes=999)
    b = hbm.register(None, kind=hbm.KIND_STATS_INDEX)
    assert a is b is hbm.noop_handle()   # process-wide singleton
    a.touch()
    a.grow(nbytes=123)
    a.release()                          # all no-ops, all safe
    assert hbm.ledger().total_bytes() == 0
    assert hbm.ledger().artifact_count() == 0


def test_off_mode_register_overhead_is_negligible():
    """The off-mode register must cost nanoseconds, not microseconds.
    The bound is a generous 5us/call so a loaded CI box cannot flake;
    nothing holds a tighter one."""
    obs.set_hbm_obs_mode("off")
    n = 20_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        h = hbm.register(None, kind=hbm.KIND_REPLAY_KEYS, nbytes=8)
        h.touch()
        h.release()
    per_call_ns = (time.perf_counter_ns() - t0) / n
    assert per_call_ns < 5_000


def test_bad_mode_string_rejected():
    with pytest.raises(ValueError):
        obs.set_hbm_obs_mode("loud")


# ------------------------------------------------------ leak tracing --------


def test_owner_gc_without_release_counts_leak():
    leaks0 = _counter_value("hbm.resident_leaks")
    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/leaky", nbytes=4096)
    assert hbm.ledger().total_bytes() == 4096
    del owner
    gc.collect()
    assert _counter_value("hbm.resident_leaks") == leaks0 + 1
    recs = hbm.leak_records()
    assert len(recs) == 1
    assert recs[0]["table_path"] == "/t/leaky"
    assert recs[0]["kind"] == hbm.KIND_REPLAY_KEYS
    assert recs[0]["nbytes"] == 4096
    # the leak auto-deregisters: gauges must not keep counting a
    # buffer that died with its owner
    assert hbm.ledger().total_bytes() == 0
    assert hbm.ledger().artifact_count() == 0


def test_release_detaches_finalizer_no_phantom_leak():
    leaks0 = _counter_value("hbm.resident_leaks")
    owner = _Owner()
    h = hbm.register(owner, kind=hbm.KIND_STATS_INDEX, nbytes=64)
    h.release()
    del owner
    gc.collect()
    assert _counter_value("hbm.resident_leaks") == leaks0


def test_leak_fails_audit_and_strict_raises():
    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_CKPT_HANDOFF,
                 table_path="/t/leaky", nbytes=128)
    del owner
    gc.collect()
    result = hbm.audit()
    assert not result["ok"] and result["leaks"]
    obs.set_hbm_obs_mode("strict")
    with pytest.raises(RuntimeError, match="leaked"):
        hbm.audit()


def test_strict_audit_detects_unrecorded_grow_as_drift():
    arr = jnp.arange(64, dtype=jnp.int32)
    owner = _Owner()
    h = hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS, arrays=(arr,))
    # lie about the size: the registered figure no longer matches the
    # live array — that's drift, byte-exactly
    h.grow(nbytes=h.nbytes + 8)
    obs.set_hbm_obs_mode("strict")
    with pytest.raises(RuntimeError, match="unrecorded grow"):
        hbm.audit()
    h.release()
    del owner


# ------------------------------------- reconciliation over real loads -------


def _tpu_table(tmp_path, n_commits, files_per_commit=20):
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.models.actions import AddFile, RemoveFile
    from delta_tpu.models.schema import INTEGER, StructField, StructType
    from delta_tpu.table import Table

    eng = TpuEngine(replay_shards=8)
    t = Table.for_path(str(tmp_path), eng)
    t.create_transaction_builder().with_schema(
        StructType([StructField("x", INTEGER)])).build().commit()
    for i in range(n_commits):
        txn = t.start_transaction()
        for j in range(files_per_commit):
            txn.add_file(AddFile(
                path=f"p{i}_{j}.parquet", partitionValues={}, size=100 + j,
                modificationTime=1000 + i, dataChange=True,
                stats=json.dumps({"numRecords": 10 * j,
                                  "minValues": {"x": j},
                                  "maxValues": {"x": j + 100}})))
        if i > 0:
            txn.remove_file(RemoveFile(
                path=f"p{i - 1}_0.parquet", deletionTimestamp=2000 + i,
                dataChange=True))
        txn.commit()
    return t


def test_strict_reconciliation_over_sharded_load_and_advance(tmp_path):
    """The acceptance cycle: a real sharded load registers the resident
    replay key lane under the right table, the audit reconciles
    byte-exactly against jax.live_arrays(), an incremental advance
    grows the entry in place (still byte-exact), and releasing leaves
    the ledger empty — all under strict, which would raise on any
    drift or leak."""
    from delta_tpu.models.actions import AddFile
    from delta_tpu.parallel.resident import release_snapshot_resident

    obs.set_hbm_obs_mode("strict")
    t = _tpu_table(tmp_path, 8)
    snap = t.latest_snapshot()
    _ = snap.state.live_mask  # force replay
    res = snap._state.resident
    assert res is not None, "sharded load did not establish residency"

    led = hbm.ledger()
    assert led.artifact_count() == 1
    assert led.kind_bytes(hbm.KIND_REPLAY_KEYS) == res.key_sh.nbytes
    [rec] = hbm.residents()
    assert rec["table_path"] == str(tmp_path)   # table_scope attribution
    assert rec["kind"] == hbm.KIND_REPLAY_KEYS
    assert rec["rebuild_cost_class"] == "expensive"
    result = hbm.audit()                        # strict: raises on drift
    assert result["ok"]
    assert result["verified_bytes"] == result["ledger_bytes"] \
        == res.key_sh.nbytes

    # advance: the donated in-place append swaps the device buffer;
    # grow() must re-point the audit weakrefs and re-account the bytes
    txn = t.start_transaction()
    for j in range(20):
        txn.add_file(AddFile(
            path=f"inc_{j}.parquet", partitionValues={}, size=50,
            modificationTime=5000, dataChange=True))
    txn.commit()
    snap2 = t.update()
    assert snap2._state.resident is res
    assert led.artifact_count() == 1            # moved, not re-registered
    result = hbm.audit()
    assert result["ok"]
    assert result["verified_bytes"] == result["ledger_bytes"] \
        == res.key_sh.nbytes

    release_snapshot_resident(snap2)
    assert led.total_bytes() == 0
    assert led.artifact_count() == 0
    assert hbm.audit()["ok"]

    del snap, snap2, res
    gc.collect()
    hbm.audit()                                 # strict: no leaks either


def test_stats_index_lanes_register_with_table_attribution(tmp_path):
    from delta_tpu.stats.device_index import snapshot_stats_index

    obs.set_hbm_obs_mode("strict")
    t = _tpu_table(tmp_path, 3)
    snap = t.latest_snapshot()
    state = snap.state
    idx = snapshot_stats_index(state, state.add_files_table)
    assert idx is not None and idx.has_lanes
    lanes = idx.device_lanes()
    assert lanes[0] is not None

    led = hbm.ledger()
    nbytes = led.kind_bytes(hbm.KIND_STATS_INDEX)
    assert nbytes > 0
    recs = [r for r in hbm.residents()
            if r["kind"] == hbm.KIND_STATS_INDEX]
    assert len(recs) == 1
    assert recs[0]["table_path"] == str(tmp_path)
    assert recs[0]["version"] == snap.version
    assert recs[0]["rebuild_cost_class"] == "cheap"
    assert hbm.audit()["ok"]

    touches0 = led.touches
    idx.device_lanes()                          # read path touches
    assert led.touches > touches0

    idx.release()
    assert led.kind_bytes(hbm.KIND_STATS_INDEX) == 0
    assert hbm.audit()["ok"]


def test_handoff_part_keys_release_helper():
    from delta_tpu.ops.page_decode import PartKeys, release_part_keys

    codes = jnp.arange(128, dtype=jnp.uint32)
    keys = PartKeys(codes=codes, n_add=4, n_rem=0, n_bad=0,
                    uniq=[], n_rows=4)
    keys.hbm = hbm.register(keys, kind=hbm.KIND_CKPT_HANDOFF,
                            table_path="/t/ckpt", arrays=(codes,),
                            rebuild_cost_class="cheap")
    assert hbm.ledger().kind_bytes(hbm.KIND_CKPT_HANDOFF) == codes.nbytes
    release_part_keys([keys])
    assert keys.hbm is None
    assert hbm.ledger().kind_bytes(hbm.KIND_CKPT_HANDOFF) == 0
    release_part_keys([keys])                   # idempotent on None


def test_serve_cache_eviction_releases_everything(tmp_path):
    """Evicting a cached table must deregister every ledger-accounted
    artifact it owned (replay key lane AND stats-index lane); the
    strict audit proves nothing leaked and nothing drifted."""
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.serve.cache import SnapshotCache
    from delta_tpu.serve.config import ServeConfig
    from delta_tpu.stats.device_index import snapshot_stats_index

    obs.set_hbm_obs_mode("strict")
    t1 = _tpu_table(tmp_path / "t1", 6)
    t2 = _tpu_table(tmp_path / "t2", 6)
    del t1, t2
    # the builder tables' own commit-path residents are not under test;
    # start this epoch with an empty ledger so every entry below is
    # cache-owned
    obs.reset_hbm_obs()
    eng = TpuEngine(replay_shards=8)
    cache = SnapshotCache(eng, ServeConfig(cache_tables=1,
                                           refresh_ms=60_000.0))

    snap, meta = cache.snapshot_for(str(tmp_path / "t1"))
    assert meta == {}
    _ = snap.state.live_mask
    assert snap._state.resident is not None
    idx = snapshot_stats_index(snap.state, snap.state.add_files_table)
    assert idx is not None and idx.device_lanes()[0] is not None

    led = hbm.ledger()
    t1_path = str(tmp_path / "t1")
    assert {r["table_path"] for r in hbm.residents()} == {t1_path}
    assert led.artifact_count() == 2
    assert hbm.audit()["ok"]

    # a warm hit touches the resident artifacts (recency accounting)
    touches0 = led.touches
    cache.snapshot_for(t1_path)
    assert led.touches > touches0

    # capacity 1: loading the second table evicts the first, and the
    # eviction releases both of its device lanes through the ledger
    snap2, _ = cache.snapshot_for(str(tmp_path / "t2"))
    _ = snap2.state.live_mask
    assert all(r["table_path"] != t1_path for r in hbm.residents())
    result = hbm.audit()
    assert result["ok"]

    del snap, idx
    gc.collect()
    hbm.audit()                                 # still zero leaks


# ------------------------------------------------- health + CLI -------------


def test_health_summary_shape():
    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/a", nbytes=512)
    s = hbm.health_summary()
    assert s["resident_bytes"] == 512
    assert s["resident_artifacts"] == 1
    assert s["peak_bytes"] == 512
    assert s["by_kind"] == {hbm.KIND_REPLAY_KEYS: 512}
    assert isinstance(s["leaks"], int)
    del owner


def test_cli_rollup_roundtrips_from_jsonl(tmp_path):
    owners = [_Owner() for _ in range(3)]
    hbm.register(owners[0], kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/a", version=3, nbytes=4096)
    hbm.register(owners[1], kind=hbm.KIND_STATS_INDEX,
                 table_path="/t/a", version=3, nbytes=256)
    hbm.register(owners[2], kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/b", version=9, nbytes=8192)
    dump = tmp_path / "ledger.jsonl"
    assert hbm.dump_ledger(str(dump)) == 3

    residents, leaks = hbm_cli.load_ledger_dump(str(dump))
    assert len(residents) == 3 and not leaks
    # the dump-side rollup must match the live ledger record-for-record
    assert hbm_cli.rollup_records(residents, by="table") \
        == hbm.rollup(by="table")
    assert hbm_cli.rollup_records(residents, by="kind") \
        == hbm.rollup(by="kind")
    del owners


def test_cli_views_and_exit_codes(tmp_path, capsys):
    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/a", nbytes=4096)
    leaker = _Owner()
    hbm.register(leaker, kind=hbm.KIND_STATS_INDEX,
                 table_path="/t/gone", nbytes=64)
    del leaker
    gc.collect()
    dump = tmp_path / "ledger.jsonl"
    hbm.dump_ledger(str(dump))

    assert hbm_cli.main([str(dump)]) == 0
    out = capsys.readouterr().out
    assert "/t/a" in out and "replay-keys" in out

    assert hbm_cli.main([str(dump), "--top", "5", "--json"]) == 0
    top = json.loads(capsys.readouterr().out)
    assert top[0]["nbytes"] == 4096

    # leaks present -> report + nonzero exit (the CI grep signal)
    assert hbm_cli.main([str(dump), "--leaks"]) == 1
    out = capsys.readouterr().out
    assert "LEAK" in out and "/t/gone" in out

    assert hbm_cli.main([str(tmp_path / "missing.jsonl")]) == 2


def test_serve_health_carries_hbm_section():
    """The serve health() payload exposes the ledger summary (no accept
    thread needed — construct the server and call the handler)."""
    from delta_tpu.serve.server import DeltaServeServer

    owner = _Owner()
    hbm.register(owner, kind=hbm.KIND_REPLAY_KEYS,
                 table_path="/t/a", nbytes=1024)
    srv = DeltaServeServer("127.0.0.1", 0)
    try:
        health = srv.health()
    finally:
        srv._listener.close()
    assert health["hbm"]["resident_bytes"] == 1024
    assert health["hbm"]["by_kind"] == {hbm.KIND_REPLAY_KEYS: 1024}
    assert health["hbm"]["resident_artifacts"] == 1
    del owner
