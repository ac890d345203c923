"""Device-fault chaos plane: seeded injection at the dispatch funnel,
route breakers with host-twin degradation, HBM shed-and-retry.

The storage half of the chaos story lives in test_resilience.py
(ChaosStore hammering the LogStore); this module soaks the device half:
a seeded :class:`ChaosEngine` armed at the
``obs/device.py::device_dispatch()`` funnel injects dispatch errors,
simulated RESOURCE_EXHAUSTED, transfer stalls, and recompile storms
into every gated device route (replay / parse / decode / skip / sql),
and the acceptance property is the same as the storage soak's: the
workload converges **bit-identically** to the fault-free run, because
every route classifies, counts, and falls back to its host twin instead
of corrupting or dying.

Everything runs on CPU (the conftest mesh emulates 8 devices) — the
gate economics still choose the device routes there, so the injection
exercises the real absorption paths, never mocks."""

import time

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import obs, resilience
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.expressions import col, lit
from delta_tpu.obs import hbm
from delta_tpu.parallel import gate
from delta_tpu.resilience import device_faults
from delta_tpu.resilience.breaker import route_breaker_for
from delta_tpu.resilience.classify import TRANSIENT, classify
from delta_tpu.resilience.device_chaos import (
    ChaosEngine,
    DeviceChaosError,
    DeviceChaosSchedule,
    DeviceResourceExhaustedError,
    engine_from_env,
)
from delta_tpu.sql import sql
from delta_tpu.tables import Table

GATES = ("replay", "parse", "decode", "skip", "sql")


@pytest.fixture(autouse=True)
def _device_chaos_obs():
    """Gate records on, ledger accounting on, both swept per test.

    `resilience.reset()` (the conftest autouse fixture) already disarms
    any leftover chaos engine and clears the route breakers; this adds
    the obs planes the assertions below read."""
    obs.reset_device_obs()
    obs.reset_hbm_obs()
    obs.set_device_obs_mode("on")
    obs.set_hbm_obs_mode("on")
    yield
    obs.set_device_obs_mode(None)
    obs.set_hbm_obs_mode(None)
    obs.reset_device_obs()
    obs.reset_hbm_obs()


def _chaos(seed, **rates):
    """A chaos engine whose stalls cost no wall clock."""
    return ChaosEngine(DeviceChaosSchedule(seed, **rates),
                       sleep=lambda s: None)


def _drive(engine, n=40):
    """Deterministic dispatch sequence straight at the funnel hook."""
    for i in range(n):
        try:
            engine.on_dispatch(f"kern.{i % 3}", key=(i % 5,),
                               gate=GATES[i % 5])
        except DeviceChaosError:
            pass
    return list(engine.fault_log)


# ------------------------------------------------- schedule / engine


def test_schedule_replay_identical_fault_log():
    """The replayability contract: same seed + same dispatch sequence
    -> bit-identical fault schedule; a different seed diverges."""
    rates = dict(dispatch_error_rate=0.2, oom_rate=0.1,
                 stall_rate=0.1, recompile_rate=0.1)
    log_a = _drive(_chaos(7, **rates))
    log_b = _drive(_chaos(7, **rates))
    assert log_a == log_b
    assert log_a  # the schedule actually injected something
    assert log_a != _drive(_chaos(8, **rates))


def test_fault_counts_mirror_log_and_counter():
    before = obs.counter("chaos.device_faults").value
    eng = _chaos(3, dispatch_error_rate=0.3, oom_rate=0.2)
    log = _drive(eng)
    assert eng.total_faults == len(log)
    assert sum(eng.fault_counts.values()) == len(log)
    assert eng.fault_counts["error"] == sum(
        1 for k, _, _ in log if k == "error")
    assert obs.counter("chaos.device_faults").value == before + len(log)


def test_context_manager_arms_the_dispatch_funnel():
    """Arming injects at the real `obs.device_dispatch` seam; exiting
    the context restores clean dispatch."""
    with _chaos(1, dispatch_error_rate=1.0) as eng:
        with pytest.raises(DeviceChaosError):
            with obs.device_dispatch("probe.kernel", key=(8,), gate="sql"):
                pass
    assert eng.fault_log == [("error", "probe.kernel", "sql")]
    with obs.device_dispatch("probe.kernel", key=(8,), gate="sql"):
        pass  # disarmed: no injection


def test_injection_works_with_device_obs_off():
    """The funnel hook runs before the obs-mode check: chaos does not
    require the observability plane."""
    obs.set_device_obs_mode("off")
    with _chaos(2, dispatch_error_rate=1.0):
        with pytest.raises(DeviceChaosError):
            with obs.device_dispatch("probe.kernel", gate="skip"):
                pass


def test_resilience_reset_disarms():
    eng = _chaos(1, dispatch_error_rate=1.0)
    eng.arm()
    resilience.reset()
    with obs.device_dispatch("probe.kernel", gate="sql"):
        pass  # no injection: reset() cleared the armed engine


def test_kernel_filter_scopes_injection():
    eng = _chaos(5, dispatch_error_rate=1.0)
    eng.kernel_filter = lambda name: name.startswith("sqlops.")
    with eng:
        with obs.device_dispatch("replay.single_raw", gate="replay"):
            pass  # filtered out: untouched
        with pytest.raises(DeviceChaosError):
            with obs.device_dispatch("sqlops.sort", gate="sql"):
                pass
    assert [k for k, _, _ in eng.fault_log] == ["error"]


def test_recompile_injection_salts_key_and_counts_compiles():
    """A recompile injection makes the SAME shape key read as novel, so
    device obs counts a compile per injection — the storm alarm's input
    — without touching the jit cache."""
    before = obs.counter("device.compiles").value
    with _chaos(9, recompile_rate=1.0) as eng:
        for _ in range(3):
            with obs.device_dispatch("probe.kernel", key=(4, 4),
                                     gate="decode"):
                pass
    assert eng.fault_counts["recompile"] == 3
    # every dispatch compiled: the salt made each key a first sighting
    assert obs.counter("device.compiles").value == before + 3


def test_stall_injection_sleeps_but_never_raises():
    naps = []
    eng = ChaosEngine(
        DeviceChaosSchedule(4, stall_rate=1.0, stall_s=(0.01, 0.02)),
        sleep=naps.append)
    with eng:
        with obs.device_dispatch("probe.kernel", gate="parse"):
            pass
    assert len(naps) == 1
    assert 0.01 <= naps[0] <= 0.02
    assert eng.fault_counts["stall"] == 1


def test_injected_faults_classify_transient():
    """Both injected fault shapes must classify transient — that is
    what licenses the absorption paths to run the host twin."""
    assert classify(DeviceChaosError("injected")) == TRANSIENT
    oom = DeviceResourceExhaustedError("sqlops.sort")
    assert classify(oom) == TRANSIENT
    assert device_faults.is_resource_exhausted(oom)
    assert "RESOURCE_EXHAUSTED" in str(oom)
    assert not device_faults.is_resource_exhausted(ValueError("nope"))


def test_engine_from_env(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_DEVICE_CHAOS", "off")
    assert engine_from_env() is None
    monkeypatch.setenv("DELTA_TPU_DEVICE_CHAOS", "17")
    monkeypatch.setenv("DELTA_TPU_DEVICE_CHAOS_RATE", "0.25")
    monkeypatch.setenv("DELTA_TPU_DEVICE_CHAOS_KINDS", "error,stall")
    eng = engine_from_env()
    assert eng is not None
    s = eng.schedule
    assert s.seed == 17
    assert s.dispatch_error_rate == 0.25
    assert s.stall_rate == 0.25
    assert s.oom_rate == 0.0 and s.recompile_rate == 0.0


# ------------------------------------------------- HBM shed-and-retry


class _Artifact:
    """A weakref-able owner whose evictor releases its handle."""

    def __init__(self, cost):
        arr = np.zeros(64, dtype=np.int64)
        self.handle = hbm.register(
            self, kind="test-artifact", table_path=f"/t/{cost}",
            nbytes=arr.nbytes, rebuild_cost_class=cost)
        self.evicted = False
        self.handle._evictor = hbm._wrap_evictor(self.evict)

    def evict(self):
        self.evicted = True
        self.handle.release()


def test_shed_evicts_cheapest_to_rebuild_first():
    exp = _Artifact("expensive")
    cheap = _Artifact("cheap")
    norm = _Artifact("normal")
    n, freed = hbm.shed(max_artifacts=1)
    assert (n, freed) == (1, 512)
    assert cheap.evicted and not norm.evicted and not exp.evicted
    n, _ = hbm.shed(max_artifacts=2)
    assert n == 2
    assert norm.evicted and exp.evicted
    assert hbm.ledger().artifact_count() == 0
    assert not hbm.leak_records()


def test_shed_cap_env_knob(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_HBM_SHED_MAX", "1")
    arts = [_Artifact("normal") for _ in range(3)]
    n, _ = hbm.shed()
    assert n == 1
    assert sum(a.evicted for a in arts) == 1


def test_shed_skips_artifacts_without_evictor():
    arr = np.zeros(8, dtype=np.int64)
    owner = _Artifact("cheap")
    pinned = hbm.register(owner, kind="pinned", table_path="/t/p",
                          nbytes=arr.nbytes)  # no evictor: unsheddable
    n, _ = hbm.shed(max_artifacts=8)
    assert n == 1  # only the evictable one went
    assert hbm.ledger().artifact_count() == 1
    pinned.release()


def test_shed_retry_evicts_and_retries_once():
    art = _Artifact("cheap")
    before = obs.counter("hbm.shed_retries").value
    calls = []

    def thunk():
        calls.append(1)
        if len(calls) == 1:
            raise DeviceResourceExhaustedError("sqlops.group_codes")
        return "answer"

    assert device_faults.shed_retry("sql", thunk) == "answer"
    assert len(calls) == 2
    assert art.evicted
    assert obs.counter("hbm.shed_retries").value == before + 1
    assert obs.counter("hbm.sheds").value >= 1


def test_shed_retry_nothing_sheddable_propagates():
    """Empty ledger: the allocation failure goes straight to the
    absorption path (host twin), no blind second attempt."""
    calls = []

    def thunk():
        calls.append(1)
        raise DeviceResourceExhaustedError("sqlops.sort")

    with pytest.raises(DeviceResourceExhaustedError):
        device_faults.shed_retry("sql", thunk)
    assert len(calls) == 1


def test_shed_retry_non_oom_errors_pass_through():
    art = _Artifact("cheap")

    def thunk():
        raise DeviceChaosError("not an allocation failure")

    with pytest.raises(DeviceChaosError):
        device_faults.shed_retry("sql", thunk)
    assert not art.evicted  # shed is reserved for allocation pressure
    art.evict()


def test_shed_noop_when_ledger_off():
    obs.set_hbm_obs_mode("off")
    assert hbm.shed() == (0, 0)


# ------------------------------------------------ the one guarded call


def _half_open(monkeypatch, g):
    """Gate `g`'s breaker tripped, cooled down, its one probe taken."""
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_RESET_S", "30")
    resilience.reset()
    gate.route_failed(g, DeviceChaosError("trip"))
    b = route_breaker_for(g)
    later = time.monotonic() + 31.0
    b._clock = lambda: later
    b.before_call()
    assert b.state == "half_open"
    return b


def _scripted(steps):
    """A device thunk that raises or returns `steps`, one a call."""
    left = list(steps)

    def thunk():
        step = left.pop(0)
        if isinstance(step, BaseException):
            raise step
        return step

    thunk.left = left
    return thunk


def _oom():
    return DeviceResourceExhaustedError("probe.kernel")


# case: (the thunk's script, value, fell_back reason, fallback counter's
# delta, shed retries, breaker after a half-open probe)
_GUARDED_CASES = {
    "answers": (lambda: ["answer"], "answer", None, 0, 0, "closed"),
    "declines": (lambda: [None], None, None, 0, 0, "half_open"),
    "transient": (lambda: [DeviceChaosError("injected")], None,
                  "device-error:DeviceChaosError", 1, 0, "open"),
    "permanent": (lambda: [FileNotFoundError("part gone")],
                  FileNotFoundError, None, 0, 0, "closed"),
    "oom-then-answers": (lambda: [_oom(), "answer"], "answer", None,
                         0, 1, "closed"),
    "oom-twice": (lambda: [_oom(), _oom()], None,
                  "device-error:DeviceResourceExhaustedError", 1, 1,
                  "open"),
}


@pytest.mark.parametrize("case", list(_GUARDED_CASES))
@pytest.mark.parametrize("g", GATES)
def test_guarded_is_the_route_contract(g, case, monkeypatch):
    """`device_faults.guarded` for every gate and outcome: the value or
    the exception, the cataloged counter's delta, the fallback on the
    gate record, the shed-and-retry, and what the breaker was told."""
    script, value, reason, counted, shed, state = _GUARDED_CASES[case]
    b = _half_open(monkeypatch, g)
    art = _Artifact("cheap")  # what an allocation failure can shed
    fallbacks = obs.counter(gate.ROUTES[g].fallback_counter)
    before = fallbacks.value
    retries = obs.counter("hbm.shed_retries").value
    obs.record_gate_decision(g, "device", {"op": "probe"}, {},
                             reason="breaker-probe")
    thunk = _scripted(script())
    if isinstance(value, type):
        with pytest.raises(value, match="part gone"):
            device_faults.guarded(g, thunk, fallbacks)
    else:
        out = device_faults.guarded(g, thunk, fallbacks)
        assert out.value == value and out.fell_back == reason
    assert not thunk.left  # every scripted attempt was made, no more
    assert fallbacks.value == before + counted
    assert obs.counter("hbm.shed_retries").value == retries + shed
    assert art.evicted == bool(shed)
    rec = obs.get_gate_records()[-1]
    assert rec["gate"] == g and rec["chosen"] == "device"
    assert rec["fell_back_to"] == ("host" if reason else None)
    assert rec.get("fallback_reason") == reason
    assert b.state == state
    if not art.evicted:
        art.evict()


def test_try_device_classifies_and_reports_nothing_else(monkeypatch):
    """The inner step (the early replay launch's): a transient failure
    feeds the breaker and is the outcome; no counter, no gate record,
    and an answer closes no probe (`compute_masks_device` reports)."""
    b = _half_open(monkeypatch, "replay")
    fallbacks = obs.counter(gate.ROUTES["replay"].fallback_counter)
    before = fallbacks.value
    obs.record_gate_decision("replay", "single", {"op": "probe"}, {})
    assert device_faults.try_device(
        "replay", _scripted(["launched"])) == ("launched", None)
    assert b.state == "half_open"
    out = device_faults.try_device(
        "replay", _scripted([DeviceChaosError("injected")]))
    assert out == (None, "device-error:DeviceChaosError")
    assert b.state == "open"
    with pytest.raises(FileNotFoundError):
        device_faults.try_device(
            "replay", _scripted([FileNotFoundError("gone")]))
    assert fallbacks.value == before
    assert obs.get_gate_records()[-1]["fell_back_to"] is None


def test_skip_retry_refetches_the_lanes_a_shed_evicted(monkeypatch):
    """The skip route's one thunk fetches the resident lanes itself: an
    allocation failure in the mask kernel sheds those very lanes (the
    cheapest artifact held), and the retry uploads them anew instead of
    reading arrays the ledger no longer counts."""
    import json
    import threading

    from delta_tpu.expressions.tree import Comparison
    from delta_tpu.ops import skipping as ops_skipping
    from delta_tpu.stats.skipping import skipping_mask

    class State:
        stats_index = None

    files = pa.table({
        "path": [f"f{i}.parquet" for i in range(8)],
        "stats": [json.dumps({"numRecords": 10, "minValues": {"a": i},
                              "maxValues": {"a": i + 2},
                              "nullCount": {"a": 0}}) for i in range(8)],
    })
    st = State()
    st.add_files_table = files
    st._stats_index_lock = threading.Lock()
    conjs = [Comparison("<", col("a"), lit(5))]
    want = skipping_mask(files, conjs, None)  # the Arrow ladder
    assert want.sum() == 5
    monkeypatch.setenv("DELTA_TPU_DEVICE_SKIP", "force")
    assert (skipping_mask(files, conjs, None, state=st) == want).all()
    assert st.stats_index._dev is not None  # resident from here on

    real, seen = ops_skipping.skip_mask_block, []

    def oom_once(*args):
        seen.append(args[0])
        if len(seen) == 1:
            raise _oom()
        return real(*args)

    monkeypatch.setattr(ops_skipping, "skip_mask_block", oom_once)
    counters = {n: obs.counter(n) for n in (
        "scan.stats_index_lane_splits", "hbm.shed_retries",
        "scan.device_plans", "scan.device_fallbacks")}
    before = {n: c.value for n, c in counters.items()}
    got = skipping_mask(files, conjs, None, state=st)
    assert (got == want).all()
    assert len(seen) == 2 and seen[0] is not seen[1]
    assert {n: c.value - before[n] for n, c in counters.items()} == {
        "scan.stats_index_lane_splits": 1, "hbm.shed_retries": 1,
        "scan.device_plans": 1, "scan.device_fallbacks": 0}
    assert obs.get_gate_records()[-1]["fell_back_to"] is None
    assert hbm.audit()["ok"] and not hbm.leak_records()


# --------------------------------------------- route breakers / gate


def _trip_sql(threshold):
    for _ in range(threshold):
        verdict = gate.route_failed("sql", DeviceChaosError("injected"))
        assert verdict == TRANSIENT


def _sql_decision():
    """One economics-scale sql_route decision (device-profitable)."""
    return gate.sql_route("group-agg", 200_000, nbytes=1_600_000,
                          engine_enabled=True)


def test_route_breaker_trips_and_degrades_decisions(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "2")
    resilience.reset()  # re-read the knob on next breaker creation
    assert _sql_decision() == "device"  # healthy: economics picks device
    before = obs.counter("gate.route_breaker_degrades").value
    _trip_sql(2)
    assert route_breaker_for("sql").state == "open"
    assert _sql_decision() == "host"
    rec = obs.get_gate_records()[-1]
    assert rec["reason"] == "breaker-open"
    assert obs.counter("gate.route_breaker_degrades").value == before + 1
    # the shared registry exposes it (serve /health renders this map)
    assert resilience.breaker_states()["route:sql"]["state"] == "open"


def test_route_breaker_permanent_failures_never_trip(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "2")
    resilience.reset()
    for _ in range(6):
        assert gate.route_failed(
            "sql", FileNotFoundError("part gone")) != TRANSIENT
    assert route_breaker_for("sql").state == "closed"
    assert _sql_decision() == "device"


def test_route_breaker_half_open_probe_rearms(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_RESET_S", "30")
    resilience.reset()
    _trip_sql(1)
    b = route_breaker_for("sql")
    assert b.state == "open"
    assert _sql_decision() == "host"
    # cooldown elapses (virtual clock: no wall waiting)
    now = [time.monotonic() + 31.0]
    b._clock = lambda: now[0]
    assert _sql_decision() == "device"
    assert obs.get_gate_records()[-1]["reason"] == "breaker-probe"
    # while the probe is in flight, further decisions stay degraded
    assert _sql_decision() == "host"
    gate.route_ok("sql")  # the probe's caller reports success
    assert b.state == "closed"
    assert _sql_decision() == "device"
    assert obs.get_gate_records()[-1]["reason"] == "economics"


def test_route_breaker_probe_failure_reopens(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_RESET_S", "30")
    resilience.reset()
    _trip_sql(1)
    b = route_breaker_for("sql")
    now = [time.monotonic() + 31.0]
    b._clock = lambda: now[0]
    assert _sql_decision() == "device"  # the probe
    gate.route_failed("sql", DeviceChaosError("probe failed"))
    assert b.state == "open"
    assert _sql_decision() == "host"  # clock restarted at the failure


def test_env_forced_routes_outrank_the_breaker(monkeypatch):
    """`DELTA_TPU_DEVICE_SQL=force` is explicit operator intent: the
    breaker must not silently override it."""
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "1")
    resilience.reset()
    _trip_sql(1)
    assert route_breaker_for("sql").state == "open"
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL", "force")
    assert _sql_decision() == "device"


# ------------------------------------------------------- chaos soak


def _engine():
    """A TpuEngine with every gated route opted in (on CPU the
    accel-backend default leaves parse/decode/skip off)."""
    eng = TpuEngine()
    eng.use_device_parse = True
    eng.use_device_decode = True
    eng.use_device_skip = True
    eng.use_device_sql = True
    return eng


def _batch(start, n):
    x = np.arange(start, start + n, dtype=np.int64)
    return pa.table({"x": x, "g": x % 7})


def _workload(eng, path):
    """Drive all five gated routes end to end: replay (snapshot
    builds), parse (json log tail), decode (checkpoint parts), skip
    (filtered scan planning), sql (device operators). Returns a
    logical digest that must be identical under ANY fault schedule."""
    dta.write_table(path, _batch(0, 2000), engine=eng)
    for b in range(1, 4):
        dta.write_table(path, _batch(b * 2000, 2000), engine=eng,
                        mode="append")
    Table.for_path(path, eng).checkpoint()
    for b in range(4, 6):
        dta.write_table(path, _batch(b * 2000, 2000), engine=eng,
                        mode="append")
    snap = Table.for_path(path, eng).latest_snapshot()
    filtered = dta.read_table(path, engine=eng,
                              filter=col("x") > lit(9_000))
    agg = sql(f"SELECT g, SUM(x) AS s, COUNT(*) AS c FROM '{path}' "
              f"GROUP BY g ORDER BY g", engine=eng)
    ordered = sql(f"SELECT x FROM '{path}' WHERE x < 100 "
                  f"ORDER BY x DESC LIMIT 7", engine=eng)
    full = dta.read_table(path, engine=eng)
    return (snap.version,
            sorted(filtered.column("x").to_pylist()),
            agg.to_pydict(),
            ordered.to_pydict(),
            sorted(full.column("x").to_pylist()))


_SOAK_RATES = dict(dispatch_error_rate=0.15, oom_rate=0.08,
                   stall_rate=0.08, recompile_rate=0.08)


def test_device_chaos_soak_converges_bit_identical():
    """THE acceptance property: under sustained seeded device chaos on
    every route, the workload's results are bit-identical to the
    fault-free run's — and the strict ledger audit stays green."""
    obs.set_hbm_obs_mode("strict")
    # both engines stay referenced through the audit: dropping an
    # engine mid-test would (correctly) record its still-resident
    # artifacts as leaks and fail the strict audit
    clean_eng, eng = _engine(), _engine()
    clean = _workload(clean_eng, "memory://dchaos-clean/tbl")
    ch = _chaos(11, **_SOAK_RATES)
    with ch:
        faulty = _workload(eng, "memory://dchaos-11/tbl")
    assert faulty == clean
    assert ch.total_faults > 0
    # chaos actually reached the gated routes, not just a corner
    gates_hit = {g for _k, _n, g in ch.fault_log if g}
    assert len(gates_hit) >= 3, gates_hit
    # strict audit: zero drift, zero leaks on every failure path
    assert hbm.audit()["ok"]
    assert not hbm.leak_records()


def test_device_chaos_soak_fault_schedule_replays(monkeypatch):
    """Same seed, same workload -> the identical fault schedule AND
    identical results: incidents replay from one integer. The pipelined
    log load dispatches from reader/parser threads (which interleaves
    fault *attribution* across runs), so this pins the serial path — the
    draw schedule itself is thread-safe by construction (one RNG under
    one lock) and the all-threads soaks above assert convergence."""
    monkeypatch.setenv("DELTA_TPU_PIPELINE", "off")
    ch_a = _chaos(23, **_SOAK_RATES)
    with ch_a:
        digest_a = _workload(_engine(), "memory://dchaos-a/tbl")
    # the replay must start from the state run A started from: empty
    # route breakers, empty resident ledger (a shed during run B must
    # not find run A's leftovers), fresh dispatch obs
    import gc
    gc.collect()
    resilience.reset()
    obs.reset_device_obs()
    obs.reset_hbm_obs()
    obs.set_device_obs_mode("on")
    ch_b = _chaos(23, **_SOAK_RATES)
    with ch_b:
        digest_b = _workload(_engine(), "memory://dchaos-b/tbl")
    assert ch_a.fault_log == ch_b.fault_log
    assert ch_a.fault_counts == ch_b.fault_counts
    assert digest_a == digest_b


def test_device_chaos_every_kind_absorbed():
    """Each fault kind alone converges — no kind relies on another's
    side effects to stay correct."""
    clean = _workload(_engine(), "memory://dchaos-kinds-clean/tbl")
    for i, rates in enumerate((
            dict(dispatch_error_rate=0.3),
            dict(oom_rate=0.3),
            dict(stall_rate=0.3),
            dict(recompile_rate=0.3))):
        resilience.reset()
        ch = _chaos(31 + i, **rates)
        with ch:
            digest = _workload(_engine(),
                               f"memory://dchaos-kind-{i}/tbl")
        assert digest == clean, f"diverged under {rates}"
        assert ch.total_faults > 0, f"nothing injected for {rates}"


def test_soak_breakers_trip_and_recover_on_schedule(monkeypatch):
    """Poison only the sql route at 100% and watch the breaker arc:
    trip within K classified failures, degrade decisions to the host
    twin, then re-arm through a half-open probe once chaos clears."""
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_RESET_S", "0.05")
    resilience.reset()
    eng = _engine()
    path = "memory://dchaos-breaker/tbl"
    dta.write_table(path, _batch(0, 4000), engine=eng)
    q = (f"SELECT g, SUM(x) AS s FROM '{path}' GROUP BY g ORDER BY g")
    want = sql(q, engine=eng).to_pydict()

    fallbacks = obs.counter("sql.device_fallbacks").value
    degrades = obs.counter("gate.route_breaker_degrades").value
    ch = _chaos(41, dispatch_error_rate=1.0)
    ch.kernel_filter = lambda name: name.startswith("sqlops.")
    with ch:
        for _ in range(4):
            assert sql(q, engine=eng).to_pydict() == want
        assert route_breaker_for("sql").state == "open"
        # every poisoned device attempt fell back and was counted
        assert obs.counter("sql.device_fallbacks").value > fallbacks
        # later queries were degraded at DECISION time (no device try)
        assert obs.counter(
            "gate.route_breaker_degrades").value > degrades
    # chaos gone: after the cooldown one probe re-arms the route
    time.sleep(0.06)
    assert sql(q, engine=eng).to_pydict() == want
    assert route_breaker_for("sql").state == "closed"
    reasons = [r["reason"] for r in obs.get_gate_records()
               if r["gate"] == "sql"]
    assert "breaker-open" in reasons and "breaker-probe" in reasons


def test_serve_stays_correct_under_device_chaos():
    """The serve workload: a live server answers correctly while the
    device plane is under chaos, and /health exposes the route
    breakers alongside the storage ones."""
    from delta_tpu.connect import connect
    from delta_tpu.serve import DeltaServeServer, ServeConfig

    eng = _engine()
    path = "memory://dchaos-serve/tbl"
    dta.write_table(path, _batch(0, 3000), engine=eng)
    srv = DeltaServeServer(
        "127.0.0.1", 0, engine=eng,
        config=ServeConfig.from_env(workers=2, max_queue=8,
                                    drain_grace_s=5.0))
    srv.start_background()
    try:
        host, port = srv.address
        with connect(host, port) as c:
            baseline = c.read_table(path).num_rows
            assert baseline == 3000
            with _chaos(53, **_SOAK_RATES) as ch:
                for _ in range(3):
                    assert c.read_table(path).num_rows == baseline
            h = c.health()
            assert "breakers" in h
    finally:
        srv.shutdown(1.0)
    assert not hbm.leak_records()


@pytest.mark.slow
def test_device_chaos_soak_many_seeds_thousand_faults():
    """The long soak: accumulate >=1000 injected faults across seeds;
    every run must converge bit-identically with a green strict audit
    and zero ledger leaks. Fixed seeds — failures replay exactly."""
    obs.set_hbm_obs_mode("strict")
    clean_eng = _engine()
    clean = _workload(clean_eng, "memory://dchaos-slow-clean/tbl")
    rates = dict(dispatch_error_rate=0.25, oom_rate=0.15,
                 stall_rate=0.15, recompile_rate=0.15)
    total = 0
    seed = 100
    while total < 1000:
        resilience.reset()
        # sweep the previous seed's residents (its engine is about to
        # be dropped) so each run audits only its own artifacts
        obs.reset_hbm_obs()
        eng = _engine()
        ch = _chaos(seed, **rates)
        with ch:
            digest = _workload(eng, f"memory://dchaos-slow-{seed}/tbl")
        assert digest == clean, f"seed {seed} diverged"
        assert hbm.audit()["ok"], f"seed {seed} failed the audit"
        assert not hbm.leak_records(), f"seed {seed} leaked"
        total += ch.total_faults
        seed += 1
        assert seed < 200, "fault rates too low to reach 1000 faults"
    assert total >= 1000
