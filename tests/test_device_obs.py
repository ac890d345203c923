"""Device-execution observability (`obs.device`): the dispatch funnel,
runtime transfer-budget audit, gate calibration join, capture-conditions
stamp, and the `delta-gate` CLI round-trip.

Everything runs on CPU; the integration tests drive the real
json-parse / replay kernels through their production funnels and assert
the packaged manifest audits them byte-exactly (0 violations)."""

import functools
import json
import time

import numpy as np
import pytest

from delta_tpu import obs
from delta_tpu.obs import device as device_obs
from delta_tpu.tools import gate_cli


@pytest.fixture(autouse=True)
def _clean_device_obs():
    """Every test starts and ends with empty rings, no pending
    decisions, and the mode re-read from the (test-runner) env."""
    obs.reset_device_obs()
    yield
    obs.set_device_obs_mode(None)
    obs.reset_device_obs()


def _counter_value(name):
    return obs.counter(name).value


def _inject_budget(tmp_path, monkeypatch, entry, name="test-lane"):
    """Point DELTA_TPU_TRANSFER_BUDGET at a doctored one-entry manifest
    (the lru_cache drops so the override is read immediately)."""
    man = tmp_path / "budget.json"
    man.write_text(json.dumps({"paths": {name: entry}}))
    monkeypatch.setenv("DELTA_TPU_TRANSFER_BUDGET", str(man))
    device_obs._budget_manifest.cache_clear()
    return name


_INT32_LANE_ENTRY = {
    "unit": "row",
    "budget_bytes_per_unit": 4,
    "device_put_exhaustive": True,
    "lanes": [{"name": "vals", "kind": "dtype", "dtype": "int32"},
              {"name": "n_op", "kind": "scalar", "dtype": "int32"}],
}


# ----------------------------------------------------- disabled path --------

def test_disabled_path_is_shared_stateless_noop():
    obs.set_device_obs_mode("off")
    a = obs.device_dispatch("k.one", key=(8,), budget="whatever")
    b = obs.device_dispatch("k.two")
    assert a is b  # process-wide singleton: no per-call allocation
    arr = np.zeros(16, np.int32)
    with a as dd:
        assert dd.h2d("lane", arr) is arr  # pass-through identity
        assert dd.d2h("out", arr) is arr
        dd.set(anything=1)
    assert obs.get_dispatch_records() == []
    assert obs.gate_observation("replay", "host") is a  # same singleton
    # decisions stay counted (always-on economics counter), unrecorded
    before = _counter_value("gate.decisions")
    obs.record_gate_decision("replay", "single", {"n_rows": 4},
                             {"single": 0.001})
    assert _counter_value("gate.decisions") == before + 1
    assert obs.get_gate_records() == []


def test_disabled_dispatch_overhead_is_negligible():
    """The off-mode funnel must cost nanoseconds, not microseconds —
    it sits on per-block hot loops. The bound is a generous 5us/call so
    a loaded CI box cannot flake; nothing holds a tighter one."""
    obs.set_device_obs_mode("off")
    n = 20_000
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with obs.device_dispatch("hot.kernel", key=(1,)) as dd:
            dd.h2d("lane", 0)
    per_call_ns = (time.perf_counter_ns() - t0) / n
    assert per_call_ns < 5_000


# ------------------------------------------------ compile tracking ----------

def test_compile_tracking_first_sighting_per_key():
    obs.set_device_obs_mode("on")
    d0 = _counter_value("device.dispatches")
    c0 = _counter_value("device.compiles")
    for key in [(8,), (8,), (16,)]:
        with obs.device_dispatch("t.kernel", key=key):
            pass
    recs = obs.get_dispatch_records()
    assert [r["compile"] for r in recs] == [True, False, True]
    assert [r["distinct_keys"] for r in recs] == [1, 1, 2]
    assert all(r["wall_ns"] >= 0 and r["status"] == "ok" for r in recs)
    assert _counter_value("device.dispatches") - d0 == 3
    assert _counter_value("device.compiles") - c0 == 2


def test_recompile_storm_alarm(monkeypatch):
    monkeypatch.setenv("DELTA_TPU_RECOMPILE_ALARM", "2")
    obs.set_device_obs_mode("on")
    s0 = _counter_value("device.recompile_storms")
    for i in range(4):  # 4 distinct shape keys, alarm threshold 2
        with obs.device_dispatch("churny.kernel", key=(i,)):
            pass
    # keys 3 and 4 are each past the threshold
    assert _counter_value("device.recompile_storms") - s0 == 2


# ------------------------------------------------- budget audit -------------

def test_budget_audit_clean_when_byte_exact(tmp_path, monkeypatch):
    name = _inject_budget(tmp_path, monkeypatch, _INT32_LANE_ENTRY)
    obs.set_device_obs_mode("on")
    v0 = _counter_value("device.budget_violations")
    with obs.device_dispatch("t.kernel", budget=name, units=10) as dd:
        dd.h2d("vals", np.zeros(10, np.int32))  # 40 B == 10 * int32
        dd.h2d("n_op", np.int32(10))            # scalar lane: exempt
    [rec] = obs.get_dispatch_records()
    assert rec["violations"] == []
    assert rec["h2d_bytes"] == 44
    assert _counter_value("device.budget_violations") == v0


def test_budget_audit_catches_injected_overbudget_lane(tmp_path,
                                                       monkeypatch):
    name = _inject_budget(tmp_path, monkeypatch, _INT32_LANE_ENTRY)
    obs.set_device_obs_mode("on")
    v0 = _counter_value("device.budget_violations")
    with obs.device_dispatch("t.kernel", budget=name, units=10) as dd:
        dd.h2d("vals", np.zeros(11, np.int32))  # 44 B > budgeted 40 B
    [rec] = obs.get_dispatch_records()
    assert len(rec["violations"]) == 1
    assert "'vals'" in rec["violations"][0]
    assert "44 B > budgeted 40 B" in rec["violations"][0]
    assert _counter_value("device.budget_violations") == v0 + 1


def test_budget_audit_undeclared_lane(tmp_path, monkeypatch):
    name = _inject_budget(tmp_path, monkeypatch, _INT32_LANE_ENTRY)
    obs.set_device_obs_mode("on")
    with obs.device_dispatch("t.kernel", budget=name, units=4) as dd:
        dd.h2d("vals", np.zeros(4, np.int32))
        dd.h2d("smuggled", np.zeros(64, np.int8))
    [rec] = obs.get_dispatch_records()
    assert len(rec["violations"]) == 1
    assert "undeclared lane 'smuggled'" in rec["violations"][0]

    # a non-exhaustive entry tolerates extra lanes (the static lint
    # only pins exhaustive sites)
    obs.reset_device_obs()
    lax = dict(_INT32_LANE_ENTRY, device_put_exhaustive=False)
    name = _inject_budget(tmp_path, monkeypatch, lax)
    with obs.device_dispatch("t.kernel", budget=name, units=4) as dd:
        dd.h2d("vals", np.zeros(4, np.int32))
        dd.h2d("smuggled", np.zeros(64, np.int8))
    [rec] = obs.get_dispatch_records()
    assert rec["violations"] == []


def test_budget_audit_bitplane_and_per_lane_units(tmp_path, monkeypatch):
    entry = {
        "device_put_exhaustive": True,
        "lanes": [{"name": "plane", "kind": "bitplane"},
                  {"name": "idx", "kind": "dtype", "dtype": "int64"}],
    }
    name = _inject_budget(tmp_path, monkeypatch, entry)
    obs.set_device_obs_mode("on")
    with obs.device_dispatch("t.kernel", budget=name, units=1024) as dd:
        dd.h2d("plane", np.zeros(128, np.uint8))       # 1024 bits exactly
        dd.h2d("idx", np.zeros(3, np.int64), units=3)  # per-lane override
    [rec] = obs.get_dispatch_records()
    assert rec["violations"] == []

    obs.reset_device_obs()
    with obs.device_dispatch("t.kernel", budget=name, units=1024) as dd:
        dd.h2d("plane", np.zeros(129, np.uint8))  # one byte over
    [rec] = obs.get_dispatch_records()
    assert len(rec["violations"]) == 1
    assert "'plane'" in rec["violations"][0]


def test_budget_unknown_entry_is_a_violation(tmp_path, monkeypatch):
    _inject_budget(tmp_path, monkeypatch, _INT32_LANE_ENTRY)
    obs.set_device_obs_mode("on")
    with obs.device_dispatch("t.kernel", budget="no-such-entry",
                             units=1) as dd:
        dd.h2d("vals", np.zeros(1, np.int32))
    [rec] = obs.get_dispatch_records()
    assert "not in manifest" in rec["violations"][0]


def test_budget_strict_mode_raises(tmp_path, monkeypatch):
    name = _inject_budget(tmp_path, monkeypatch, _INT32_LANE_ENTRY)
    obs.set_device_obs_mode("strict")
    with pytest.raises(RuntimeError, match="transfer budget exceeded"):
        with obs.device_dispatch("t.kernel", budget=name, units=10) as dd:
            dd.h2d("vals", np.zeros(11, np.int32))
    # the violating dispatch is still recorded before the raise
    [rec] = obs.get_dispatch_records()
    assert rec["violations"]


# -------------------------------------------- gate calibration join ---------

def test_gate_join_computes_calibration_error():
    obs.set_device_obs_mode("on")
    obs.record_gate_decision("parse", "host", {"nbytes": 1 << 20},
                             {"host": 0.004, "device": 0.009})
    with obs.gate_observation("parse", "host"):
        time.sleep(0.002)
    obs.flush_gate_decisions()
    [rec] = obs.get_gate_records()
    assert rec["chosen"] == "host"
    assert rec["observed_routes"] == ["host"]
    assert rec["observed_s"] >= 0.002
    expected = (rec["observed_s"] - 0.004) / 0.004 * 100.0
    assert rec["calibration_error_pct"] == pytest.approx(expected)


def test_gate_fallback_accumulates_both_routes():
    """A mid-flight fallback (device parse returned None, resident
    lanes evicted) must price the TOTAL cost paid — abandoned attempt
    plus fallback route — on the one decision record."""
    obs.set_device_obs_mode("on")
    f0 = _counter_value("gate.fallbacks")
    obs.record_gate_decision("parse", "device", {"nbytes": 4096},
                             {"device": 0.001, "host": 0.002})
    with obs.gate_observation("parse", "device"):
        time.sleep(0.001)
    obs.gate_fell_back("parse", "host", reason="device-parse-unavailable")
    with obs.gate_observation("parse", "host"):
        time.sleep(0.001)
    obs.flush_gate_decisions()
    [rec] = obs.get_gate_records()
    assert rec["fell_back_to"] == "host"
    assert rec["fallback_reason"] == "device-parse-unavailable"
    assert rec["observed_routes"] == ["device", "host"]
    assert rec["observed_s"] >= 0.002  # both attempts accumulated
    assert _counter_value("gate.fallbacks") == f0 + 1


def test_dispatch_with_gate_joins_pending_decision():
    obs.set_device_obs_mode("on")
    obs.record_gate_decision("replay", "single", {"n_rows": 64},
                             {"single": 0.001})
    with obs.device_dispatch("replay.single_fa", key=(64, 1),
                             gate="replay", route="single"):
        pass
    obs.flush_gate_decisions()
    [rec] = obs.get_gate_records()
    assert rec["observed_routes"] == ["single"]
    assert rec["observed_s"] is not None
    assert rec["calibration_error_pct"] is not None


def test_next_decision_finalizes_previous_same_gate():
    obs.set_device_obs_mode("on")
    obs.record_gate_decision("skip", "device", {"n_files": 10},
                             {"device": 0.001})
    with obs.gate_observation("skip", "device"):
        pass
    # a second decision for the same gate closes the first
    obs.record_gate_decision("skip", "host", {"n_files": 2}, {})
    recs = obs.get_gate_records()
    assert len(recs) == 2
    assert recs[0]["calibration_error_pct"] is not None
    # no prediction for the chosen route -> no error, never a crash
    assert recs[1]["calibration_error_pct"] is None


def test_unjoined_and_unpredicted_decisions_have_null_error():
    obs.set_device_obs_mode("on")
    obs.record_gate_decision("replay", "single", {"n_rows": 8},
                             {"single": 0.5})  # predicted, never observed
    obs.record_gate_decision("parse", "host", {"nbytes": 8}, {},
                             reason="env-override")  # observed, no pred
    with obs.gate_observation("parse", "host"):
        pass
    for rec in obs.get_gate_records():
        assert rec["calibration_error_pct"] is None


def test_summarize_gates_medians():
    obs.set_device_obs_mode("on")
    for pred, sleep_s in [(0.001, 0.002), (0.001, 0.004)]:
        obs.record_gate_decision("parse", "host", {"nbytes": 1},
                                 {"host": pred})
        with obs.gate_observation("parse", "host"):
            time.sleep(sleep_s)
    summary = obs.summarize_gates()
    r = summary["parse"]["routes"]["host"]
    assert summary["parse"]["decisions"] == 2
    assert r["n"] == 2 and r["joined"] == 2
    assert r["median_predicted_s"] == pytest.approx(0.001)
    assert r["median_observed_s"] >= 0.002
    assert r["median_abs_err_pct"] > 0


# -------------------------------------------- capture conditions ------------

def test_capture_conditions_schema_and_fingerprint():
    cond = obs.capture_conditions(cache_state="warm")
    assert cond["schema"] == obs.CONDITIONS_SCHEMA
    assert cond["platform"]  # jax is importable in the test env
    assert cond["device_count"] >= 1
    fp = obs.conditions_fingerprint(cond)
    assert str(cond["platform"]) in fp and "warm" in fp
    # pre-schema sentinel fingerprints as itself -> its own trend group
    assert (obs.conditions_fingerprint(obs.CONDITIONS_UNKNOWN)
            == obs.CONDITIONS_UNKNOWN)
    assert obs.conditions_fingerprint(None) == "missing"
    cold = obs.capture_conditions(cache_state="cold")
    assert obs.conditions_fingerprint(cold) != fp


def test_capture_conditions_extra_overrides():
    cond = obs.capture_conditions(extra={"workload": "bench"})
    assert cond["workload"] == "bench"
    assert cond["cache_state"] == "unknown"


# ------------------------------------- gate log + delta-gate CLI ------------

def _seed_records(tmp_path):
    """One joined decision per gate + one budgeted dispatch; returns the
    gate-log path."""
    obs.set_device_obs_mode("on")
    for gate, route in [("replay", "single"), ("parse", "host"),
                        ("skip", "device")]:
        obs.record_gate_decision(gate, route, {"n_rows": 128},
                                 {route: 0.001})
        with obs.gate_observation(gate, route):
            time.sleep(0.001)
    with obs.device_dispatch("replay.single_fa", key=(128, 1),
                             gate="replay", route="single") as dd:
        dd.h2d("keys", np.zeros(128, np.uint32))
        dd.d2h("live", np.zeros(16, np.uint8))
    log = tmp_path / "gate_log.jsonl"
    n = obs.dump_gate_log(str(log))
    assert n == 4
    return log


def test_dump_gate_log_round_trips_through_cli(tmp_path, capsys):
    log = _seed_records(tmp_path)
    gates, dispatches = gate_cli.load_gate_log(str(log))
    assert {g["gate"] for g in gates} == {"replay", "parse", "skip"}
    assert all(g["calibration_error_pct"] is not None for g in gates)
    assert len(dispatches) == 1
    # internal bookkeeping keys never leak into the artifact
    assert all(not k.startswith("_") for g in gates for k in g)

    assert gate_cli.main([str(log)]) == 0
    out = capsys.readouterr().out
    for gate in ("replay", "parse", "skip"):
        assert f"gate {gate}:" in out
    assert "observed~" in out and "|err|~" in out

    assert gate_cli.main([str(log), "--dispatches"]) == 0
    out = capsys.readouterr().out
    assert "replay.single_fa" in out and "h2d=512" in out


# ------------------------------ flight recorder / chrome wiring -------------

def test_gate_events_reach_flight_recorder_and_chrome_export():
    """PR 8 wiring: gate decisions and dispatches ride the active
    request span, so the flight recorder and the Chrome exporter see
    them with zero extra plumbing."""
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    rec = obs.FlightRecorder()
    obs.add_exporter(rec)
    try:
        obs.set_device_obs_mode("on")
        with obs.span("snapshot.load", table="t"):
            obs.record_gate_decision("replay", "single", {"n_rows": 8},
                                     {"single": 0.001})
            with obs.device_dispatch("replay.single_fa", key=(8, 1),
                                     gate="replay", route="single"):
                pass
        [trace_id] = rec.trace_ids()
        spans = rec.get(trace_id)
        events = [e for s in spans for e in (s.get("events") or [])]
        names = [e["name"] for e in events]
        assert "gate.decision" in names and "device.dispatch" in names
        decision = next(e for e in events if e["name"] == "gate.decision")
        assert decision["attrs"]["route"] == "single"
        assert decision["attrs"]["predicted_single_ms"] == 1.0

        doc = obs.chrome_trace(obs.get_finished_spans())
        instants = [ev for ev in doc["traceEvents"] if ev.get("ph") == "i"]
        assert {"gate.decision", "device.dispatch"} <= {
            ev["name"] for ev in instants}
    finally:
        obs.remove_exporter(rec)
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()


# ------------------------------------------ real-kernel integration ---------

_dumps = functools.partial(json.dumps, separators=(",", ":"))


def _commit_buffer():
    """(buf, starts, versions) exactly as replay's `_read_commits_buffer`
    shapes them (mirrors tests/test_device_parse.py)."""
    commits = [
        [_dumps({"add": {"path": f"f{i}.parquet", "partitionValues": {},
                         "size": 10 + i, "modificationTime": 100 + i,
                         "dataChange": True}})]
        for i in range(4)
    ] + [[_dumps({"remove": {"path": "f0.parquet", "dataChange": True,
                             "deletionTimestamp": 999}})]]
    blobs = [("\n".join(lines) + "\n").encode() for lines in commits]
    starts = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=starts[1:])
    return b"".join(blobs), starts, np.arange(len(blobs), dtype=np.int64)


def test_parse_hot_path_audits_byte_exact():
    """The production json-parse funnel against the PACKAGED manifest:
    a clean run records dispatches and exactly zero violations."""
    from delta_tpu.replay.device_parse import parse_commits_device

    obs.set_device_obs_mode("strict")  # any over-budget byte would raise
    v0 = _counter_value("device.budget_violations")
    buf, starts, versions = _commit_buffer()
    out = parse_commits_device(buf, starts, versions)
    assert out is not None
    recs = [r for r in obs.get_dispatch_records()
            if r["kernel"] == "json_parse.window"]
    assert recs, "device parse ran but recorded no dispatch"
    for r in recs:
        assert r["violations"] == []
        assert r["budget"] == "json-parse-window"
        assert r["h2d_bytes"] > 0 and r["d2h_bytes"] > 0
    assert _counter_value("device.budget_violations") == v0


def test_replay_hot_path_audits_byte_exact():
    """replay_select through its production funnel under strict mode:
    dispatch recorded, zero violations, gate join lands."""
    from delta_tpu.ops.replay import replay_select

    obs.set_device_obs_mode("strict")
    obs.record_gate_decision("replay", "single", {"n_rows": 6},
                             {"single": 0.001})
    pk = np.array([0, 1, 2, 0, 1, 2], np.uint32)
    dk = np.zeros(6, np.uint32)
    version = np.array([0, 0, 0, 1, 1, 1], np.int64)
    order = np.arange(6, dtype=np.int64)
    is_add = np.array([1, 1, 1, 1, 0, 1], bool)
    live, tomb = replay_select([pk, dk], version, order, is_add)
    assert live.sum() + tomb.sum() == 3  # one winner per key
    recs = [r for r in obs.get_dispatch_records()
            if r["kernel"].startswith("replay.single")]
    assert recs and all(r["violations"] == [] for r in recs)
    [gate_rec] = obs.get_gate_records()
    assert gate_rec["observed_s"] is not None
    assert gate_rec["observed_routes"] == ["single"]
