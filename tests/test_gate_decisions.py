"""The decision table of `parallel/gate.py`: for each gate, what it
chooses and why, under the CPU model (a transfer is free) and under the
accelerator model (the `_FALLBACK_*` placeholders, plain numbers, so no
chip is needed to price a route).

The crossovers pinned under the accelerator model are the ones the
benchmark's cells stand on: a replay goes to the chip from 1.48M rows
and to the mesh from 4M, a commit parse goes to the chip above 34.65 MB,
a two-atom plan from 1.97M files, a page decode never. Whoever re-prices
the gate edits this table with it."""

import types

import numpy as np
import pyarrow as pa
import pytest

import delta_tpu.api as dta
from delta_tpu import Table, obs
from delta_tpu.engine.tpu import TpuEngine
from delta_tpu.parallel import gate
from delta_tpu.replay.columnar import clear_parse_cache
from delta_tpu.resilience.device_chaos import DeviceChaosError

CHIP = gate.LinkModel(dict(gate._FALLBACK_H2D), gate._FALLBACK_RTT_S,
                      gate._FALLBACK_HOST_ROWS_S,
                      gate._FALLBACK_DEVICE_ROWS_S)
MODELS = {"cpu": gate._CPU_MODEL, "chip": CHIP}

ROUTE_FN = {"replay": gate.replay_route, "parse": gate.parse_route,
            "decode": gate.decode_route, "skip": gate.skip_route,
            "sql": gate.sql_route}
INPUT_KEYS = {
    "replay": {"n_rows", "n_shards", "nbytes_est"},
    "parse": {"nbytes", "engine_enabled"},
    "decode": {"nbytes", "engine_enabled"},
    "skip": {"n_files", "n_atoms", "engine_enabled"},
    "sql": {"op", "n_rows", "nbytes", "engine_enabled"},
}
PREDICTED_KEYS = {g: {"host", "device"} for g in INPUT_KEYS}
PREDICTED_KEYS["replay"] = {"host", "single", "sharded"}
PRICED = ("economics", "breaker-open", "breaker-probe")
TWO_WAY = ("parse", "decode", "skip", "sql")
# one call per two-way gate that the economics send to the device under
# the CPU model: what an override, `forced` or a breaker has to outrank
PROFITABLE = {
    "parse": dict(nbytes=1 << 30, engine_enabled=True),
    "decode": dict(nbytes=1 << 30, engine_enabled=True),
    "skip": dict(n_files=10_000, n_atoms=8, engine_enabled=True),
    "sql": dict(op="group-agg", n_rows=200_000, nbytes=1_600_000,
                engine_enabled=True),
}
MB = 1_000_000


def _off(call):
    return {**call, "engine_enabled": False}


def _sql(op, n_rows, nbytes=0, **kw):
    return dict(op=op, n_rows=n_rows, nbytes=nbytes, engine_enabled=True,
                **kw)


# (model, gate, call, override, chosen, reason)
TABLE = [
    # ---- the CPU model: a transfer is free, the opt-in decides
    ("cpu", "replay", dict(n_rows=40), None, "single", "economics"),
    ("cpu", "replay", dict(n_rows=0), None, "single", "empty"),
    ("cpu", "replay", dict(n_rows=3_999_999, n_shards=4), None,
     "single", "economics"),
    ("cpu", "replay", dict(n_rows=4_000_000, n_shards=4), None,
     "sharded", "economics"),
    ("cpu", "replay", dict(n_rows=4_000_000, n_shards=1), None,
     "single", "economics"),
    ("cpu", "replay", dict(n_rows=100, n_shards=4, forced="sharded"), None,
     "sharded", "forced"),
    ("cpu", "replay", dict(n_rows=100, n_shards=1, forced="sharded"), None,
     "single", "economics"),
    ("cpu", "replay", dict(n_rows=0, n_shards=4, forced="sharded"), None,
     "sharded", "forced"),
    *[("cpu", g, _off(PROFITABLE[g]), None, "host", "engine-disabled")
      for g in TWO_WAY],
    *[("cpu", g, PROFITABLE[g], None, "device", "economics")
      for g in TWO_WAY],
    *[("cpu", g, {**_off(PROFITABLE[g]), "forced": "device"}, None,
       "device", "forced") for g in TWO_WAY],
    *[("cpu", g, {**PROFITABLE[g], "forced": "host"}, None,
       "host", "forced") for g in TWO_WAY],
    # a non-positive size stands down as "engine-disabled"
    ("cpu", "parse", dict(nbytes=0, engine_enabled=True), None,
     "host", "engine-disabled"),
    ("cpu", "decode", dict(nbytes=0, engine_enabled=True), None,
     "host", "engine-disabled"),
    ("cpu", "skip", dict(n_files=0, n_atoms=8, engine_enabled=True), None,
     "host", "engine-disabled"),
    ("cpu", "skip", dict(n_files=10_000, n_atoms=0, engine_enabled=True),
     None, "host", "engine-disabled"),
    ("cpu", "sql", _sql("join", 0), None, "host", "engine-disabled"),
    # sql: a failed link probe outranks `forced`, an override outranks it
    ("cpu", "sql", _sql("join", 1000, probe_failed=True), None,
     "host", "probe-failed"),
    ("cpu", "sql", _sql("join", 1000, probe_failed=True, forced="device"),
     None, "host", "probe-failed"),
    ("cpu", "sql", _sql("join", 1000, probe_failed=True), "force",
     "device", "env"),
    # ---- the accelerator model: the crossovers the cells stand on
    ("chip", "replay", dict(n_rows=1_470_000), None, "host", "economics"),
    ("chip", "replay", dict(n_rows=1_490_000), None, "single", "economics"),
    ("chip", "replay", dict(n_rows=1_490_000, n_shards=4), None,
     "single", "economics"),
    ("chip", "replay", dict(n_rows=4_000_000, n_shards=4), None,
     "sharded", "economics"),
    ("chip", "replay", dict(n_rows=4_000_000, n_shards=1), None,
     "single", "economics"),
    ("chip", "replay", dict(n_rows=1000, n_shards=4, forced="sharded"),
     None, "sharded", "forced"),
    ("chip", "replay", dict(n_rows=1000, n_shards=4), None,
     "host", "economics"),
    ("chip", "replay", dict(n_rows=1_470_000, nbytes_est=0), None,
     "single", "economics"),
    ("chip", "parse", dict(nbytes=34 * MB, engine_enabled=True), None,
     "host", "economics"),
    ("chip", "parse", dict(nbytes=35 * MB, engine_enabled=True), None,
     "device", "economics"),
    ("chip", "skip", dict(n_files=1_900_000, n_atoms=2,
                          engine_enabled=True), None, "host", "economics"),
    ("chip", "skip", dict(n_files=2_000_000, n_atoms=2,
                          engine_enabled=True), None, "device", "economics"),
    *[("chip", "decode", dict(nbytes=n, engine_enabled=True), None,
       "host", "economics") for n in (64 * MB, 1000 * MB, 16_000 * MB)],
    *[("chip", "sql", _sql(op, n, nbytes=8 * n), None, chosen, "economics")
      for op, n, chosen in (("join", 700_000, "host"),
                            ("join", 730_000, "device"),
                            ("group-agg", 1_950_000, "host"),
                            ("group-agg", 2_050_000, "device"),
                            ("sort", 1_450_000, "host"),
                            ("sort", 1_530_000, "device"),
                            # the spine's resolution is priced as a join
                            ("query", 700_000, "host"),
                            ("query", 730_000, "device"))],
    # operands already on the chip cross no link and pay no round trip
    ("chip", "sql", _sql("join", 1000, nbytes=0), None,
     "device", "economics"),
    ("chip", "sql", _sql("join", 1000, nbytes=8000), None,
     "host", "economics"),
    # ---- every spelling of every override
    *[("chip", g, _off(PROFITABLE[g]), word, "device", "env")
      for g in TWO_WAY for word in ("force", "1", "on", "device", "FORCE")],
    *[("cpu", g, {**PROFITABLE[g], "forced": "device"}, word, "host", "env")
      for g in TWO_WAY for word in ("0", "off", "host", "Off")],
    # a word that is no spelling, or none at all, is no override
    *[("cpu", g, PROFITABLE[g], word, "device", "economics")
      for g in TWO_WAY for word in ("", "maybe")],
    ("chip", "replay", dict(n_rows=10_000_000, n_shards=4), "host",
     "host", "env"),
    ("chip", "replay", dict(n_rows=10, n_shards=4), "single",
     "single", "env"),
    ("chip", "replay", dict(n_rows=10, n_shards=4), "sharded",
     "sharded", "env"),
    ("chip", "replay", dict(n_rows=10, n_shards=1), "sharded",
     "single", "env"),
    ("chip", "replay", dict(n_rows=10, n_shards=4, forced="sharded"),
     "host", "host", "env"),
    ("chip", "replay", dict(n_rows=10), "device", "host", "economics"),
]


def _case_id(case):
    model, name, call, override, chosen, reason = case
    args = ",".join(f"{k}={v}" for k, v in call.items()
                    if k != "engine_enabled")
    env = "" if override is None else f"|env={override!r}"
    return f"{model}:{name}({args}){env}->{chosen}/{reason}"


@pytest.fixture(autouse=True)
def _gate_records(monkeypatch):
    for spec in gate.ROUTES.values():
        monkeypatch.delenv(spec.env, raising=False)
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    yield
    obs.set_device_obs_mode(None)
    obs.reset_device_obs()


def _decide(monkeypatch, model, name, call, override=None):
    """One decision: the answer and the one record it left."""
    monkeypatch.setattr(gate, "link_model", lambda: MODELS[model])
    if override is not None:
        monkeypatch.setenv(gate.ROUTES[name].env, override)
    before = len(obs.get_gate_records())
    answer = ROUTE_FN[name](**call)
    records = obs.get_gate_records()[before:]
    assert len(records) == 1
    return answer, records[0]


@pytest.mark.parametrize("case", TABLE, ids=_case_id)
def test_decision(monkeypatch, case):
    model, name, call, override, chosen, reason = case
    answer, rec = _decide(monkeypatch, model, name, call, override)
    assert (answer, rec["gate"], rec["chosen"], rec["reason"]) == \
        (chosen, name, chosen, reason)
    assert set(rec["inputs"]) == INPUT_KEYS[name]
    assert set(rec["predicted_s"]) == (
        PREDICTED_KEYS[name] if reason in PRICED else set())


def test_replay_estimates_the_bytes_it_is_not_given(monkeypatch):
    _, rec = _decide(monkeypatch, "chip", "replay", dict(n_rows=4_000_000))
    assert rec["inputs"]["nbytes_est"] == 1_000_000
    _, rec = _decide(monkeypatch, "chip", "replay",
                     dict(n_rows=4_000_000, nbytes_est=7))
    assert rec["inputs"]["nbytes_est"] == 7


def test_the_crossovers_are_where_the_cells_files_say(monkeypatch):
    """`chipbench/configs/*.json` (`assumed.route`) cite these to three
    digits; the predictions of a record are the two sides."""
    def margin(name, **call):
        _, rec = _decide(monkeypatch, "chip", name, call)
        p = rec["predicted_s"]
        return p["host"] - p.get("device", p.get("single"))

    assert margin("replay", n_rows=1_479_000) < 0 < \
        margin("replay", n_rows=1_481_000)
    assert margin("parse", nbytes=34_640_000, engine_enabled=True) < 0 < \
        margin("parse", nbytes=34_660_000, engine_enabled=True)
    assert margin("skip", n_files=1_969_000, n_atoms=2,
                  engine_enabled=True) < 0 < \
        margin("skip", n_files=1_971_000, n_atoms=2, engine_enabled=True)


# ------------------------------------------------------------ the breaker


def _trip(name):
    for _ in range(2):
        gate.route_failed(name, DeviceChaosError("injected"))


@pytest.fixture
def _breaker_at_two(monkeypatch):
    from delta_tpu import resilience

    monkeypatch.setenv("DELTA_TPU_ROUTE_BREAKER_THRESHOLD", "2")
    resilience.reset()
    yield
    resilience.reset()


@pytest.mark.parametrize("name", TWO_WAY)
def test_an_open_breaker_sends_the_economics_to_the_host(
        monkeypatch, _breaker_at_two, name):
    _trip(name)
    answer, rec = _decide(monkeypatch, "cpu", name, PROFITABLE[name])
    assert (answer, rec["reason"]) == ("host", "breaker-open")
    assert set(rec["predicted_s"]) == {"host", "device"}
    # operator intent outranks it
    answer, rec = _decide(monkeypatch, "cpu", name,
                          {**PROFITABLE[name], "forced": "device"})
    assert (answer, rec["reason"]) == ("device", "forced")
    answer, rec = _decide(monkeypatch, "cpu", name, PROFITABLE[name],
                          override="force")
    assert (answer, rec["reason"]) == ("device", "env")


def test_an_open_breaker_sends_a_replay_to_the_host(
        monkeypatch, _breaker_at_two):
    _trip("replay")
    answer, rec = _decide(monkeypatch, "cpu", "replay", dict(n_rows=40))
    assert (answer, rec["reason"]) == ("host", "breaker-open")


def test_the_spines_resolution_pays_no_breaker_toll(
        monkeypatch, _breaker_at_two):
    _trip("sql")
    answer, rec = _decide(monkeypatch, "cpu", "sql", _sql("query", 1))
    assert (answer, rec["reason"]) == ("device", "economics")
    answer, rec = _decide(monkeypatch, "cpu", "sql", _sql("join", 1000))
    assert (answer, rec["reason"]) == ("host", "breaker-open")


# ------------------------------------------------------- the replay kernel


def _engine(n_shards, forced=False):
    mesh = None
    if n_shards > 1:
        mesh = types.SimpleNamespace(
            devices=types.SimpleNamespace(size=n_shards))
    return types.SimpleNamespace(mesh=mesh, _mesh_forced=forced)


B = 32_000_000   # BLOCKWISE_MIN_ROWS

KERNELS = [
    # (model, rows, shards, forced, override, kernel, chosen)
    ("cpu", 40, 1, False, None, "single", "single"),
    ("cpu", B - 1, 1, False, None, "single", "single"),
    ("cpu", B, 1, False, None, "single-blockwise", "single"),
    ("cpu", 3_999_999, 4, False, None, "single", "single"),
    ("cpu", 4_000_000, 4, False, None, "sharded", "sharded"),
    ("cpu", 4 * B - 1, 4, False, None, "sharded", "sharded"),
    ("cpu", 4 * B, 4, False, None, "sharded-blockwise", "sharded"),
    ("cpu", 40, 4, True, None, "sharded", "sharded"),
    ("cpu", 40, 4, False, "sharded", "sharded", "sharded"),
    ("cpu", B, 4, False, "single", "single-blockwise", "single"),
    ("cpu", 4 * B, 4, False, "host", "host", "host"),
    ("chip", 1_470_000, 1, False, None, "host", "host"),
    ("chip", 1_490_000, 1, False, None, "single", "single"),
    ("chip", 1_470_000, 4, False, None, "host", "host"),
    ("chip", 1_470_000, 4, True, None, "sharded", "sharded"),
    ("chip", 6_000_000, 4, False, None, "sharded", "sharded"),
    ("chip", B, 1, False, None, "single-blockwise", "single"),
]


@pytest.mark.parametrize(
    "model,rows,shards,forced,override,kernel,chosen", KERNELS,
    ids=[f"{c[0]}:{c[1]}x{c[2]}{'F' if c[3] else ''}|{c[4]}->{c[5]}"
         for c in KERNELS])
def test_replay_kernel(monkeypatch, model, rows, shards, forced, override,
                       kernel, chosen):
    """One function says which of the five implementations runs, and
    leaves the one `replay` record its caller is counted by."""
    assert gate.BLOCKWISE_MIN_ROWS == B
    monkeypatch.setattr(gate, "link_model", lambda: MODELS[model])
    if override is not None:
        monkeypatch.setenv("DELTA_TPU_REPLAY_ROUTE", override)
    assert gate.replay_kernel(rows, _engine(shards, forced)) == kernel
    [rec] = obs.get_gate_records()
    assert (rec["gate"], rec["chosen"]) == ("replay", chosen)
    assert rec["inputs"]["n_rows"] == rows
    assert rec["inputs"]["n_shards"] == shards


def test_replay_kernel_without_an_engine(monkeypatch):
    assert gate.replay_kernel(40) == "single"
    assert gate.replay_kernel(40, object()) == "single"
    assert [r["inputs"]["n_shards"] for r in obs.get_gate_records()] == [1, 1]


# ------------------------------------------- one replay record for a load


def _commit(path, i):
    rows = pa.table({"id": pa.array(np.arange(i * 100, i * 100 + 100),
                                    pa.int64())})
    dta.write_table(path, rows, mode="append" if i else "error",
                    target_rows_per_file=10)


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    json_only = str(tmp_path_factory.mktemp("gate_loads") / "json")
    checkpointed = str(tmp_path_factory.mktemp("gate_loads") / "ckpt")
    for i in range(4):
        _commit(json_only, i)
        _commit(checkpointed, i)
    Table.for_path(checkpointed, TpuEngine()).checkpoint()
    _commit(checkpointed, 4)
    return {"json": json_only, "checkpoint": checkpointed}


@pytest.mark.parametrize("source,early_launch", [("json", True),
                                                 ("checkpoint", False)])
def test_a_load_leaves_one_replay_record(tables, source, early_launch):
    """`device_route_pct` and `mesh_sharded_pct` count `replay` records
    by `chosen`. A load of commits alone launches its replay from the
    scanner's key lanes, ahead of `compute_masks_device` (no
    `replay.keys` span); a load from a checkpoint asks there. Each asks
    once."""
    clear_parse_cache()
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        snap = Table.for_path(tables[source], TpuEngine()).latest_snapshot()
        n_files = snap.num_files
        spans = {s.to_dict()["name"] for s in obs.get_finished_spans()}
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert n_files == (40 if source == "json" else 50)
    assert ("replay.keys" not in spans) == early_launch
    replays = [r for r in obs.get_gate_records() if r["gate"] == "replay"]
    assert [(r["chosen"], r["reason"]) for r in replays] == \
        [("single", "economics")]
    assert replays[0]["inputs"]["n_rows"] == n_files
