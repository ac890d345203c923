"""Link-model profitability gate for the replay product path.

The replay driver has three routes — host-vectorized, single-chip
kernel, and mesh-sharded (`parallel/sharded_replay.py`) — and the right
one depends on the *link*, not the compute: the host<->device path's
bandwidth per transfer size and its round trip. This module turns a
`LinkModel` into the routing decision instead of hardcoded row counts:

- tiny segments are RTT-dominated -> host replay beats any device
  dispatch;
- mid-size segments -> single-chip kernel, with H2D transfers chunked
  to the fast-bucket size (`LinkModel.chunk_bytes`);
- large segments on a >1-device mesh -> sharded replay, where per-shard
  state residency (parallel/resident.py) amortizes the link cost across
  `Snapshot.update()` calls.

On accelerator backends the model is the `_FALLBACK_*` placeholders
below unless `DELTA_TPU_LINK_MODEL` names a measured capture; on CPU
backends (tests, dev boxes) transfers are memcpys and the model
collapses to "device always profitable" so behavior is deterministic.
Env overrides:

  DELTA_TPU_REPLAY_ROUTE       force "host" | "single" | "sharded"
  DELTA_TPU_SHARDED_MIN_ROWS   row floor for the sharded route
  DELTA_TPU_LINK_MODEL         path to a link-model json (device_merit shape)
  DELTA_TPU_LINK_H2D_BPS       flat H2D bandwidth override (bytes/s)
  DELTA_TPU_LINK_RTT_S         round-trip override (seconds)
  DELTA_TPU_H2D_CHUNK          transfer chunk size override (bytes)
  DELTA_TPU_DEVICE_PARSE       force|1|on -> device JSON parse,
                               0|off -> host (parse_route)
  DELTA_TPU_DEVICE_SKIP        force|1|on -> device data skipping,
                               0|off -> host numpy twin (skip_route)
  DELTA_TPU_DEVICE_DECODE      force|1|on -> device checkpoint page
                               decode, 0|off -> Arrow (decode_route)
  DELTA_TPU_DEVICE_SQL         force|1|on -> device SQL operators,
                               0|off -> host pandas (sql_route)
"""

from __future__ import annotations

import functools
import json
import os
from pathlib import Path
from typing import Dict, NamedTuple, Optional

from delta_tpu.obs.device import record_gate_decision
from delta_tpu.obs.registry import counter

# PLACEHOLDERS, not measured on this device: no capture of the link the
# engine runs on today exists yet (ROADMAP A2), so these shape the
# routing until one is supplied through DELTA_TPU_LINK_MODEL.
_FALLBACK_H2D = {8 << 20: 1_050_000_000.0, 64 << 20: 29_000_000.0}
_FALLBACK_RTT_S = 0.078
# replay_fa workload calibration fallbacks: host-vectorized replay rate
# and device compute rate (rows/s) when the json carries no workloads.
_FALLBACK_HOST_ROWS_S = 17e6
_FALLBACK_DEVICE_ROWS_S = 170e6

# Sharding below this many rows never pays on a single host: the host
# routing pass (stable shard argsort) costs more than the per-shard sort
# saving. Overridable; the sharded tests force it down to exercise the
# mesh on tiny logs, bench artifacts record where the real crossover is.
DEFAULT_SHARDED_MIN_ROWS = 4_000_000

# FA delta coding ships ~2 bits/row of flags plus byte-packed refs for
# the non-new minority — ~4 rows/byte is the planning estimate.
_FA_BYTES_PER_ROW = 0.25

# JSON-parse routing estimates: the host C++ field-extraction scan
# at ~270 MB/s on one vCPU, the device structural scan at ~2 GB/s —
# both placeholders, not measured on this device (the chip's own
# readings are in PERF.md; ROADMAP A1 re-prices the gate from them).
_HOST_SCAN_BPS = 270e6
_DEVICE_PARSE_BPS = 2e9

# Checkpoint page-decode routing estimates: the Arrow C++ reader
# decodes checkpoint parts at roughly 900 MB/s of raw page bytes on one
# vCPU; the one-lane device decode is planned at ~3 GB/s (a single
# dispatch whose extract/gather stages are memory-bound). As with the
# parse gate, only the crossover's order of magnitude matters.
_HOST_ARROW_BPS = 900e6
_DEVICE_DECODE_BPS = 3e9

# Data-skipping routing estimates in atom x file cells/s: the host
# numpy twin streams a few int64 compares per cell, the device kernel
# is one fused dispatch over lanes already resident in HBM (the index
# ships once per snapshot version — see stats/device_index.py — so the
# per-scan device cost is one RTT plus the compute).
_HOST_SKIP_CELLS_PS = 50e6
_DEVICE_SKIP_CELLS_PS = 5e9

# SQL operator routing estimates in rows/s, per operator class. The
# host numbers are pandas on one vCPU (merge is hash-probe bound,
# groupby is hash-agg bound, sort_values is comparison bound); the
# device numbers are the `ops/sqlops.py` kernels, whose sorts and
# segment reductions are memory-bound. As with the other gates only
# the crossover's order of magnitude matters — the dominant real-world
# term is the link (`h2d_seconds` over the operand bytes), which is
# what keeps SQL on host across a slow link and on device locally.
_HOST_SQL_ROWS_PS = {"join": 8e6, "group-agg": 20e6, "sort": 15e6}
_DEVICE_SQL_ROWS_PS = {"join": 120e6, "group-agg": 300e6, "sort": 150e6}


class LinkModel(NamedTuple):
    """Host<->device link + replay-rate model used for routing."""

    h2d_bps: dict          # {transfer_size_bytes: bytes_per_s}
    rtt_s: float
    host_rows_per_s: float
    device_rows_per_s: float

    def chunk_bytes(self) -> int:
        """Largest transfer size that still rides the fastest measured
        bandwidth bucket — the H2D chunking quantum."""
        override = os.environ.get("DELTA_TPU_H2D_CHUNK")
        if override:
            return int(override)
        if not self.h2d_bps:
            return 0
        return int(max(self.h2d_bps, key=lambda sz: self.h2d_bps[sz]))

    def h2d_seconds(self, nbytes: int) -> float:
        """Predicted H2D time for `nbytes` shipped in fast-bucket
        chunks (one RTT per dispatch, amortized bandwidth after)."""
        if nbytes <= 0 or not self.h2d_bps:
            return 0.0
        chunk = self.chunk_bytes()
        bps = self.h2d_bps.get(chunk, max(self.h2d_bps.values()))
        return self.rtt_s + nbytes / max(bps, 1.0)


_CPU_MODEL = LinkModel({}, 0.0, _FALLBACK_HOST_ROWS_S, float("inf"))


@functools.lru_cache(maxsize=1)
def link_model() -> LinkModel:
    """The active link model: the `DELTA_TPU_LINK_MODEL` capture (or the
    placeholders) on accelerator backends, the trivial (free-transfer)
    model on CPU backends."""
    import jax

    override = os.environ.get("DELTA_TPU_LINK_MODEL")
    if jax.default_backend() == "cpu" and not override:
        return _CPU_MODEL

    h2d = dict(_FALLBACK_H2D)
    rtt = _FALLBACK_RTT_S
    host_rate = _FALLBACK_HOST_ROWS_S
    dev_rate = _FALLBACK_DEVICE_ROWS_S
    if override:
        try:
            merit = json.loads(Path(override).read_text())
            link = merit.get("link", {})
            raw = link.get("h2d_bytes_per_s") or {}
            if raw:
                h2d = {int(k): float(v) for k, v in raw.items()}
            rtt = float(link.get("rtt_s", rtt))
            fa = merit.get("workloads", {}).get("replay_fa", {})
            n = float(fa.get("n", 0))
            if n and fa.get("t_host_s"):
                host_rate = n / float(fa["t_host_s"])
            if n and fa.get("t_device_compute_s"):
                dev_rate = n / float(fa["t_device_compute_s"])
        except (OSError, ValueError):
            pass  # fall back to the baked-in shape
    bps_env = os.environ.get("DELTA_TPU_LINK_H2D_BPS")
    if bps_env:
        h2d = {self_sz: float(bps_env) for self_sz in (h2d or {8 << 20: 0})}
    rtt_env = os.environ.get("DELTA_TPU_LINK_RTT_S")
    if rtt_env:
        rtt = float(rtt_env)
    return LinkModel(h2d, rtt, host_rate, dev_rate)


def reset_model_cache() -> None:
    """Drop the cached model (tests flip env knobs)."""
    link_model.cache_clear()


def sharded_min_rows() -> int:
    env = os.environ.get("DELTA_TPU_SHARDED_MIN_ROWS")
    if env:
        return int(env)
    return DEFAULT_SHARDED_MIN_ROWS


class RouteSpec(NamedTuple):
    """Declared contract surface of one gated device route."""

    env: str               # override knob the route function reads
    fallback_counter: str  # cataloged counter the fallback path bumps
    doc_anchor: str        # docs/architecture.md heading slug (prefix)
    breaker: str           # registry key of the route's circuit breaker


# The route registry: one entry per gate name passed to `_decide`.
# This is the declarative half of the 7-point route contract (host
# twin, fallback + counter, dispatch funnel, budget entry, calibration
# join, env override, capture-conditions stamp); the delta-lint
# `route-contract` pass parses it statically and cross-checks every
# claim against the code, so a new `*_route` function must register
# here — and actually honor the contract — before lint passes. Keep
# values literal: the checker reads the AST, it never imports us.
ROUTES: Dict[str, RouteSpec] = {
    "replay": RouteSpec(
        env="DELTA_TPU_REPLAY_ROUTE",
        fallback_counter="replay.resident_fallbacks",
        doc_anchor="the-profitability-gate",
        breaker="route:replay"),
    "parse": RouteSpec(
        env="DELTA_TPU_DEVICE_PARSE",
        fallback_counter="parse.device_fallbacks",
        doc_anchor="device-json-action-parse",
        breaker="route:parse"),
    "decode": RouteSpec(
        env="DELTA_TPU_DEVICE_DECODE",
        fallback_counter="decode.device_fallbacks",
        doc_anchor="device-checkpoint-page-decode",
        breaker="route:decode"),
    "skip": RouteSpec(
        env="DELTA_TPU_DEVICE_SKIP",
        fallback_counter="scan.device_fallbacks",
        doc_anchor="device-scan-planning",
        breaker="route:skip"),
    "sql": RouteSpec(
        env="DELTA_TPU_DEVICE_SQL",
        fallback_counter="sql.device_fallbacks",
        doc_anchor="device-sql-execution",
        breaker="route:sql"),
}


_ROUTE_FAILURES = counter("gate.route_failures")
_BREAKER_DEGRADES = counter("gate.route_breaker_degrades")


def _route_breaker(gate: str):
    """The circuit breaker guarding one gate's device route (lazy
    import: gate.py must stay importable without the resilience
    package loaded)."""
    from delta_tpu.resilience.breaker import route_breaker_for
    return route_breaker_for(gate)


def _breaker_admit(gate: str, chosen: str, reason: str):
    """Consult the route breaker before committing a device choice.

    Open breaker -> degrade to the host twin ("breaker-open");
    half-open -> admit the decision as the probe ("breaker-probe") —
    the executing site reports the outcome via :func:`route_ok` /
    :func:`route_failed`, and a probe whose caller never reports is
    reclaimed by the breaker after its reset window."""
    from delta_tpu.errors import CircuitOpenError
    from delta_tpu.resilience.breaker import HALF_OPEN
    b = _route_breaker(gate)
    try:
        b.before_call()
    except CircuitOpenError:
        _BREAKER_DEGRADES.inc()
        return "host", "breaker-open"
    if b.state == HALF_OPEN:
        return chosen, "breaker-probe"
    return chosen, reason


def route_ok(gate: str) -> None:
    """Report one successful device-route execution to the gate's
    breaker (closes a half-open probe, clears failure streaks)."""
    _route_breaker(gate).on_success()


def route_failed(gate: str, exc: BaseException) -> str:
    """Report one failed device-route execution; returns the
    classification verdict.

    The exception is routed through `resilience/classify.py`: transient
    verdicts count toward the breaker's trip threshold, permanent ones
    report as success (the device answered; the error is an answer —
    same contract as storage breakers)."""
    from delta_tpu.resilience.classify import TRANSIENT, classify
    verdict = classify(exc)
    _ROUTE_FAILURES.inc()
    b = _route_breaker(gate)
    if verdict == TRANSIENT:
        b.on_failure()
    else:
        b.on_success()
    return verdict


def _decide(gate: str, chosen: str, inputs: Dict[str, object],
            predicted: Optional[Dict[str, float]] = None,
            reason: str = "economics") -> str:
    """Record the decision (obs/device.py joins it with the observed
    execution cost for calibration) and return the chosen route."""
    if chosen != "host" and reason not in ("env", "forced") \
            and inputs.get("op") != "query":
        # env/forced outrank the breaker (explicit operator intent);
        # every economic device choice pays the breaker toll so a
        # poisoned route degrades to its host twin within K failures.
        # The sql "query" spine resolution is exempt: it binds no
        # execution (no route_ok/route_failed ever answers it), so
        # letting it take the half-open probe would wedge the probe
        # slot for a full reset window — the per-operator decisions
        # that follow are the ones that pay the toll.
        chosen, reason = _breaker_admit(gate, chosen, reason)
    record_gate_decision(gate, chosen, inputs, predicted or {}, reason)
    return chosen


def replay_route(
    n_rows: int,
    n_shards: int = 1,
    nbytes_est: Optional[int] = None,
    forced: Optional[str] = None,
) -> str:
    """Pick the replay route: "host", "single", or "sharded".

    `forced` carries caller intent that bypasses the economics (an
    explicitly constructed mesh keeps its sharded semantics); the
    DELTA_TPU_REPLAY_ROUTE env var outranks everything (tests, bench
    lanes). Every decision emits a gate record — inputs, per-route
    predicted seconds, chosen route, reason — for calibration against
    the observed dispatch cost (see obs/device.py)."""
    inputs = {"n_rows": n_rows, "n_shards": n_shards,
              "nbytes_est": nbytes_est}
    env_route = os.environ.get("DELTA_TPU_REPLAY_ROUTE")
    if env_route in ("host", "single", "sharded"):
        if env_route == "sharded" and n_shards <= 1:
            return _decide("replay", "single", inputs, reason="env")
        return _decide("replay", env_route, inputs, reason="env")
    if forced == "sharded" and n_shards > 1:
        return _decide("replay", "sharded", inputs, reason="forced")
    if n_rows <= 0:
        return _decide("replay", "single", inputs, reason="empty")

    model = link_model()
    if nbytes_est is None:
        nbytes_est = int(n_rows * _FA_BYTES_PER_ROW)
        inputs["nbytes_est"] = nbytes_est
    t_host = n_rows / max(model.host_rows_per_s, 1.0)
    t_device = (model.h2d_seconds(nbytes_est)
                + n_rows / model.device_rows_per_s)
    # the sharded route shares the single-chip transfer economics; its
    # per-chip compute advantage is recorded under the same prediction
    predicted = {"host": t_host, "single": t_device, "sharded": t_device}
    if t_host < t_device:
        return _decide("replay", "host", inputs, predicted)
    if n_shards > 1 and n_rows >= sharded_min_rows():
        return _decide("replay", "sharded", inputs, predicted)
    return _decide("replay", "single", inputs, predicted)


def parse_route(
    nbytes: int,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
) -> str:
    """Pick the commit-JSON parse route: "host" (C++ scanner / generic
    Arrow) or "device" (ops/json_parse.py batched field extraction).

    Unlike `replay_route`, the CPU free-transfer model does NOT flip
    this to device-always: the host C++ scanner IS the calibrated
    fast path on CPU backends, so the device route needs the engine's
    construction-time opt-in (`use_device_parse`, true on accelerator
    backends) before the link economics are even consulted.
    DELTA_TPU_DEVICE_PARSE outranks everything (tests, bench lanes)."""
    inputs = {"nbytes": nbytes, "engine_enabled": engine_enabled}
    env = os.environ.get("DELTA_TPU_DEVICE_PARSE")
    if env is not None:
        if env.lower() in ("force", "1", "on", "device"):
            return _decide("parse", "device", inputs, reason="env")
        if env.lower() in ("0", "off", "host"):
            return _decide("parse", "host", inputs, reason="env")
    if forced in ("host", "device"):
        return _decide("parse", forced, inputs, reason="forced")
    if not engine_enabled or nbytes <= 0:
        return _decide("parse", "host", inputs, reason="engine-disabled")
    model = link_model()
    t_host = nbytes / _HOST_SCAN_BPS
    t_device = model.h2d_seconds(nbytes) + nbytes / _DEVICE_PARSE_BPS
    predicted = {"host": t_host, "device": t_device}
    return _decide("parse", "device" if t_device < t_host else "host",
                   inputs, predicted)


def decode_route(
    nbytes: int,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
) -> str:
    """Pick the checkpoint page-decode route: "host" (the Arrow reader)
    or "device" (log/page_decode.py one-lane plan +
    ops/page_decode.py batched decode, one dispatch per part).

    Decided ONCE per checkpoint read over the parts' total byte size —
    the dispatch funnel then accumulates every part's observed cost
    onto the single decision. Like `parse_route`, the CPU free-transfer
    model does NOT flip this to device-always: Arrow IS the calibrated
    fast path on CPU backends, so the device route needs the engine's
    construction-time opt-in (`use_device_decode`, true on accelerator
    backends) before the link economics are consulted. Unsupported
    shapes fall back whole-part mid-flight (`obs.gate_fell_back`).
    DELTA_TPU_DEVICE_DECODE outranks everything (tests, bench lanes)."""
    inputs = {"nbytes": nbytes, "engine_enabled": engine_enabled}
    env = os.environ.get("DELTA_TPU_DEVICE_DECODE")
    if env is not None:
        if env.lower() in ("force", "1", "on", "device"):
            return _decide("decode", "device", inputs, reason="env")
        if env.lower() in ("0", "off", "host"):
            return _decide("decode", "host", inputs, reason="env")
    if forced in ("host", "device"):
        return _decide("decode", forced, inputs, reason="forced")
    if not engine_enabled or nbytes <= 0:
        return _decide("decode", "host", inputs,
                       reason="engine-disabled")
    model = link_model()
    t_host = nbytes / _HOST_ARROW_BPS
    t_device = model.h2d_seconds(nbytes) + nbytes / _DEVICE_DECODE_BPS
    predicted = {"host": t_host, "device": t_device}
    return _decide("decode", "device" if t_device < t_host else "host",
                   inputs, predicted)


def sql_route(
    op: str,
    n_rows: int,
    nbytes: int = 0,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
    probe_failed: bool = False,
) -> str:
    """Pick the route for one SQL operator: "host" (the pandas
    executor, the bit-exact parity oracle) or "device" (the
    `ops/sqlops.py` kernels behind `sqlengine/device.py::DeviceSpine`).

    `op` is the operator class ("join" | "group-agg" | "sort"; the
    per-query spine resolution uses "query" with the join economics).
    `nbytes` is the operand bytes that must cross the link for this
    operator — rows already HBM-resident via the operand cache
    (`sqlengine/operands.py`) are excluded by the caller, which is how
    a warm cache shifts the crossover toward the device. Like
    `parse_route`, the device route needs the engine's opt-in
    (`use_device_sql`, true on TpuEngine) before the economics run;
    `probe_failed` marks a broken link probe (the decision record says
    so instead of a spine silently resolving to None).
    DELTA_TPU_DEVICE_SQL outranks everything (tests, bench lanes)."""
    inputs = {"op": op, "n_rows": n_rows, "nbytes": nbytes,
              "engine_enabled": engine_enabled}
    env = os.environ.get("DELTA_TPU_DEVICE_SQL")
    if env is not None and env != "":
        if env.lower() in ("force", "1", "on", "device"):
            return _decide("sql", "device", inputs, reason="env")
        if env.lower() in ("0", "off", "host"):
            return _decide("sql", "host", inputs, reason="env")
    if probe_failed:
        return _decide("sql", "host", inputs, reason="probe-failed")
    if forced in ("host", "device"):
        return _decide("sql", forced, inputs, reason="forced")
    if not engine_enabled or n_rows <= 0:
        return _decide("sql", "host", inputs, reason="engine-disabled")
    model = link_model()
    rate_h = _HOST_SQL_ROWS_PS.get(op, _HOST_SQL_ROWS_PS["join"])
    rate_d = _DEVICE_SQL_ROWS_PS.get(op, _DEVICE_SQL_ROWS_PS["join"])
    t_host = n_rows / rate_h
    t_device = model.h2d_seconds(nbytes) + n_rows / rate_d
    predicted = {"host": t_host, "device": t_device}
    return _decide("sql", "device" if t_device < t_host else "host",
                   inputs, predicted)


def skip_route(
    n_files: int,
    n_atoms: int,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
) -> str:
    """Pick the data-skipping route for one scan plan: "host" (numpy
    twin over the encoded lanes) or "device" (ops/skipping.py batched
    kernel over the resident index).

    Like `parse_route`, the CPU free-transfer model does not flip this
    to device-always — the numpy twin is fast and allocation-free on
    CPU backends, so the device route needs the engine's
    construction-time opt-in (`use_device_skip`) before the economics
    run. The economics differ from `parse_route` in one way: the lane
    matrix is already HBM-resident (shipped once per snapshot version),
    so the device side pays one dispatch RTT, never a bulk H2D.
    DELTA_TPU_DEVICE_SKIP outranks everything (tests, bench lanes)."""
    inputs = {"n_files": n_files, "n_atoms": n_atoms,
              "engine_enabled": engine_enabled}
    env = os.environ.get("DELTA_TPU_DEVICE_SKIP")
    if env is not None:
        if env.lower() in ("force", "1", "on", "device"):
            return _decide("skip", "device", inputs, reason="env")
        if env.lower() in ("0", "off", "host"):
            return _decide("skip", "host", inputs, reason="env")
    if forced in ("host", "device"):
        return _decide("skip", forced, inputs, reason="forced")
    if not engine_enabled or n_files <= 0 or n_atoms <= 0:
        return _decide("skip", "host", inputs, reason="engine-disabled")
    model = link_model()
    cells = float(n_files) * float(n_atoms)
    t_host = cells / _HOST_SKIP_CELLS_PS
    t_device = model.rtt_s + cells / _DEVICE_SKIP_CELLS_PS
    predicted = {"host": t_host, "device": t_device}
    return _decide("skip", "device" if t_device < t_host else "host",
                   inputs, predicted)
