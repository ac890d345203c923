"""The routing gate: the one place that decides where work runs.

Five gates, one record a decision (`obs.record_gate_decision`):

- `replay`: the host twin, the single-chip kernel or the mesh-sharded
  one (`replay_route`), and which of their five implementations runs
  (`replay_kernel`: each device route has a blockwise variant for a log
  past `BLOCKWISE_MIN_ROWS` a chip);
- `parse`, `decode`, `skip`, `sql`: host or device (`parse_route`,
  `decode_route`, `skip_route`, `sql_route`). The four share one body
  (`_two_way`); each states only its inputs and its two cost terms.

A decision is, in order: the gate's override (`ROUTES[gate].env`), the
caller's `forced`, the engine's opt-in, then host seconds against device
seconds under `link_model()`: the placeholders below on an accelerator,
free transfers on a CPU backend (tests, dev boxes), where the replay
always takes the device and the two-way gates follow the opt-in alone.
An open route breaker sends an economic device choice to its host twin.

  DELTA_TPU_REPLAY_ROUTE    "host" | "single" | "sharded"
  DELTA_TPU_DEVICE_PARSE    force|1|on|device, 0|off|host (parse_route)
  DELTA_TPU_DEVICE_SKIP     the same (skip_route)
  DELTA_TPU_DEVICE_DECODE   the same (decode_route)
  DELTA_TPU_DEVICE_SQL      the same (sql_route)
"""

from __future__ import annotations

import os
from typing import Callable, Dict, NamedTuple, Optional

from delta_tpu.obs.device import record_gate_decision
from delta_tpu.obs.registry import counter

# PLACEHOLDERS, not measured on this device: PR 22's smoke read the
# link at 4.26-5.80 GB/s, and ROADMAP A1 re-prices the gate from the
# chip's own readings (PERF.md) once a cell can judge it.
_FALLBACK_H2D = {8 << 20: 1_050_000_000.0, 64 << 20: 29_000_000.0}
_FALLBACK_RTT_S = 0.078
# host-vectorized replay rate and device compute rate (rows/s)
_FALLBACK_HOST_ROWS_S = 17e6
_FALLBACK_DEVICE_ROWS_S = 170e6

# Sharding below this many rows never pays on a single host: the host
# routing pass (stable shard argsort) costs more than the per-shard sort
# saving. An engine built with a mesh of its own keeps it below the
# floor (`forced`); DELTA_TPU_REPLAY_ROUTE forces either side.
DEFAULT_SHARDED_MIN_ROWS = 4_000_000

# beyond this many file actions a chip, one-shot device replay would
# need multi-GB HBM headroom for the sort; stream blocks instead
BLOCKWISE_MIN_ROWS = 32_000_000

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
_ACCELERATOR_MODEL = LinkModel(_FALLBACK_H2D, _FALLBACK_RTT_S,
                               _FALLBACK_HOST_ROWS_S,
                               _FALLBACK_DEVICE_ROWS_S)


def link_model() -> LinkModel:
    """The active link model: the placeholders on accelerator backends,
    the trivial (free-transfer) model on CPU backends."""
    import jax

    if jax.default_backend() == "cpu":
        return _CPU_MODEL
    return _ACCELERATOR_MODEL


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
    `resilience/device_faults.py::guarded` reports the outcome via
    :func:`route_ok` / :func:`route_failed`, and a probe whose caller
    never reports is reclaimed by the breaker after its reset window."""
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
    the observed dispatch cost (see obs/device.py). Callers with an
    engine in hand ask `replay_kernel`."""
    inputs = {"n_rows": n_rows, "n_shards": n_shards,
              "nbytes_est": nbytes_est}
    env_route = os.environ.get(ROUTES["replay"].env)
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
    if n_shards > 1 and n_rows >= DEFAULT_SHARDED_MIN_ROWS:
        return _decide("replay", "sharded", inputs, predicted)
    return _decide("replay", "single", inputs, predicted)


def replay_kernel(n_rows: int, engine=None) -> str:
    """Which replay implementation runs for `n_rows` file actions on
    `engine`: "host" (the twin), "single", "single-blockwise", "sharded"
    or "sharded-blockwise".

    The one place that reads the engine's mesh for a routing decision:
    a mesh the caller built (`engine._mesh_forced`) keeps the sharded
    route whatever the size. Each call records one `replay` decision
    (`replay_route`), whose `chosen` stays host | single | sharded; a
    device route streams blocks once a chip's share of the rows reaches
    `BLOCKWISE_MIN_ROWS`. The early launches (`replay/columnar.py`,
    `ops/page_decode.py`) ask whether the answer is plain "single";
    `replay/state.py::compute_masks_device` dispatches on it."""
    mesh = getattr(engine, "mesh", None)
    n_shards = mesh.devices.size if mesh is not None else 1
    forced = ("sharded" if n_shards > 1
              and getattr(engine, "_mesh_forced", False) else None)
    route = replay_route(n_rows, n_shards=n_shards, forced=forced)
    if route == "host":
        return route
    chips = n_shards if route == "sharded" else 1
    if n_rows >= BLOCKWISE_MIN_ROWS * chips:
        return route + "-blockwise"
    return route


_DEVICE_WORDS = ("force", "1", "on", "device")
_HOST_WORDS = ("0", "off", "host")


def _two_way(gate: str, inputs: Dict[str, object], nonempty: bool,
             forced: Optional[str], host_s: float,
             device_s: Callable[[LinkModel], float],
             probe_failed: bool = False) -> str:
    """The body the host-or-device gates share. In order: the gate's
    env override (outranks everything: tests, bench lanes), a broken
    link probe (sql only), the caller's `forced`, the engine's
    construction-time opt-in (`inputs["engine_enabled"]`; the CPU
    free-transfer model must NOT flip these to device-always, the host
    side IS the calibrated fast path there) and a non-positive size,
    then the two cost terms under the link model."""
    override = (os.environ.get(ROUTES[gate].env) or "").lower()
    if override in _DEVICE_WORDS:
        return _decide(gate, "device", inputs, reason="env")
    if override in _HOST_WORDS:
        return _decide(gate, "host", inputs, reason="env")
    if probe_failed:
        return _decide(gate, "host", inputs, reason="probe-failed")
    if forced in ("host", "device"):
        return _decide(gate, forced, inputs, reason="forced")
    if not inputs["engine_enabled"] or not nonempty:
        return _decide(gate, "host", inputs, reason="engine-disabled")
    predicted = {"host": host_s, "device": device_s(link_model())}
    return _decide(
        gate,
        "device" if predicted["device"] < predicted["host"] else "host",
        inputs, predicted)


def parse_route(
    nbytes: int,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
) -> str:
    """Pick the commit-JSON parse route: "host" (C++ scanner / generic
    Arrow) or "device" (ops/json_parse.py batched field extraction).
    The opt-in is `use_device_parse` (true on accelerator backends);
    the window's bytes cross the link."""
    return _two_way(
        "parse", {"nbytes": nbytes, "engine_enabled": engine_enabled},
        nbytes > 0, forced,
        host_s=nbytes / _HOST_SCAN_BPS,
        device_s=lambda link: (link.h2d_seconds(nbytes)
                               + nbytes / _DEVICE_PARSE_BPS))


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
    onto the single decision. The opt-in is `use_device_decode`;
    unsupported shapes fall back whole-part mid-flight
    (`obs.gate_fell_back`)."""
    return _two_way(
        "decode", {"nbytes": nbytes, "engine_enabled": engine_enabled},
        nbytes > 0, forced,
        host_s=nbytes / _HOST_ARROW_BPS,
        device_s=lambda link: (link.h2d_seconds(nbytes)
                               + nbytes / _DEVICE_DECODE_BPS))


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
    a warm cache shifts the crossover toward the device. The opt-in is
    `use_device_sql` (true on TpuEngine); `probe_failed` marks a broken
    link probe (the decision record says so instead of a spine silently
    resolving to None) and outranks `forced`."""
    rate_h = _HOST_SQL_ROWS_PS.get(op, _HOST_SQL_ROWS_PS["join"])
    rate_d = _DEVICE_SQL_ROWS_PS.get(op, _DEVICE_SQL_ROWS_PS["join"])
    return _two_way(
        "sql", {"op": op, "n_rows": n_rows, "nbytes": nbytes,
                "engine_enabled": engine_enabled},
        n_rows > 0, forced,
        host_s=n_rows / rate_h,
        device_s=lambda link: link.h2d_seconds(nbytes) + n_rows / rate_d,
        probe_failed=probe_failed)


def skip_route(
    n_files: int,
    n_atoms: int,
    engine_enabled: bool = False,
    forced: Optional[str] = None,
) -> str:
    """Pick the data-skipping route for one scan plan: "host" (numpy
    twin over the encoded lanes) or "device" (ops/skipping.py batched
    kernel over the resident index). The opt-in is `use_device_skip`.
    The lane matrix is already HBM-resident (shipped once per snapshot
    version), so the device side pays one dispatch RTT, never a bulk
    H2D."""
    cells = float(n_files) * float(n_atoms)
    return _two_way(
        "skip", {"n_files": n_files, "n_atoms": n_atoms,
                 "engine_enabled": engine_enabled},
        n_files > 0 and n_atoms > 0, forced,
        host_s=cells / _HOST_SKIP_CELLS_PS,
        device_s=lambda link: link.rtt_s + cells / _DEVICE_SKIP_CELLS_PS)
