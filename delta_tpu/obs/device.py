"""Device-execution observability: the `device_dispatch()` funnel.

PRs 7/11/12/14 moved the replay/checkpoint/parse/skipping hot paths
onto XLA kernels routed by `parallel/gate.py` cost models — and none of
that execution layer was observable: the telemetry plane (PR 8) stops
at the request level, the transfer-budget lint (PR 9) proves what
*should* cross the link, and nothing records what *did*. This module is
the runtime half of both:

- **Dispatch profiler** — every jit/shard_map launch in `ops/` and
  `parallel/` runs inside ``with device_dispatch(name, key=...) as dd``,
  recording per-kernel wall time, whether this launch compiled (first
  sighting of a shape-bucket `key`) or ran steady-state, and actual
  H2D/D2H bytes per named lane (``dd.h2d("lane_bytes", arr, units=n)``).
  What the compiler itself reports joins the record: while device obs
  is on, JAX's monitoring listeners put on the dispatch open on the
  compiling thread each program's `fun_name`, its backend seconds
  (`compile_s`) and whether the persistent cache answered, and the same
  interval lands as a finished `device.compile` span. Where a launch is
  asynchronous, the later blocking read joins through ``dd.wait()``
  (`wait_ns`). Recompile storms from shape churn become a counted,
  alarmable event (`device.recompile_storms`) instead of a silent bench
  mystery.
- **Runtime transfer-budget audit** — observed lane bytes are
  reconciled against `resources/transfer_budget.json` at dispatch exit:
  each recorded lane must match its manifest declaration byte-exactly
  (dtype lanes at ``units * itemsize``, bitplanes at ``units / 8`` —
  exact because `pad_bucket` sizes are multiples of 8; scalars are
  excluded, and undeclared lanes are violations only for
  ``device_put_exhaustive`` entries). Overruns bump
  `device.budget_violations`; ``strict`` mode raises.
- **Gate calibration** — every `parallel/gate.py` route decision emits
  a structured record (inputs, predicted per-route cost, chosen route,
  reason) which later observations join: device routes
  join automatically at `device_dispatch` exit, host routes through
  ``gate_observation(gate, "host")``, and mid-flight fallbacks are
  marked by ``gate_fell_back()`` with the fallback cost accumulated
  onto the same record. The per-decision relative error between
  observed and predicted-for-the-chosen-route lands in the
  `gate.calibration_error` histogram and the `delta-gate` CLI.

Gating mirrors `trace.py`: ``DELTA_TPU_DEVICE_OBS=off|on|strict``
(default off). The disabled path is a true no-op — `device_dispatch()`
returns a process-wide stateless singleton: no allocation, no clock
read, no counter touch (the lone exception is `gate.decisions`, an
always-on counter bumped per routing decision, orders of magnitude
colder than the dispatch path). ``strict`` is ``on`` plus raise-on-
budget-violation, for tests and canary lanes.

The audit intentionally leaves the `jax.device_put` calls at the sites
untouched — the static transfer-budget lint keys on them, and this
module only *observes* around them.
"""

from __future__ import annotations

import collections
import contextvars
import functools
import json
import logging
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from delta_tpu.obs import trace as _trace
from delta_tpu.obs.registry import counter, histogram

_log = logging.getLogger(__name__)

MODE_OFF = 0
MODE_ON = 1
MODE_STRICT = 2

_MODES = {"off": MODE_OFF, "on": MODE_ON, "strict": MODE_STRICT,
          "0": MODE_OFF, "1": MODE_ON, "2": MODE_STRICT}


def _mode_from_env() -> int:
    raw = os.environ.get("DELTA_TPU_DEVICE_OBS", "off").strip().lower()
    mode = _MODES.get(raw)
    if mode is None:
        _log.warning("unknown DELTA_TPU_DEVICE_OBS=%r; device obs stays off",
                     raw)
        return MODE_OFF
    return mode


_mode: int = _mode_from_env()


def device_obs_mode() -> int:
    return _mode


def device_obs_enabled() -> bool:
    return _mode != MODE_OFF


def set_device_obs_mode(mode: Optional[str]) -> None:
    """Programmatically set the device-obs mode ('off'|'on'|'strict');
    None re-reads `DELTA_TPU_DEVICE_OBS`. Tests and bench use this;
    production uses the env var."""
    global _mode
    if mode is None:
        _mode = _mode_from_env()
    else:
        try:
            _mode = _MODES[mode.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown device obs mode {mode!r}; expected off|on|strict"
            ) from None
    _sync_compile_listeners()


# -- instruments (resolved once; see resources/metric_names.json) ------------

_DISPATCHES = counter("device.dispatches")
_COMPILES = counter("device.compiles")
_RECOMPILE_STORMS = counter("device.recompile_storms")
_H2D = counter("device.h2d_bytes")
_D2H = counter("device.d2h_bytes")
_VIOLATIONS = counter("device.budget_violations")
_DECISIONS = counter("gate.decisions")
_FALLBACKS = counter("gate.fallbacks")
_DISPATCH_NS = histogram("device.dispatch_ns")
_CALIB_ERR = histogram("gate.calibration_error")


# -- budget manifest ---------------------------------------------------------

# dtype byte widths the manifest may commit to (keep in sync with the
# static pass — both sides must price a lane identically)
_DTYPE_BYTES = {
    "int8": 1, "uint8": 1, "bool": 1,
    "int16": 2, "uint16": 2, "float16": 2, "bfloat16": 2,
    "int32": 4, "uint32": 4, "float32": 4,
    "int64": 8, "uint64": 8, "float64": 8,
}


@functools.lru_cache(maxsize=1)
def _budget_manifest() -> Dict[str, dict]:
    """``paths`` table of the committed transfer-budget manifest.
    `DELTA_TPU_TRANSFER_BUDGET` overrides the packaged resource (tests
    inject doctored manifests through it); unreadable manifests degrade
    to an empty table — the audit then flags every budgeted dispatch as
    unknown-entry rather than crashing the hot path."""
    path = os.environ.get("DELTA_TPU_TRANSFER_BUDGET")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                            "resources", "transfer_budget.json")
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        paths = data.get("paths", {})
        return paths if isinstance(paths, dict) else {}
    except (OSError, ValueError) as e:
        _log.warning("transfer-budget manifest unreadable (%s): %s", path, e)
        return {}


def _lane_expected_bytes(decl: dict, units: Optional[int]) -> Optional[int]:
    """Byte-exact expectation for one declared lane at `units` units, or
    None when the lane is exempt (scalar) or unpriceable (no units,
    unknown dtype)."""
    kind = decl.get("kind")
    if kind == "scalar" or units is None:
        return None
    if kind == "bitplane":
        # packbits emits whole bytes; pad_bucket unit counts are
        # multiples of 8 so this is exact, and the ceil covers fixture
        # lanes that are not bucket-padded
        return (int(units) + 7) // 8
    itemsize = _DTYPE_BYTES.get(decl.get("dtype", ""))
    if itemsize is None:
        return None
    return int(units) * itemsize


# -- record rings ------------------------------------------------------------

_RING_MAX = int(os.environ.get("DELTA_TPU_DEVICE_OBS_RING", 8192))
_dispatch_ring: collections.deque = collections.deque(maxlen=_RING_MAX)
_gate_ring: collections.deque = collections.deque(maxlen=_RING_MAX)

# first-sighting shape keys per kernel name: a dispatch whose key has
# not been seen is a compile; a kernel accumulating more distinct keys
# than the alarm threshold is a recompile storm (shape churn defeating
# pad_bucket)
_seen_lock = threading.Lock()
_seen_keys: Dict[str, set] = {}


def _storm_threshold() -> int:
    try:
        return int(os.environ.get("DELTA_TPU_RECOMPILE_ALARM", 8))
    except ValueError:
        return 8


# the calling context's pending (not yet finalized) gate decisions,
# keyed by gate name. Same-thread by construction: every route function
# is called on the thread that then executes the routed work, so the
# contextvar joins decision -> observation without any cross-thread
# hand-off.
_PENDING: contextvars.ContextVar[Optional[Dict[str, dict]]] = (
    contextvars.ContextVar("delta_tpu_pending_gates", default=None))


# -- gate decision records ---------------------------------------------------


def record_gate_decision(gate: str, chosen: str, inputs: Dict[str, object],
                         predicted: Dict[str, float],
                         reason: str = "economics") -> None:
    """Record one routing decision: `predicted` maps route name to the
    model's predicted seconds (empty when the decision bypassed the
    economics — env override, forced caller intent, empty input). The
    record stays pending until observations join it; a later decision
    for the same gate finalizes it."""
    _DECISIONS.inc()
    if _mode == MODE_OFF:
        return
    rec = {
        "type": "gate_decision",
        "gate": gate,
        "chosen": chosen,
        "reason": reason,
        "inputs": dict(inputs),
        "predicted_s": {k: float(v) for k, v in predicted.items()},
        "ts_unix_ns": time.time_ns(),
        "observed_s": None,
        "observed_routes": [],
        "fell_back_to": None,
        "calibration_error_pct": None,
    }
    pend = dict(_PENDING.get() or {})
    prev = pend.get(gate)
    if prev is not None:
        _finalize_decision(prev)
    pend[gate] = rec
    _PENDING.set(pend)
    _gate_ring.append(rec)
    # ride the active request span (flight recorder + Chrome export pick
    # events up from there): the trace answers "which route did this
    # dispatch take, and why"
    _trace.add_event("gate.decision", gate=gate, route=chosen, reason=reason,
                     **{f"predicted_{k}_ms": round(v * 1e3, 4)
                        for k, v in rec["predicted_s"].items()})


def gate_fell_back(gate: str, to_route: str, reason: str = "") -> None:
    """Mark the pending decision for `gate` as having fallen back
    mid-flight (device parse returned None, resident lanes evicted,
    ...): the fallback route's cost joins the same record, so the
    calibration error prices the total cost actually paid."""
    _FALLBACKS.inc()
    if _mode == MODE_OFF:
        return
    rec = (_PENDING.get() or {}).get(gate)
    if rec is not None:
        rec["fell_back_to"] = to_route
        if reason:
            rec["fallback_reason"] = reason
    _trace.add_event("gate.fallback", gate=gate, to_route=to_route,
                     reason=reason)


def _observe_gate(gate: str, route: str, seconds: float) -> None:
    """Accumulate one observed execution onto the pending decision for
    `gate` (a fallen-back decision accumulates both the abandoned
    attempt and the fallback route)."""
    rec = (_PENDING.get() or {}).get(gate)
    if rec is None:
        return
    rec["observed_s"] = (rec["observed_s"] or 0.0) + float(seconds)
    rec["observed_routes"].append(route)


def _finalize_decision(rec: dict) -> None:
    """Compute the calibration error for a decision whose observations
    are complete. Signed error is kept on the record; the histogram gets
    the absolute percentage (its export buckets are positive)."""
    if rec.get("_final"):
        return
    rec["_final"] = True
    obs_s = rec.get("observed_s")
    pred = rec.get("predicted_s") or {}
    pred_chosen = pred.get(rec.get("chosen"))
    if obs_s is None or not pred_chosen or pred_chosen <= 0:
        return
    err_pct = (obs_s - pred_chosen) / pred_chosen * 100.0
    rec["calibration_error_pct"] = err_pct
    _CALIB_ERR.observe(abs(err_pct))


def flush_gate_decisions() -> None:
    """Finalize every pending decision in the calling context (bench /
    CLI / test boundary — after this, calibration errors are computed
    and the histogram is settled)."""
    pend = _PENDING.get() or {}
    for rec in pend.values():
        _finalize_decision(rec)
    _PENDING.set({})


def get_gate_records() -> List[dict]:
    """Finalized gate-decision records, oldest first (bounded ring)."""
    flush_gate_decisions()
    return list(_gate_ring)


class _GateObsCtx:
    """Times a host-route execution and joins it onto the pending
    decision: ``with gate_observation("replay", "host"): ...``."""

    __slots__ = ("_gate", "_route", "_t0")

    def __init__(self, gate: str, route: str):
        self._gate = gate
        self._route = route
        self._t0 = 0

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            dt = (time.perf_counter_ns() - self._t0) / 1e9
            _observe_gate(self._gate, self._route, dt)
        return False


def gate_observation(gate: str, route: str):
    """Context manager observing a non-dispatch (host-route) execution
    for gate calibration; the shared no-op singleton when disabled."""
    if _mode == MODE_OFF:
        return _NOOP_DISPATCH
    return _GateObsCtx(gate, route)


# -- compile events from the compiler ----------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fired only when the persistent cache answered, inside the compile
# event of the same program, on the compiling thread
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# the dispatch open in the calling context (jit compiles on the thread
# that calls it, so the compile event finds its dispatch here)
_OPEN: contextvars.ContextVar[Optional["_DispatchCtx"]] = (
    contextvars.ContextVar("delta_tpu_open_dispatch", default=None))
_retrieval = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _CACHE_RETRIEVAL_EVENT:
        _retrieval.seconds = duration_secs


def _on_time_span(event: str, start_time: float, end_time: float,
                  fun_name: str = "?", **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    retrieval_s = getattr(_retrieval, "seconds", None)
    _retrieval.seconds = None
    compile_s = end_time - start_time
    program = {"fun_name": str(fun_name), "compile_s": compile_s,
               "cache_hit": retrieval_s is not None}
    if retrieval_s is not None:
        program["cache_retrieval_s"] = retrieval_s
    dd = _OPEN.get()
    if dd is not None:
        dd._programs.append(program)
    _trace.record_span("device.compile", int(start_time * 1e9),
                       int(compile_s * 1e9),
                       kernel=dd._name if dd is not None else None,
                       fun_name=program["fun_name"],
                       cache_hit=program["cache_hit"])


def _sync_compile_listeners() -> None:
    """JAX's listeners are registered while device obs is on and
    unregistered when it goes off. A process that has not imported JAX
    yet registers at its first live dispatch (the site is about to
    call a jit, so JAX is there by then)."""
    global _listening
    want = _mode != MODE_OFF
    if want == _listening or (want and "jax" not in sys.modules):
        return
    with _listen_lock:
        if want == _listening:
            return
        from jax import monitoring

        if want:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_time_span_listener(_on_time_span)
        else:
            monitoring.unregister_event_duration_listener(_on_duration)
            monitoring.unregister_event_time_span_listener(_on_time_span)
        _listening = want


# -- the dispatch funnel -----------------------------------------------------


class _NoopDispatch:
    """Disabled-path singleton: stateless, reentrant, thread-safe. Every
    recorder method is a no-op; `h2d`/`d2h` pass their array through so
    instrumented sites read identically in both modes."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def h2d(self, lane, obj, units=None):
        return obj

    def d2h(self, lane, obj, units=None):
        return obj

    def set(self, **attrs) -> None:
        pass

    def wait(self):
        return self


_NOOP_DISPATCH = _NoopDispatch()

# Device-fault chaos hook (resilience/device_chaos.py). When armed,
# every device_dispatch() call — obs on or off — passes through the
# engine's on_dispatch() before a context is built: it may sleep
# (transfer stall), salt the compile key (recompile storm), or raise
# (dispatch error / simulated RESOURCE_EXHAUSTED). Injected exceptions
# surface at the call site's `with` statement, indistinguishable from
# a real launch failure.
_dispatch_chaos = None


def set_dispatch_chaos(engine) -> None:
    """Arm (or, with None, disarm) the device-fault chaos engine."""
    global _dispatch_chaos
    _dispatch_chaos = engine


class _DispatchCtx:
    """Live-path recorder for one kernel launch."""

    __slots__ = ("_name", "_key", "_budget", "_units", "_gate", "_route",
                 "_attrs", "_lanes", "_h2d_total", "_d2h_total", "_t0",
                 "_programs", "_open_token", "_record", "_gate_record")

    def __init__(self, name: str, key, budget: Optional[str],
                 units: Optional[int], gate: Optional[str], route: str):
        self._name = name
        self._key = key
        self._budget = budget
        self._units = units
        self._gate = gate
        self._route = route
        self._attrs: Dict[str, object] = {}
        self._lanes: List[Tuple[str, str, int, Optional[int]]] = []
        self._h2d_total = 0
        self._d2h_total = 0
        self._t0 = 0
        self._programs: List[dict] = []
        self._open_token = None
        self._record: Optional[dict] = None
        self._gate_record: Optional[dict] = None

    def __enter__(self):
        self._open_token = _OPEN.set(self)
        self._t0 = time.perf_counter_ns()
        return self

    def wait(self):
        """Context manager round the blocking read of an asynchronous
        launch, after this dispatch has closed: its time joins the
        record (`wait_ns`) and the gate decision the launch joined, so
        both see the kernel and not only its launch."""
        return _DispatchWait(self)

    def h2d(self, lane: str, obj, units: Optional[int] = None):
        """Record `obj` (an array about to cross host->device, or an
        int byte count) as lane `lane`; `units` prices the lane against
        its manifest declaration when it differs from the dispatch-level
        unit count (e.g. a [n_lanes, n_pad] matrix). Returns `obj`."""
        nbytes = getattr(obj, "nbytes", None)
        if nbytes is None:
            nbytes = int(obj)
        self._lanes.append((lane, "h2d", int(nbytes), units))
        self._h2d_total += int(nbytes)
        return obj

    def d2h(self, lane: str, obj, units: Optional[int] = None):
        """Record device->host result bytes for lane `lane`."""
        nbytes = getattr(obj, "nbytes", None)
        if nbytes is None:
            nbytes = int(obj)
        self._lanes.append((lane, "d2h", int(nbytes), units))
        self._d2h_total += int(nbytes)
        return obj

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)

    def _audit(self) -> List[str]:
        """Reconcile recorded H2D lanes against the manifest entry."""
        entry = _budget_manifest().get(self._budget)
        if entry is None:
            return [f"budget entry {self._budget!r} not in manifest"]
        decls = {d.get("name"): d for d in entry.get("lanes", [])}
        exhaustive = bool(entry.get("device_put_exhaustive"))
        out: List[str] = []
        for lane, direction, nbytes, lane_units in self._lanes:
            if direction != "h2d":
                continue
            decl = decls.get(lane)
            if decl is None:
                if exhaustive:
                    out.append(f"undeclared lane {lane!r} shipped "
                               f"{nbytes} B (entry {self._budget!r} is "
                               f"device_put_exhaustive)")
                continue
            units = lane_units if lane_units is not None else self._units
            expected = _lane_expected_bytes(decl, units)
            if expected is not None and nbytes > expected:
                out.append(f"lane {lane!r} shipped {nbytes} B > budgeted "
                           f"{expected} B ({units} x "
                           f"{decl.get('kind')}/{decl.get('dtype', '1bit')}, "
                           f"entry {self._budget!r})")
        return out

    def __exit__(self, exc_type, exc, tb) -> bool:
        wall_ns = time.perf_counter_ns() - self._t0
        _OPEN.reset(self._open_token)
        compiled = False
        n_keys = 0
        if self._key is not None:
            with _seen_lock:
                seen = _seen_keys.setdefault(self._name, set())
                if self._key not in seen:
                    seen.add(self._key)
                    compiled = True
                n_keys = len(seen)
            if compiled:
                _COMPILES.inc()
                if n_keys > _storm_threshold():
                    _RECOMPILE_STORMS.inc()
                    _log.warning(
                        "recompile storm: kernel %s has compiled %d distinct "
                        "shape keys (alarm threshold %d) — shape churn is "
                        "defeating pad_bucket", self._name, n_keys,
                        _storm_threshold())
        _DISPATCHES.inc()
        _DISPATCH_NS.observe(wall_ns)
        if self._h2d_total:
            _H2D.inc(self._h2d_total)
        if self._d2h_total:
            _D2H.inc(self._d2h_total)
        violations = self._audit() if self._budget is not None else []
        rec = {
            "type": "device_dispatch",
            "kernel": self._name,
            "key": repr(self._key) if self._key is not None else None,
            "compile": compiled,
            "distinct_keys": n_keys,
            "compile_s": sum(p["compile_s"] for p in self._programs),
            "wall_ns": wall_ns,
            "h2d_bytes": self._h2d_total,
            "d2h_bytes": self._d2h_total,
            "lanes": [{"name": ln, "dir": d, "nbytes": nb, "units": u}
                      for ln, d, nb, u in self._lanes],
            "budget": self._budget,
            "units": self._units,
            "violations": violations,
            "gate": self._gate,
            "route": self._route,
            "status": "error" if exc_type is not None else "ok",
            "ts_unix_ns": time.time_ns(),
        }
        if self._attrs:
            rec["attrs"] = self._attrs
        if self._programs:
            rec["programs"] = self._programs
        self._record = rec
        _dispatch_ring.append(rec)
        if self._gate is not None:
            self._gate_record = (_PENDING.get() or {}).get(self._gate)
            # failed dispatches feed calibration too: a route that burns
            # wall time and then falls back to host must look *more*
            # expensive to the gate, not invisible
            _observe_gate(self._gate, self._route, wall_ns / 1e9)
        _trace.add_event("device.dispatch", kernel=self._name,
                         route=self._route, wall_ms=round(wall_ns / 1e6, 4),
                         compile=compiled, h2d_bytes=self._h2d_total,
                         violations=len(violations))
        if violations:
            _VIOLATIONS.inc(len(violations))
            _log.warning("transfer-budget audit: %s", "; ".join(violations))
            if _mode >= MODE_STRICT and exc_type is None:
                raise RuntimeError(
                    "transfer budget exceeded: " + "; ".join(violations))
        return False


class _DispatchWait:
    """Times the blocking read that follows an asynchronous launch
    (see `_DispatchCtx.wait`)."""

    __slots__ = ("_dd", "_t0")

    def __init__(self, dd: _DispatchCtx):
        self._dd = dd
        self._t0 = 0

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        wait_ns = time.perf_counter_ns() - self._t0
        rec = self._dd._record
        if rec is not None:
            rec["wait_ns"] = rec.get("wait_ns", 0) + wait_ns
        gate_rec = self._dd._gate_record
        if gate_rec is not None and not gate_rec.get("_final"):
            gate_rec["observed_s"] = ((gate_rec["observed_s"] or 0.0)
                                      + wait_ns / 1e9)
        return False


def device_dispatch(name: str, *, key=None, budget: Optional[str] = None,
                    units: Optional[int] = None, gate: Optional[str] = None,
                    route: str = "device"):
    """Open the dispatch funnel around one kernel launch.

    ``name``   stable kernel identity ("json_parse.window", ...);
    ``key``    hashable shape-bucket signature — first sighting per name
               counts as a compile, churn past the alarm threshold is a
               recompile storm;
    ``budget`` transfer-budget manifest entry to audit recorded lanes
               against (``dd.h2d(lane, arr, units=...)`` before each
               device_put);
    ``units``  default unit count for lane pricing;
    ``gate``   routing gate this dispatch executes for ("replay",
               "parse", "skip") — the observed wall time joins the
               pending decision;
    ``route``  the route label recorded on the join.

    Returns the shared no-op singleton when device obs is off."""
    if _dispatch_chaos is not None:
        key = _dispatch_chaos.on_dispatch(name, key=key, gate=gate,
                                          route=route)
    if _mode == MODE_OFF:
        return _NOOP_DISPATCH
    if not _listening:
        _sync_compile_listeners()
    return _DispatchCtx(name, key, budget, units, gate, route)


def program(name: str):
    """Name the function a site hands to `jax.jit` after the dispatch
    that launches it: ``jax.jit(obs.program("json_parse.window")(fn))``
    compiles as `jit_json_parse_window`, so a reader joins dispatch
    records to the device operations of a profile by name (dots become
    underscores; a dispatch that launches several programs suffixes
    them). The name is part of the persistent cache's key, the scopes
    inside a program are not: a program that gains `jax.named_scope`s
    has to change its name too, or the cache hands back the old
    executable without them. Wraps, never renames `fn` itself."""
    program_name = name.replace(".", "_")

    def deco(fn):
        @functools.wraps(fn)
        def named(*args, **kwargs):
            return fn(*args, **kwargs)

        named.__name__ = named.__qualname__ = program_name
        return named

    return deco


def get_dispatch_records() -> List[dict]:
    """Dispatch records, oldest first (bounded ring)."""
    return list(_dispatch_ring)


def reset_device_obs() -> None:
    """Clear rings, compile-tracking state, and pending decisions
    (tests/bench); the manifest cache drops so env overrides re-read."""
    _dispatch_ring.clear()
    _gate_ring.clear()
    with _seen_lock:
        _seen_keys.clear()
    _PENDING.set({})
    _budget_manifest.cache_clear()


# -- capture conditions ------------------------------------------------------

CONDITIONS_SCHEMA = "delta-tpu/capture-conditions/v1"

# sentinel for bench artifacts that predate the schema, so a comparison
# can refuse to mix them with conditioned captures instead of silently
# comparing across platforms
CONDITIONS_UNKNOWN = "unknown-pre-r20"

# Every env knob that can change a routing decision or the shape of
# what a capture measured. The delta-lint `route-contract` and
# `env-knob-capture-stamp` passes parse this tuple statically: a route
# knob (or any env_knobs.json entry marked `"capture": true`) missing
# here fails lint, so a new route can't repeat the PR 16 "forgot to
# stamp DELTA_TPU_DEVICE_DECODE" omission.
CAPTURE_ENV_KEYS = (
    "DELTA_TPU_REPLAY_ROUTE",
    "DELTA_TPU_DEVICE_PARSE",
    "DELTA_TPU_DEVICE_SKIP",
    "DELTA_TPU_DEVICE_DECODE",
    "DELTA_TPU_RESIDENT",
    "DELTA_TPU_DEVICE_CKPT_STATS",
    "DELTA_TPU_DEVICE_DV_PACK",
    "DELTA_TPU_DEVICE_DV_DECODE",
    "DELTA_TPU_DEVICE_SQL",
    "DELTA_TPU_TRACE",
    "DELTA_TPU_DEVICE_OBS",
    "DELTA_TPU_HBM_OBS",
    "DELTA_TPU_DEVICE_CHAOS",
    "JAX_PLATFORMS",
)


def capture_conditions(cache_state: str = "unknown",
                       extra: Optional[Dict[str, object]] = None
                       ) -> Dict[str, object]:
    """The versioned capture-conditions stamp: everything that made the
    r02->r05 headline ratios incomparable (platform, device count/kind,
    cache state, x64 mode) plus toolchain versions and the routing env
    overrides in force. Cheap, never raises — a half-configured backend
    records as unknown rather than failing a bench."""
    cond: Dict[str, object] = {
        "schema": CONDITIONS_SCHEMA,
        "platform": "unknown",
        "device_count": 0,
        "device_kind": "unknown",
        "x64": False,
        "cache_state": cache_state,
        "python": ".".join(map(str, sys.version_info[:3])),
        "pid_cpus": os.cpu_count() or 0,
    }
    try:
        import jax

        cond["platform"] = jax.default_backend()
        devs = jax.devices()
        cond["device_count"] = len(devs)
        cond["device_kind"] = getattr(devs[0], "device_kind", "unknown")
        cond["x64"] = bool(jax.config.jax_enable_x64)
        cond["jax"] = jax.__version__
    # delta-lint: disable=except-swallow (audited: backend discovery can
    # fail on hosts with no configured platform; conditions degrade to
    # "unknown" — a bench stamp must never abort the bench)
    except Exception:
        pass
    try:
        import numpy

        cond["numpy"] = numpy.__version__
    except ImportError:
        pass
    env = {k: v for k, v in os.environ.items()
           if k in CAPTURE_ENV_KEYS}
    if env:
        cond["env"] = env
    if extra:
        cond.update(extra)
    return cond


def conditions_fingerprint(cond) -> str:
    """Comparability key for trend analysis: captures with different
    fingerprints must never be compared in one noise band. Pre-schema
    string stamps fingerprint as themselves."""
    if isinstance(cond, str):
        return cond
    if not isinstance(cond, dict):
        return "missing"
    return "|".join(str(cond.get(k, "?")) for k in
                    ("platform", "device_count", "device_kind", "x64",
                     "cache_state"))


# -- artifacts: the gate log -------------------------------------------------


def dump_gate_log(path: str) -> int:
    """Write every gate-decision and dispatch record as JSONL (gate
    records finalized first); returns the record count. The `delta-gate`
    CLI consumes this artifact."""
    gates = get_gate_records()
    dispatches = get_dispatch_records()
    with open(path, "w", encoding="utf-8") as f:
        for rec in gates + dispatches:
            f.write(json.dumps(
                {k: v for k, v in rec.items() if not k.startswith("_")},
                sort_keys=True) + "\n")
    return len(gates) + len(dispatches)


def summarize_gates(records: Optional[List[dict]] = None
                    ) -> Dict[str, dict]:
    """Per-gate calibration summary: decision/fallback counts and, per
    chosen route, predicted vs observed medians and the median absolute
    calibration error percentage."""
    records = get_gate_records() if records is None else records
    out: Dict[str, dict] = {}
    for rec in records:
        if rec.get("type") != "gate_decision":
            continue
        g = out.setdefault(rec["gate"], {"decisions": 0, "fallbacks": 0,
                                         "routes": {}})
        g["decisions"] += 1
        if rec.get("fell_back_to"):
            g["fallbacks"] += 1
        r = g["routes"].setdefault(rec["chosen"],
                                   {"n": 0, "joined": 0, "predicted_s": [],
                                    "observed_s": [], "err_pct": []})
        r["n"] += 1
        pred = (rec.get("predicted_s") or {}).get(rec["chosen"])
        if rec.get("observed_s") is not None:
            r["joined"] += 1
            r["observed_s"].append(rec["observed_s"])
            if pred:
                r["predicted_s"].append(pred)
        if rec.get("calibration_error_pct") is not None:
            r["err_pct"].append(rec["calibration_error_pct"])
    for g in out.values():
        for r in g["routes"].values():
            for field in ("predicted_s", "observed_s"):
                vals = sorted(r.pop(field))
                r[f"median_{field}"] = vals[len(vals) // 2] if vals else None
            errs = sorted(abs(e) for e in r.pop("err_pct"))
            r["median_abs_err_pct"] = errs[len(errs) // 2] if errs else None
    return out
