"""Hierarchical span tracing: contextvar span stack, ring buffer, exporters.

Dapper-style traces (Sigelman et al. 2010) shaped after the reference's
`recordDeltaOperation` timing scopes (`DeltaLogging.scala:118`): every
instrumented operation opens a span; nested operations become child
spans sharing the root's trace id, so one `Table.latest_snapshot()`
stitches listing, parse, columnarize, and replay-kernel phases — across
threads and storage layers — into a single connected tree.

Gating: `DELTA_TPU_TRACE=off|on|verbose` (default off).  The disabled
path is near-zero cost: `span()` returns a process-wide no-op context
manager singleton — no allocation, no clock read, no contextvar touch.
`verbose` additionally enables high-cardinality spans (per-file storage
reads) that `on` folds into counters.

Sampling: `DELTA_TPU_TRACE_SAMPLE=<0..1>` (default 1.0) keeps each new
trace ROOT with that probability — head-based, so a kept trace is
always complete and a dropped one costs one RNG draw. The decision is
made once at the root and inherited by every descendant (including
cross-thread children via `wrap()` and cross-process children via the
envelope ids, which an unsampled client simply never stamps).

Profiler bridge: in a process that has imported JAX, a live span also
enters a `jax.profiler.TraceAnnotation` of its name (the span id rides
as an argument), so a profile taken while tracing holds the program's
spans of every thread on the trace's own clock, beside the device
lines. Outside a profiling session the annotation is one flag test.

Finished spans land in a bounded in-process ring buffer
(`get_finished_spans`) and are fanned out to registered exporters;
`DELTA_TPU_TRACE_FILE=<path>` auto-installs a JSONL exporter.
"""

from __future__ import annotations

import collections
import contextvars
import logging
import os
import random
import sys
import threading
import time
from typing import Dict, List, Optional

_log = logging.getLogger(__name__)

MODE_OFF = 0
MODE_ON = 1
MODE_VERBOSE = 2

_MODES = {"off": MODE_OFF, "on": MODE_ON, "verbose": MODE_VERBOSE,
          "0": MODE_OFF, "1": MODE_ON, "2": MODE_VERBOSE}


def _mode_from_env() -> int:
    raw = os.environ.get("DELTA_TPU_TRACE", "off").strip().lower()
    mode = _MODES.get(raw)
    if mode is None:
        _log.warning("unknown DELTA_TPU_TRACE=%r; tracing stays off", raw)
        return MODE_OFF
    return mode


_mode: int = _mode_from_env()


def _sample_from_env() -> float:
    raw = os.environ.get("DELTA_TPU_TRACE_SAMPLE")
    if not raw:
        return 1.0
    try:
        rate = float(raw)
    except ValueError:
        _log.warning("bad DELTA_TPU_TRACE_SAMPLE=%r; sampling stays at 1",
                     raw)
        return 1.0
    return min(1.0, max(0.0, rate))


_sample_rate: float = _sample_from_env()
_sample_rng = random.Random()  # trace keep/drop only — not security


def set_trace_sample(rate: Optional[float]) -> None:
    """Set the head-sampling rate (fraction of new trace roots kept,
    clamped to [0, 1]); None re-reads `DELTA_TPU_TRACE_SAMPLE`."""
    global _sample_rate
    if rate is None:
        _sample_rate = _sample_from_env()
    else:
        _sample_rate = min(1.0, max(0.0, float(rate)))


def trace_sample() -> float:
    return _sample_rate

# the active span of the calling context; child contexts (threads) do
# NOT inherit it automatically — use wrap() to propagate across pools
_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "delta_tpu_current_span", default=None
)

# human label for this process in merged multi-process traces (the
# Chrome exporter's process_name metadata); CLI entry points set it
# ("delta-serve", "delta-connect"), libraries leave it None
_process_label: Optional[str] = os.environ.get("DELTA_TPU_TRACE_PROCESS")


def set_process_label(label: Optional[str]) -> None:
    """Name this process for multi-process trace rendering. Spans record
    the label at creation, so set it before serving traffic."""
    global _process_label
    _process_label = label


def process_label() -> Optional[str]:
    return _process_label


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


# `jax.profiler.TraceAnnotation`, resolved by the first live span of a
# process that has imported JAX (False: this JAX has none). A process
# that never imported JAX holds no device to profile, so the bridge
# never imports it either; with tracing off nothing here is reached.
_annotation_cls = None


def _profiler_annotation(name: str, span_id: str):
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:
            cls = False
        _annotation_cls = cls
    return cls(name, span_id=span_id) if cls else None


class Span:
    """One finished or in-flight operation: half-open interval + metadata.

    `start_unix_ns` anchors the span on the wall clock (exporters need
    absolute timestamps); `duration_ns` is measured on the monotonic
    clock so it survives wall-clock steps.
    """

    __slots__ = ("trace_id", "span_id", "parent_id", "name",
                 "start_unix_ns", "monotonic_start_ns", "duration_ns",
                 "attrs", "events", "status", "thread_id", "thread_name",
                 "pid", "process")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], attrs: Dict[str, object]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_unix_ns = time.time_ns()
        self.monotonic_start_ns = time.perf_counter_ns()
        self.duration_ns: Optional[int] = None
        self.attrs = attrs
        self.events: List[Dict[str, object]] = []
        self.status = "ok"
        cur = threading.current_thread()
        self.thread_id = cur.ident or 0
        self.thread_name = cur.name
        self.pid = os.getpid()
        self.process = _process_label

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def set_attrs(self, **attrs) -> None:
        self.attrs.update(attrs)

    def add_event(self, name: str, **attrs) -> None:
        self.events.append({"name": name, "ts_unix_ns": time.time_ns(),
                            "attrs": attrs})

    @property
    def recording(self) -> bool:
        return True

    def timed(self, key: str) -> "_Timed":
        """A context manager that adds the milliseconds spent inside it
        to attribute `key` of this span: a sum over many short intervals
        (a file's open, its decode) that are too many to be spans of
        their own. It may be kept and entered again and again, by the
        span's own thread, one entry at a time."""
        return _Timed(self.attrs, key)

    def to_dict(self) -> Dict[str, object]:
        return {
            "type": "span",
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_unix_ns": self.start_unix_ns,
            "duration_ns": self.duration_ns,
            "status": self.status,
            "thread_id": self.thread_id,
            "thread_name": self.thread_name,
            "pid": self.pid,
            "process": self.process,
            "attrs": self.attrs,
            "events": self.events,
        }

    def __repr__(self):
        return (f"Span({self.name!r}, trace={self.trace_id[:8]}, "
                f"span={self.span_id}, parent={self.parent_id}, "
                f"status={self.status})")


class _Timed:
    """One entry of `Span.timed`: the span clock round a block, added
    to the attribute whether the block returns or raises."""

    __slots__ = ("_attrs", "_key", "_start_ns")

    def __init__(self, attrs: Dict[str, object], key: str):
        self._attrs = attrs
        self._key = key

    def __enter__(self) -> None:
        self._start_ns = time.perf_counter_ns()

    def __exit__(self, exc_type, exc, tb) -> bool:
        spent = (time.perf_counter_ns() - self._start_ns) / 1e6
        self._attrs[self._key] = self._attrs.get(self._key, 0.0) + spent
        return False


class _NoopSpan:
    """The recorded-nothing span: every mutator is a no-op. A single
    process-wide instance backs the disabled path."""

    __slots__ = ()

    trace_id = None
    span_id = None
    parent_id = None
    name = None
    status = "ok"
    duration_ns = None

    def set_attr(self, key: str, value) -> None:
        pass

    def set_attrs(self, **attrs) -> None:
        pass

    def add_event(self, name: str, **attrs) -> None:
        pass

    @property
    def recording(self) -> bool:
        return False

    def timed(self, key: str) -> "_NoopCtx":
        return _NOOP_CTX    # no clock read, nothing allocated


class _NoopCtx:
    """Reusable, reentrant, thread-safe no-op context manager: carries no
    per-use state, so one singleton serves every disabled `span()` call."""

    __slots__ = ()

    def __enter__(self) -> _NoopSpan:
        return _NOOP_SPAN

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()
_NOOP_CTX = _NoopCtx()


class _SuppressedMarker:
    """Sentinel installed in `_CURRENT` for the extent of an UNSAMPLED
    trace root: descendants (same-thread, and cross-thread via wrap())
    see it and record nothing, so a dropped trace is dropped whole —
    never a parent-less fragment."""

    __slots__ = ()


_SUPPRESSED = _SuppressedMarker()


class _SpanCtx:
    """Live-path context manager: creates the span on __enter__ (so the
    parent is read from the entering context, not the creating one)."""

    __slots__ = ("_name", "_attrs", "_span", "_token", "_annotation")

    def __init__(self, name: str, attrs: Dict[str, object]):
        self._name = name
        self._attrs = attrs
        self._span: Optional[Span] = None
        self._token = None
        self._annotation = None

    def __enter__(self):
        parent = _CURRENT.get()
        if parent is _SUPPRESSED:
            return _NOOP_SPAN  # inside an unsampled trace
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
        else:
            # new trace root: the head-sampling decision happens here,
            # once, and binds the whole (cross-thread) subtree below
            if _sample_rate < 1.0 and _sample_rng.random() >= _sample_rate:
                self._token = _CURRENT.set(_SUPPRESSED)
                return _NOOP_SPAN
            trace_id, parent_id = _new_id(16), None
        s = Span(self._name, trace_id, _new_id(8), parent_id, self._attrs)
        self._span = s
        self._token = _CURRENT.set(s)
        self._annotation = _profiler_annotation(self._name, s.span_id)
        if self._annotation is not None:
            self._annotation.__enter__()
        return s

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._span
        if s is None:
            # suppressed (unsampled root, or child of one): unwind the
            # sentinel if this ctx installed it, record nothing
            if self._token is not None:
                _CURRENT.reset(self._token)
                self._token = None
            return False
        s.duration_ns = time.perf_counter_ns() - s.monotonic_start_ns
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if exc_type is not None:
            s.status = "error"
            s.attrs.setdefault("error.type", exc_type.__name__)
            if exc is not None:
                s.attrs.setdefault("error.message", str(exc)[:200])
        _CURRENT.reset(self._token)
        _finish(s)
        return False


# A span that times one phase of a pass over a table is worth its own
# name once the table is large: from this many rows it is recorded under
# `on`, below it under `verbose` alone (`span(..., _verbose=rows <
# PHASE_SPAN_ROWS)`). A refresh of a few thousand rows is a few
# milliseconds, its parent says it all, and a profile of a small run
# keeps its handful of names.
PHASE_SPAN_ROWS = 1 << 16


def span(name: str, _verbose: bool = False, **attrs):
    """Open a span named `name` with initial attributes `attrs`.

    Use as a context manager: ``with span("snapshot.load", table=p) as s:``.
    `_verbose=True` marks a high-cardinality span recorded only under
    `DELTA_TPU_TRACE=verbose` (e.g. per-file storage reads). When tracing
    is disabled (or the span is verbose-only and the mode is `on`) a
    shared no-op context manager is returned — near-zero cost.
    """
    if _mode == MODE_OFF or (_verbose and _mode < MODE_VERBOSE):
        return _NOOP_CTX
    if _CURRENT.get() is _SUPPRESSED:
        return _NOOP_CTX  # unsampled trace: skip the ctx allocation too
    return _SpanCtx(name, attrs)


def record_span(name: str, start_unix_ns: int, duration_ns: int,
                **attrs) -> None:
    """Record an interval that something else timed (a compiler event,
    say) as a finished span with that start and length, under the span
    open in the calling context. No-op when tracing is off or the
    trace is unsampled; outside any span it roots a trace of its own."""
    if _mode == MODE_OFF:
        return
    parent = _CURRENT.get()
    if parent is _SUPPRESSED:
        return
    if parent is not None:
        trace_id, parent_id = parent.trace_id, parent.span_id
    else:
        if _sample_rate < 1.0 and _sample_rng.random() >= _sample_rate:
            return
        trace_id, parent_id = _new_id(16), None
    s = Span(name, trace_id, _new_id(8), parent_id, attrs)
    s.start_unix_ns = int(start_unix_ns)
    s.duration_ns = int(duration_ns)
    _finish(s)


def current_span() -> Optional[Span]:
    """The context's active span, or None outside any span (or when
    tracing is off / the trace was not sampled)."""
    cur = _CURRENT.get()
    return None if cur is _SUPPRESSED else cur


def trace_context() -> Optional[tuple]:
    """(trace_id, span_id) of the active span for wire propagation, or
    None outside any span / tracing off / trace unsampled (so remote
    children of a dropped trace are dropped too). Stamp these into an
    outgoing request envelope; the server side adopts them via
    remote_parent()."""
    cur = _CURRENT.get()
    if cur is None or cur is _SUPPRESSED:
        return None
    return (cur.trace_id, cur.span_id)


# envelope trace ids arrive from untrusted peers; accept only plain hex
# strings of sane length so a hostile client can't bloat span records
_MAX_WIRE_ID_LEN = 64


def _valid_wire_id(value) -> bool:
    return (isinstance(value, str) and 0 < len(value) <= _MAX_WIRE_ID_LEN
            and all(c in "0123456789abcdefABCDEF-" for c in value))


class _AdoptCtx:
    """Adopt a remote (trace_id, parent_span_id) as the ambient parent.

    Installs a synthetic, never-finished Span carrying the remote ids so
    spans opened inside the scope parent *directly* under the client's
    span — the placeholder itself is never buffered or exported (the
    real span lives in the client process)."""

    __slots__ = ("_trace_id", "_parent_span_id", "_token")

    def __init__(self, trace_id: str, parent_span_id: str):
        self._trace_id = trace_id
        self._parent_span_id = parent_span_id
        self._token = None

    def __enter__(self):
        placeholder = Span("remote.parent", self._trace_id,
                           self._parent_span_id, None, {})
        self._token = _CURRENT.set(placeholder)
        return placeholder

    def __exit__(self, exc_type, exc, tb) -> bool:
        _CURRENT.reset(self._token)
        return False


def remote_parent(trace_id, parent_span_id):
    """Continue a trace started in another process: spans opened inside
    the returned context parent under (`trace_id`, `parent_span_id`) as
    read from a request envelope. No-op (shared singleton) when tracing
    is off or either id is missing/malformed — untrusted wire values
    never abort request handling."""
    if (_mode == MODE_OFF or not _valid_wire_id(trace_id)
            or not _valid_wire_id(parent_span_id)):
        return _NOOP_CTX
    return _AdoptCtx(trace_id, parent_span_id)


def set_attr(key: str, value) -> None:
    """Attach `key=value` to the active span; no-op outside a span."""
    cur = _CURRENT.get()
    if cur is not None and cur is not _SUPPRESSED:
        cur.attrs[key] = value


def set_attrs(**attrs) -> None:
    cur = _CURRENT.get()
    if cur is not None and cur is not _SUPPRESSED:
        cur.attrs.update(attrs)


def add_event(name: str, **attrs) -> None:
    """Append a point-in-time event to the active span; no-op outside."""
    cur = _CURRENT.get()
    if cur is not None and cur is not _SUPPRESSED:
        cur.add_event(name, **attrs)


def wrap(fn):
    """Bind the caller's active span to `fn` so running it on another
    thread parents its spans correctly.

    contextvars do not propagate into ThreadPoolExecutor workers; submit
    ``wrap(fn)`` instead of ``fn`` and the callee joins the caller's
    trace. Returns `fn` unchanged when tracing is off. Inside an
    UNSAMPLED trace the suppression marker is what gets bound, so the
    worker's spans are dropped with the rest of the trace.
    """
    if _mode == MODE_OFF:
        return fn
    parent = _CURRENT.get()
    if parent is None:
        return fn

    def bound(*args, **kwargs):
        token = _CURRENT.set(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            _CURRENT.reset(token)

    return bound


# -- mode control ------------------------------------------------------------


def trace_mode() -> int:
    return _mode


def trace_enabled() -> bool:
    return _mode != MODE_OFF


def set_trace_mode(mode: Optional[str]) -> None:
    """Programmatically set the trace mode ('off'|'on'|'verbose'); None
    re-reads `DELTA_TPU_TRACE` from the environment. Tests and bench use
    this; production uses the env var."""
    global _mode
    if mode is None:
        _mode = _mode_from_env()
    else:
        try:
            _mode = _MODES[mode.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown trace mode {mode!r}; expected off|on|verbose"
            ) from None
    if _mode != MODE_OFF:
        _install_env_exporter_once()


# -- collection + export -----------------------------------------------------

_BUFFER_DEFAULT = 200_000
_buffer: collections.deque = collections.deque(
    maxlen=int(os.environ.get("DELTA_TPU_TRACE_BUFFER", _BUFFER_DEFAULT))
)
_exporters: List[object] = []
_exporters_lock = threading.Lock()
_env_exporter_installed = False


def _finish(s: Span) -> None:
    _buffer.append(s)
    # snapshot the exporter list so a concurrent add/remove cannot
    # invalidate the iteration
    for exp in tuple(_exporters):
        try:
            exp(s)
        except Exception as e:
            _log.warning("trace exporter %r failed: %s", exp, e)


def get_finished_spans() -> List[Span]:
    """Finished spans in finish order (bounded ring buffer)."""
    return list(_buffer)


def reset_trace_buffer() -> None:
    _buffer.clear()


def add_exporter(exporter) -> None:
    """Register a callable(span) invoked for every finished span."""
    with _exporters_lock:
        if exporter not in _exporters:
            _exporters.append(exporter)


def remove_exporter(exporter) -> None:
    with _exporters_lock:
        if exporter in _exporters:
            _exporters.remove(exporter)


def _install_env_exporter_once() -> None:
    """Honor DELTA_TPU_TRACE_FILE: append every finished span as a JSONL
    record to the named file. Installed at most once per process."""
    global _env_exporter_installed
    if _env_exporter_installed:
        return
    path = os.environ.get("DELTA_TPU_TRACE_FILE")
    if not path:
        return
    with _exporters_lock:
        if _env_exporter_installed:
            return
        _env_exporter_installed = True
    from delta_tpu.obs.export import JsonlExporter

    try:
        add_exporter(JsonlExporter(path))
    except OSError as e:
        _log.warning("cannot open DELTA_TPU_TRACE_FILE=%r: %s", path, e)


# NOTE: the enabled-at-startup install happens in delta_tpu.obs.__init__
# (and in set_trace_mode), never at this module's import: export.py
# imports trace.py, so importing JsonlExporter from module level here
# would hit export mid-initialization and crash the whole package
# whenever DELTA_TPU_TRACE=on + DELTA_TPU_TRACE_FILE are both set.
