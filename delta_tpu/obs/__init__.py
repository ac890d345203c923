"""delta-tpu observability: hierarchical spans, metrics registry, exporters.

Zero-dependency tracing + telemetry spine (ROADMAP: observability).
Typical instrumentation site::

    from delta_tpu import obs

    with obs.span("snapshot.load", table=path) as s:
        ...
        s.set_attr("version", snap.version)

Gate with ``DELTA_TPU_TRACE=off|on|verbose`` (default off; the disabled
path returns a shared no-op context manager). ``DELTA_TPU_TRACE_FILE``
appends finished spans as JSONL; `delta-trace` (``python -m
delta_tpu.tools.trace``) summarizes either JSONL or Chrome trace files.

Counters/histograms (`counter`, `histogram`) are always on and
process-wide; resolve them once at module import and call ``.inc()`` on
the hot path.
"""

from delta_tpu.obs.device import (
    CONDITIONS_SCHEMA,
    CONDITIONS_UNKNOWN,
    capture_conditions,
    conditions_fingerprint,
    device_dispatch,
    device_obs_enabled,
    device_obs_mode,
    dump_gate_log,
    flush_gate_decisions,
    gate_fell_back,
    gate_observation,
    get_dispatch_records,
    get_gate_records,
    program,
    record_gate_decision,
    reset_device_obs,
    set_device_obs_mode,
    summarize_gates,
)
# Importing the submodule here (not just names) activates the
# ledger-derived gauges process-wide: hbm.py binds their set_fn
# callbacks at import time. Instrumented sites use the submodule
# directly (`from delta_tpu.obs import hbm`; `hbm.register(...)`).
from delta_tpu.obs import hbm
from delta_tpu.obs.export import (
    JsonlExporter,
    chrome_trace,
    load_spans,
    span_to_dict,
    write_chrome_trace,
)
from delta_tpu.obs.expose import (
    CONTENT_TYPE,
    metric_catalog,
    parse_prometheus,
    prom_name,
    render_prometheus,
)
from delta_tpu.obs.flight import FlightRecorder
from delta_tpu.obs.hbm import (
    hbm_obs_enabled,
    hbm_obs_mode,
    reset_hbm_obs,
    set_hbm_obs_mode,
)
from delta_tpu.obs.registry import (
    EXPORT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    gauge,
    histogram,
    metrics_snapshot,
    registry,
)
from delta_tpu.obs.slo import (
    Breach,
    Objective,
    SloEngine,
    SloVerdict,
    serve_objectives,
)
from delta_tpu.obs.trace import (
    MODE_OFF,
    MODE_ON,
    MODE_VERBOSE,
    PHASE_SPAN_ROWS,
    Span,
    add_event,
    add_exporter,
    current_span,
    get_finished_spans,
    process_label,
    record_span,
    remote_parent,
    remove_exporter,
    reset_trace_buffer,
    set_attr,
    set_attrs,
    set_process_label,
    set_trace_mode,
    set_trace_sample,
    span,
    trace_context,
    trace_enabled,
    trace_mode,
    trace_sample,
    wrap,
)

# Both trace and export are fully initialized here, so honoring
# DELTA_TPU_TRACE_FILE at startup is now cycle-safe (trace.py itself
# must not do this at import time — export.py imports trace.py).
if trace_enabled():
    from delta_tpu.obs.trace import _install_env_exporter_once

    _install_env_exporter_once()
    del _install_env_exporter_once

__all__ = [
    "CONDITIONS_SCHEMA",
    "CONDITIONS_UNKNOWN",
    "CONTENT_TYPE",
    "EXPORT_BUCKETS",
    "MODE_OFF",
    "MODE_ON",
    "MODE_VERBOSE",
    "PHASE_SPAN_ROWS",
    "Breach",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlExporter",
    "Objective",
    "Registry",
    "SloEngine",
    "SloVerdict",
    "Span",
    "add_event",
    "add_exporter",
    "capture_conditions",
    "chrome_trace",
    "conditions_fingerprint",
    "counter",
    "current_span",
    "device_dispatch",
    "device_obs_enabled",
    "device_obs_mode",
    "dump_gate_log",
    "flush_gate_decisions",
    "gate_fell_back",
    "gate_observation",
    "gauge",
    "get_dispatch_records",
    "get_finished_spans",
    "get_gate_records",
    "hbm",
    "hbm_obs_enabled",
    "hbm_obs_mode",
    "histogram",
    "load_spans",
    "metric_catalog",
    "metrics_snapshot",
    "record_gate_decision",
    "record_span",
    "reset_device_obs",
    "reset_hbm_obs",
    "set_hbm_obs_mode",
    "parse_prometheus",
    "process_label",
    "program",
    "prom_name",
    "registry",
    "remote_parent",
    "remove_exporter",
    "render_prometheus",
    "reset_trace_buffer",
    "serve_objectives",
    "set_attr",
    "set_attrs",
    "set_device_obs_mode",
    "set_process_label",
    "set_trace_mode",
    "summarize_gates",
    "set_trace_sample",
    "span",
    "span_to_dict",
    "trace_context",
    "trace_enabled",
    "trace_mode",
    "trace_sample",
    "wrap",
    "write_chrome_trace",
]
