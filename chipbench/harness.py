"""One run of one cell.

Everything that belongs to one cell is found by name in a file of its
own: the cell in `BENCHMARK.json`, its configuration in
`configs/<config>.json`, its traffic in `mixes/<traffic>.json`, the
driver and the generator those two name in `drivers/<kind>.py` and
`gen/<kind>.py`, and each per-layer metric in `layers/<metric>.py`,
looked up in the directories under `paths` in turn. Nothing is
registered here, so a later cell is new files and one entry.
"""

from __future__ import annotations

import argparse
import collections
import glob
import importlib.util
import json
import os
import resource
import shutil
import statistics
import sys
import tempfile
import threading
import time

from chipbench import metrics, spans, traffic, trace_reduce
from chipbench.system import DeltaTpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OP = "chipbench.op"


def say(text: str) -> None:
    print(text, flush=True)


class Cell:
    """A cell of a benchmark file, with the files its names lead to."""

    def __init__(self, bench_path: str, workload: str):
        self.bench = self._json(bench_path)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"chipbench: no workload {workload!r} in "
                             f"{bench_path}; it has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        config = next(c for c in self.bench["configs"]
                      if c["name"] == self.entry["config"])
        self.config = self._json(os.path.join(ROOT, config["file"]))
        self.mix = self._json(self.find("mixes",
                                        self.entry["traffic"] + ".json"))

    @staticmethod
    def _json(path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def find(self, kind: str, filename: str) -> str:
        for base in self.bench["paths"]:
            path = os.path.join(ROOT, base, kind, filename)
            if os.path.exists(path):
                return path
        raise SystemExit(f"chipbench: no {kind}/{filename} under "
                         f"{self.bench['paths']}")

    def module(self, kind: str, name: str):
        path = self.find(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod

    def metrics_of(self, group: str) -> list:
        """The metrics of `end_to_end` or `per_layer` that this cell
        reports: those with no `workloads` key, or with it in the key."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]


def deploy(environment: dict) -> None:
    """The deployment's process environment, as its configuration file
    states it: set before the program and Arrow are imported, and never
    a variable of the program's own, so no route is ever chosen here."""
    for name, value in environment.items():
        if name.startswith("DELTA_TPU_"):
            raise SystemExit(f"chipbench: a configuration may not set "
                             f"{name}: the program runs on its defaults")
        os.environ[name] = value


def device_found() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def device_or_exit(chips: int) -> dict:
    """The chips this cell asks for, or no run at all."""
    found = device_found()
    if found["platform"] != "tpu" or found["count"] != chips:
        raise SystemExit(f"chipbench: the cell needs {chips} TPU chip(s), "
                         f"JAX found {found}; there is no fallback")
    return found


class Window:
    """The measured operations of one run, and what was compared."""

    def __init__(self):
        self.ops = []       # kind, start/end unix ns, latency ms, ok
        self.compared = {}

    def record(self, kind, start_ns, end_ns, latency_ms, compared,
               host=None):
        ok = True
        for name, got, want in compared:
            row = self.compared.setdefault(
                name, {"n": 0, "mismatches": 0, "last": None})
            row["n"] += 1
            row["last"] = (got, want)
            if got != want:
                row["mismatches"] += 1
                row.setdefault("first_bad", (got, want))
                ok = False
        self.ops.append({"kind": kind, "start_unix_ns": start_ns,
                         "end_unix_ns": end_ns, "latency_ms": latency_ms,
                         "ok": ok, "host": host})

    def say_compared(self, title: str) -> None:
        """Each number compared, beside its limit: the comparison is
        exact, so the limit on mismatches is 0."""
        for name, row in self.compared.items():
            got, want = row.get("first_bad", row["last"])
            say(f"{title} {name}: compared {row['n']}, mismatches "
                f"{row['mismatches']} (limit 0); "
                f"{'first mismatch' if row['mismatches'] else 'last'}: "
                f"got {got} want {want}")


class TracedRun:
    """What the readers under `layers/` read from."""

    def __init__(self, window, span_dicts, gates, dispatches, reduced,
                 device_kind, clock_offset_ns):
        self.ops = window.ops
        self.spans = span_dicts
        self.gates = gates
        self.dispatches = dispatches
        self.trace = reduced
        self.device_kind = device_kind
        self._offset = clock_offset_ns

    def to_trace_ns(self, unix_ns: int) -> int:
        return unix_ns + self._offset


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t0: float, bench_path: str | None = None,
             require_chip: bool = True, system=None) -> dict:
    """Run the cell once; returns the result object. `require_chip` and
    `system` are for the tests, which drive everything but the look for
    a chip, on a system of their choosing."""
    import jax

    cell = Cell(bench_path or os.path.join(ROOT, "BENCHMARK.json"), workload)
    deploy(cell.config.get("environment", {}))
    device = (device_or_exit(cell.entry["chips"]) if require_chip
              else device_found())

    say(f"deployment environment: {cell.config.get('environment', {})}")

    from delta_tpu import obs
    from delta_tpu.engine.tpu import configure_compilation_cache

    configure_compilation_cache()   # JAX_COMPILATION_CACHE_DIR, else
    compiles = []                   # <checkout>/.jax_cache

    def on_duration(event, seconds_, **_kw):
        if event == COMPILE_EVENT:
            compiles.append(seconds_)

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    workdir = tempfile.mkdtemp(prefix="chipbench-")   # under TMPDIR
    try:
        t_gen = time.perf_counter()
        generator = cell.module("gen", cell.config["generator"]["kind"])
        manifest = generator.generate(
            workdir, {**cell.config["generator"],
                      **cell.mix.get("fixture", {})}, seed)
        say(f"fixture: {cell.config['name']} seed {seed}: version "
            f"{manifest.version}, {manifest.num_files()} live files, "
            f"{manifest.load_actions} actions and {manifest.log_bytes} "
            f"bytes to a cold load, made in "
            f"{time.perf_counter() - t_gen:.2f} s under {workdir}")

        driver = cell.module("drivers", cell.mix["driver"]).Driver(
            system or DeltaTpu(), manifest)
        schedule = traffic.schedule(cell.mix, seed)
        annotate = jax.profiler.TraceAnnotation

        def run_op(window, params, deadline=float("inf")):
            """One operation. Returns its kind, or, where it ended past
            the deadline and so was the window's last, a function that
            checks it in full once the window has closed."""
            prep = driver.prepare(params)
            before = resource.getrusage(resource.RUSAGE_SELF)
            start_ns, start = time.time_ns(), time.perf_counter()
            with annotate(OP):
                answer = driver.timed(prep)
            end = time.perf_counter()
            after = resource.getrusage(resource.RUSAGE_SELF)
            host = {"cpu_s": (after.ru_utime + after.ru_stime
                              - before.ru_utime - before.ru_stime),
                    "minflt": after.ru_minflt - before.ru_minflt}

            def check(full):
                kind, compared = driver.check(prep, answer, full)
                window.record(kind, start_ns,
                              start_ns + int((end - start) * 1e9),
                              (end - start) * 1e3, compared, host)
                return kind

            if end >= deadline:
                return lambda: check(True)
            return check(deadline == float("inf"))

        warm = Window()
        driver.warm_up(lambda params: run_op(warm, params), schedule)
        warm.say_compared("warm-up")

        trace_dir = os.path.join(workdir, "trace")
        if trace:
            obs.set_trace_mode("on")
            obs.set_device_obs_mode("on")
            obs.reset_trace_buffer()
            obs.reset_device_obs()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)

        window = Window()
        schedule = traffic.schedule(cell.mix, seed)   # from a block's start
        compiles_before = len(compiles)
        setup_s = time.perf_counter() - t0
        window_unix_ns, w0 = time.time_ns(), time.perf_counter()
        with annotate(trace_reduce.WINDOW):
            closing = None
            while closing is None:
                done = run_op(window, next(schedule), w0 + seconds)
                closing = done if callable(done) else None
            window_s = time.perf_counter() - w0
        closing()   # the last operation's answer, compared in full
        in_window = compiles[compiles_before:]
        if trace:
            jax.profiler.stop_trace()

        window.say_compared("window")
        n_ok = sum(op["ok"] for op in window.ops)
        correct = (n_ok == len(window.ops)
                   and all(op["ok"] for op in warm.ops))
        by_kind = collections.defaultdict(list)
        for op in window.ops:
            by_kind[op["kind"]].append(op["latency_ms"])
        kinds = ", ".join(
            f"{len(v)} {kind} (median {statistics.median(v):.1f} ms, "
            f"longest {max(v):.1f} ms)" for kind, v in by_kind.items())
        say(f"window: {window_s:.3f} s, {len(window.ops)} operations: "
            f"{kinds}; {n_ok} correct; set-up {setup_s:.3f} s with "
            f"{sum(compiles[:compiles_before]):.2f} s of backend compile "
            f"or cache retrieval in {compiles_before} programs")
        if in_window:
            raise SystemExit(
                f"chipbench: {len(in_window)} program(s) compiled inside "
                f"the window ({sum(in_window):.2f} s): the warm-up missed "
                "a shape, so this run measures nothing")

        busy = sum(op["latency_ms"] for op in window.ops) / 1e3
        host = [op["host"] for op in window.ops]
        say(f"host: the operations take {100 * busy / window_s:.1f}% of "
            f"the window, the harness between them the rest; per "
            f"operation, median: "
            f"{statistics.median(h['cpu_s'] for h in host):.3f} s of CPU, "
            f"{statistics.median(h['minflt'] for h in host):.0f} "
            f"minor page faults (0 where the host does not count them)")
        say("host, per operation (ms, CPU s, minor faults): " + " ".join(
            f"{op['latency_ms']:.0f}/{op['host']['cpu_s']:.2f}/"
            f"{op['host']['minflt']}" for op in window.ops[:40]))
        stats = [d.memory_stats() or {} for d in jax.devices()]
        # what the chip's allocator handed out at its fullest, and what
        # it holds in reserve for the compiled programs' temporaries,
        # which `peak_bytes_in_use` leaves out (PERF.md, Findings)
        device["memory_peak_bytes"] = max(
            s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
            for s in stats)
        say(f"device: memory_stats of chip 0: {stats[0]}")
        result = {"correct": correct, "attempted": len(window.ops),
                  "failed": len(window.ops) - n_ok, "device": device}

        if not trace:
            found = metrics.end_to_end(
                [op["latency_ms"] for op in window.ops], n_ok, window_s,
                setup_s)
            if set(by_kind) == {"load"}:    # BASELINE.json's "files/sec"
                say(f"actions/s: "
                    f"{found['ops_per_s'][0] * manifest.load_actions:.0f} "
                    "(ops_per_s x the actions a cold load of this table reads)")
        else:
            found, breakdown = per_layer(
                cell, window, window_unix_ns,
                window_unix_ns + int(window_s * 1e9), trace_dir, device)
            result["breakdown"] = breakdown
            device["busy_s"] = found.pop("busy_s")
            device["window_s"] = found.pop("window_s")
        result["metrics"] = {}
        group = "per_layer" if trace else "end_to_end"
        for m in cell.metrics_of(group):
            if m["name"] in found:
                value, unit = found[m["name"]]
                result["metrics"][m["name"]] = {"value": value, "unit": unit}
            else:
                say(f"metric {m['name']}: nothing to read in this run")
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def per_layer(cell, window, w_start, w_end, trace_dir, device):
    """Reduce the traced window (`w_start` to `w_end`, unix ns) and let
    each reader of the cell's per-layer metrics take its number."""
    from delta_tpu import obs

    span_dicts = [s.to_dict() for s in obs.get_finished_spans()]
    span_dicts = [s for s in span_dicts
                  if s["duration_ns"] is not None
                  and w_start <= s["start_unix_ns"] <= w_end]
    in_window = lambda r: w_start <= r["ts_unix_ns"] <= w_end  # noqa: E731
    gates = [r for r in obs.get_gate_records() if in_window(r)]
    dispatches = [r for r in obs.get_dispatch_records() if in_window(r)]
    by_gate = collections.Counter(
        f"{r['gate']}:{r['chosen']}/{r['reason']}" for r in gates)
    by_kernel = collections.Counter(r["kernel"] for r in dispatches)
    say(f"gate decisions in the window: {dict(by_gate)}")
    say(f"device dispatches in the window: {dict(by_kernel)}")

    [xplane] = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    reduced = trace_reduce.reduce_planes(trace_reduce.read_xplane(xplane))
    offset = reduced.window[0] - w_start
    run = TracedRun(window, span_dicts, gates, dispatches, reduced,
                    device["kind"], offset)

    # this thread drives the operations, so its spans nest
    host = [(s["name"], run.to_trace_ns(s["start_unix_ns"]),
             run.to_trace_ns(spans.end_ns(s)))
            for s in span_dicts if s["thread_id"] == threading.get_ident()]
    host += [(f"{OP}:{op['kind']}", run.to_trace_ns(op["start_unix_ns"]),
              run.to_trace_ns(op["end_unix_ns"])) for op in window.ops]
    breakdown = {"device_ops": reduced.device_ops(),
                 "idle_gaps": reduced.idle_by_host(host)}

    found = {"busy_s": reduced.busy_s, "window_s": reduced.window_s}
    for m in cell.metrics_of("per_layer"):
        value = cell.module("layers", m["name"]).read(run)
        if value is not None:
            found[m["name"]] = (value, m["unit"])
    return found, breakdown


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t0)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
