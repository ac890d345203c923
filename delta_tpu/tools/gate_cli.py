"""delta-gate: gate-calibration report over a device-obs gate log.

The dispatch profiler (`obs.device`) journals two record types when
``DELTA_TPU_DEVICE_OBS=on``: ``gate_decision`` (route chosen, inputs,
per-route predicted cost, joined observed cost, signed calibration
error) and ``device_dispatch`` (per-kernel wall time, compile flag,
audited transfer bytes). `obs.dump_gate_log(path)` serializes both as
JSONL; this tool turns that artifact into the answer the link-model
economics actually need: *how wrong are the link model's predictions
on this hardware, per gate, per route?*

Usage::

    delta-gate gate_log.jsonl                 # calibration table
    delta-gate gate_log.jsonl --dispatches    # per-kernel dispatch rollup
    delta-gate gate_log.jsonl --json          # summary as JSON
    python -m delta_tpu.tools.gate_cli ...    # same, without the script
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

from delta_tpu.obs.device import summarize_gates


def load_gate_log(path: str) -> Tuple[List[dict], List[dict]]:
    """Split a dump_gate_log JSONL artifact into (gates, dispatches);
    unparseable lines are skipped (the log may be tail-truncated)."""
    gates: List[dict] = []
    dispatches: List[dict] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("type") == "gate_decision":
                gates.append(rec)
            elif rec.get("type") == "device_dispatch":
                dispatches.append(rec)
    return gates, dispatches


def _fmt_s(v: Optional[float]) -> str:
    if v is None:
        return "-"
    return f"{v * 1e3:.3f}ms" if v < 1 else f"{v:.3f}s"


def render_calibration(summary: Dict[str, dict]) -> str:
    lines = []
    for gate in sorted(summary):
        g = summary[gate]
        lines.append(f"gate {gate}: {g['decisions']} decisions, "
                     f"{g['fallbacks']} fallbacks")
        for route in sorted(g["routes"]):
            r = g["routes"][route]
            err = (f"{r['median_abs_err_pct']:.1f}%"
                   if r["median_abs_err_pct"] is not None else "-")
            lines.append(
                f"  {route:<8} n={r['n']:<4} joined={r['joined']:<4} "
                f"predicted~{_fmt_s(r['median_predicted_s']):<10} "
                f"observed~{_fmt_s(r['median_observed_s']):<10} "
                f"|err|~{err}")
    return "\n".join(lines) if lines else "no gate decisions in log"


def dispatch_rollup(dispatches: List[dict]) -> Dict[str, dict]:
    """Per-kernel aggregate: dispatch/compile counts, median steady-state
    wall, transferred bytes, budget violations."""
    out: Dict[str, dict] = {}
    for d in dispatches:
        k = out.setdefault(d.get("kernel", "?"),
                           {"dispatches": 0, "compiles": 0, "h2d_bytes": 0,
                            "d2h_bytes": 0, "violations": 0, "_walls": []})
        k["dispatches"] += 1
        k["compiles"] += bool(d.get("compile"))
        k["h2d_bytes"] += int(d.get("h2d_bytes", 0))
        k["d2h_bytes"] += int(d.get("d2h_bytes", 0))
        k["violations"] += len(d.get("violations") or [])
        if not d.get("compile"):
            k["_walls"].append(int(d.get("wall_ns", 0)))
    for k in out.values():
        walls = sorted(k.pop("_walls"))
        k["median_steady_wall_ns"] = walls[len(walls) // 2] if walls else None
    return out


def render_dispatches(rollup: Dict[str, dict]) -> str:
    lines = []
    for kernel in sorted(rollup):
        k = rollup[kernel]
        wall = k["median_steady_wall_ns"]
        wall_s = f"{wall / 1e6:.3f}ms" if wall is not None else "-"
        viol = f"  VIOLATIONS={k['violations']}" if k["violations"] else ""
        lines.append(
            f"{kernel:<28} n={k['dispatches']:<5} "
            f"compiles={k['compiles']:<3} steady~{wall_s:<10} "
            f"h2d={k['h2d_bytes']:<12} d2h={k['d2h_bytes']}{viol}")
    return "\n".join(lines) if lines else "no dispatch records in log"


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="delta-gate",
        description="Predicted-vs-observed gate calibration from a "
                    "device-obs gate log (obs.dump_gate_log JSONL).")
    parser.add_argument("log", help="gate log path (JSONL)")
    parser.add_argument("--json", action="store_true",
                        help="print the summary as JSON")
    parser.add_argument("--dispatches", action="store_true",
                        help="per-kernel dispatch rollup instead of the "
                             "calibration table")
    args = parser.parse_args(argv)

    try:
        gates, dispatches = load_gate_log(args.log)
    except OSError as e:
        print(f"delta-gate: {e}", file=sys.stderr)
        return 2

    payload: Dict[str, Any]
    if args.dispatches:
        payload = dispatch_rollup(dispatches)
        print(json.dumps(payload, indent=2) if args.json
              else render_dispatches(payload))
    else:
        payload = summarize_gates(gates)
        print(json.dumps(payload, indent=2) if args.json
              else render_calibration(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
