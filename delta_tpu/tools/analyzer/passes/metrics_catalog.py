"""Metric-name conformance.

``docs/observability.md`` and the Prometheus exposition
(`obs/expose.py`) both promise a stable metric surface; the single
source of truth is ``delta_tpu/resources/metric_names.json`` —
``{"counters": {name: help}, "histograms": {...}, "gauges": {...}}``.
Two rules cross-reference instrument sites and catalog in both
directions, entirely statically (AST census — nothing is imported),
mirroring the error-catalog pass:

- ``metric-uncataloged`` — a ``counter("...")`` / ``histogram("...")``
  / ``gauge("...")`` call whose literal name has no catalog entry
  *under that kind*: a typo'd, forgotten, or kind-mismatched metric;
- ``metric-dead-entry`` — a catalog entry no instrument site produces:
  documentation (and the zero-filled exposition) would advertise a
  series that can never move.

Only string-literal first arguments are censused; dynamic names are
out of scope by design (the repo has none — keeping it that way is
part of what this pass enforces, since a dynamic name would surface as
a dead catalog entry or an uncataloged runtime series).

The catalog path defaults to the installed package resource and can be
overridden with ``DELTA_LINT_METRIC_CATALOG`` (fixture tests and
`obs/expose.py` share the same override).
"""

from __future__ import annotations

import ast
import json
import os
from typing import Dict, List, Optional, Tuple

from delta_tpu.tools.analyzer.core import Finding, ModuleInfo, Rule, register

_KIND_BY_FN = {"counter": "counters", "histogram": "histograms",
               "gauge": "gauges"}

# instrument sites inside the obs package itself are the machinery
# (registry definitions, exposition, tests' fixtures ride through env
# override), not product metrics — EXCEPT the device-execution
# profiler, whose instruments (device.*, gate.*) are product telemetry
# and must stay cataloged like any other module's — likewise the HBM
# resident ledger (hbm.*, plus the subsumed replay/scan gauges)
_EXEMPT_PREFIX = os.path.join("delta_tpu", "obs") + os.sep
_NON_EXEMPT_BASENAMES = {"device.py", "hbm.py"}


def _catalog_path() -> Optional[str]:
    env = os.environ.get("DELTA_LINT_METRIC_CATALOG")
    if env:
        return env
    try:
        import delta_tpu

        path = os.path.join(os.path.dirname(delta_tpu.__file__),
                            "resources", "metric_names.json")
        return path if os.path.exists(path) else None
    except ImportError:  # pragma: no cover - analyzer ships inside it
        return None


def _load_catalog() -> Tuple[Optional[Dict], Optional[str]]:
    path = _catalog_path()
    if path is None:
        return None, None
    with open(path, encoding="utf-8") as f:
        return json.load(f), path


def _catalog_key_line(path: str, key: str) -> int:
    """Locate an entry's line in the JSON text, for clickable
    dead-entry findings (entries are one-per-line by convention)."""
    needle = f'"{key}"'
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, start=1):
            if line.lstrip().startswith(needle):
                return lineno
    return 1


class _MetricScan:
    """One project-wide census of literal instrument-creation sites:
    {kind: {name: [(rel, line), ...]}}."""

    def __init__(self, mods: List[ModuleInfo]):
        self.sites: Dict[str, Dict[str, List[Tuple[str, int]]]] = {
            kind: {} for kind in _KIND_BY_FN.values()}
        for mod in mods:
            if (mod.rel.startswith(_EXEMPT_PREFIX)
                    and os.path.basename(mod.rel)
                    not in _NON_EXEMPT_BASENAMES):
                continue
            self._scan(mod)

    def _scan(self, mod: ModuleInfo):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            kind = _KIND_BY_FN.get(fn_name or "")
            if kind is None:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            self.sites[kind].setdefault(arg.value, []).append(
                (mod.rel, node.lineno))


# identity-compared single-entry cache (same idiom as errors_catalog:
# a later run's fresh ModuleInfos can never falsely hit a stale census)
_CACHE: List[Tuple[List[ModuleInfo], _MetricScan]] = []


def _scan_for(mods: List[ModuleInfo]) -> _MetricScan:
    if _CACHE:
        cached_mods, cached = _CACHE[0]
        if len(cached_mods) == len(mods) \
                and all(a is b for a, b in zip(cached_mods, mods)):
            return cached
    scan = _MetricScan(mods)
    _CACHE[:] = [(list(mods), scan)]
    return scan


@register
class MetricUncatalogedRule(Rule):
    id = "metric-uncataloged"
    description = ("counter()/histogram()/gauge() literal name with no "
                   "entry of that kind in metric_names.json")

    def check_project(self, mods):
        catalog, _path = _load_catalog()
        if catalog is None:
            return ()
        scan = _scan_for(mods)
        findings = []
        for kind in sorted(scan.sites):
            cataloged = catalog.get(kind) or {}
            for name, sites in sorted(scan.sites[kind].items()):
                if name in cataloged:
                    continue
                other = [k for k in _KIND_BY_FN.values()
                         if k != kind and name in (catalog.get(k) or {})]
                hint = (f" (cataloged as a {other[0][:-1]}, not a "
                        f"{kind[:-1]})" if other
                        else " — add it to metric_names.json")
                for rel, line in sites:
                    findings.append(Finding(
                        self.id, rel, line, 0,
                        f"metric {name!r} ({kind[:-1]}) is not in "
                        f"metric_names.json{hint}"))
        return findings


@register
class MetricDeadEntryRule(Rule):
    id = "metric-dead-entry"
    description = ("metric_names.json entry that no instrument site "
                   "produces")

    def check_project(self, mods):
        catalog, path = _load_catalog()
        if catalog is None:
            return ()
        scan = _scan_for(mods)
        # only meaningful when the scanned set holds instrument sites
        # at all (a single-file scan would mark everything dead)
        if not any(scan.sites[k] for k in scan.sites):
            return ()
        findings = []
        for kind in sorted(_KIND_BY_FN.values()):
            produced = scan.sites.get(kind) or {}
            for name in sorted(catalog.get(kind) or {}):
                if name in produced:
                    continue
                findings.append(Finding(
                    self.id, os.path.basename(path),
                    _catalog_key_line(path, name), 0,
                    f"catalog entry {name!r} ({kind[:-1]}) is produced "
                    f"by no instrument site (dead entry — remove it or "
                    f"instrument it)"))
        return findings
