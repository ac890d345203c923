"""Env-knob census: ``env-knob-uncataloged`` / ``env-knob-dead-entry``
/ ``env-knob-capture-stamp``.

The repo's runtime behavior is steered by ~70 ``DELTA_TPU_*`` /
``DELTA_LINT_*`` env knobs; docs drift and undocumented knobs were the
rule, not the exception. The single source of truth is
``delta_tpu/resources/env_knobs.json`` —
``{"knobs": {NAME: {"default", "modules", "doc", "help",
"capture"?}}}`` — and this pass cross-references read sites and
catalog in both directions, entirely statically (AST census, mirrors
the metric-name pass):

- ``env-knob-uncataloged`` — an ``os.environ.get`` / ``os.getenv`` /
  ``os.environ[...]`` read of a ``DELTA_TPU_*``/``DELTA_LINT_*`` name
  with no catalog entry, or from a module the entry doesn't list
  (drift);
- ``env-knob-dead-entry`` — a catalog entry no module reads, or whose
  ``modules`` list names a scanned module with no read site (docs
  would advertise a knob that does nothing there);
- ``env-knob-capture-stamp`` — an entry marked ``"capture": true``
  (routing-relevant: it changes a gate decision or what a bench
  measured) that is missing from the obs module's
  ``CAPTURE_ENV_KEYS`` stamp tuple — the PR 16 "forgot to stamp
  DELTA_TPU_DEVICE_DECODE" class of omission.

The census resolves three indirections: names held in module-level
string constants (``BASELINE_ENV = "DELTA_LINT_BASELINE"`` then
``os.environ.get(BASELINE_ENV)``), module-local env-helper functions
(a function passing a parameter straight to ``os.environ.get`` —
``_env_num("DELTA_TPU_SERVE_WORKERS", 4)`` is a read site) and the
route registry (``os.environ.get(ROUTES[gate].env)`` in the gate
module reads every ``env`` its literal ``ROUTES`` declares). Dynamic
names beyond that are out of scope by design; a dynamic knob would
surface as a dead catalog entry, which is the point.

The catalog path defaults to the packaged resource and can be
overridden with ``DELTA_LINT_ENV_CATALOG`` (fixture tests); the obs
module holding ``CAPTURE_ENV_KEYS`` honors ``DELTA_LINT_OBS_MODULE``.
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from delta_tpu.tools.analyzer.core import Finding, ModuleInfo, Rule, register
from delta_tpu.tools.analyzer.passes._astutil import call_name
from delta_tpu.tools.analyzer.passes.metrics_catalog import _catalog_key_line
from delta_tpu.tools.analyzer.passes.route_contract import (
    REGISTRY_ENV,
    _env_name,
    _module_str_constants,
    _obs_module,
    _parse_routes,
    _str_const,
)

_KNOB_RE = re.compile(r"^DELTA_(TPU|LINT)_[A-Z0-9_]+$")

_ENV_GETTERS = ("os.environ.get", "environ.get", "os.getenv", "getenv")


def _catalog_path() -> Optional[str]:
    env = os.environ.get("DELTA_LINT_ENV_CATALOG")
    if env:
        return env
    try:
        import delta_tpu
    except ImportError:  # pragma: no cover - analyzer ships inside it
        return None
    path = os.path.join(os.path.dirname(delta_tpu.__file__),
                        "resources", "env_knobs.json")
    return path if os.path.exists(path) else None


def _load_catalog() -> Tuple[Optional[Dict], Optional[str]]:
    path = _catalog_path()
    if path is None:
        return None, None
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f), path
    except (OSError, ValueError):
        return None, None


def _env_helpers(tree: ast.Module) -> Set[str]:
    """Module-local functions that forward a parameter to
    os.environ.get / os.getenv — their literal-name call sites count
    as env reads."""
    out: Set[str] = set()
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = {a.arg for a in node.args.args}
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and sub.args \
                    and call_name(sub) in _ENV_GETTERS \
                    and isinstance(sub.args[0], ast.Name) \
                    and sub.args[0].id in params:
                out.add(node.name)
                break
    return out


class _EnvScan:
    """One project-wide census: {knob: [(rel, line), ...]}."""

    def __init__(self, mods: List[ModuleInfo]):
        self.sites: Dict[str, List[Tuple[str, int]]] = {}
        for mod in mods:
            if mod.tree is not None:
                self._scan(mod)

    def _add(self, name: Optional[str], rel: str, line: int) -> None:
        if name and _KNOB_RE.match(name):
            self.sites.setdefault(name, []).append((rel, line))

    def _scan(self, mod: ModuleInfo) -> None:
        consts = _module_str_constants(mod.tree)
        helpers = _env_helpers(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and node.args:
                cn = call_name(node)
                if cn is None:
                    continue
                arg = node.args[0]
                name = _env_name(arg, consts)
                if cn in _ENV_GETTERS and name == REGISTRY_ENV:
                    # a read through the route registry reads every
                    # override the registry declares
                    for spec in _parse_routes(mod.tree)[0].values():
                        self._add(spec.env, mod.rel, node.lineno)
                elif cn in _ENV_GETTERS:
                    self._add(name, mod.rel, node.lineno)
                elif cn.rpartition(".")[2] in helpers:
                    # helper reads resolve only for literal/const names
                    self._add(_str_const(arg) or name, mod.rel,
                              node.lineno)
            elif isinstance(node, ast.Subscript):
                base = node.value
                is_environ = (isinstance(base, ast.Attribute)
                              and base.attr == "environ") or \
                             (isinstance(base, ast.Name)
                              and base.id == "environ")
                if is_environ:
                    name = _str_const(node.slice)
                    if name is None and isinstance(node.slice, ast.Name):
                        name = consts.get(node.slice.id)
                    self._add(name, mod.rel, node.lineno)


# identity-compared single-entry census cache (same idiom as the
# metric census: fresh ModuleInfos can never falsely hit a stale scan)
_CACHE: List[Tuple[List[ModuleInfo], _EnvScan]] = []


def _scan_for(mods: List[ModuleInfo]) -> _EnvScan:
    if _CACHE:
        cached_mods, cached = _CACHE[0]
        if len(cached_mods) == len(mods) \
                and all(a is b for a, b in zip(cached_mods, mods)):
            return cached
    scan = _EnvScan(mods)
    _CACHE[:] = [(list(mods), scan)]
    return scan


@register
class EnvKnobUncatalogedRule(Rule):
    id = "env-knob-uncataloged"
    help_anchor = "env-knob-census"
    description = (
        "os.environ read of a DELTA_TPU_*/DELTA_LINT_* name with no "
        "resources/env_knobs.json entry, or from a module the entry's "
        "'modules' list doesn't name (drifted catalog)")

    def check_project(self, mods: List[ModuleInfo]) -> List[Finding]:
        catalog, _path = _load_catalog()
        if catalog is None:
            return []
        knobs = catalog.get("knobs") or {}
        scan = _scan_for(mods)
        out: List[Finding] = []
        for name in sorted(scan.sites):
            entry = knobs.get(name)
            if entry is None:
                for rel, line in scan.sites[name]:
                    out.append(Finding(
                        self.id, rel, line, 0,
                        f"env knob {name!r} is not cataloged in "
                        f"env_knobs.json — add name, default, module, "
                        f"and doc anchor"))
                continue
            listed = set(entry.get("modules") or [])
            for rel, line in scan.sites[name]:
                if listed and rel not in listed:
                    out.append(Finding(
                        self.id, rel, line, 0,
                        f"env knob {name!r} is read in {rel} but the "
                        f"catalog lists {sorted(listed)} — update the "
                        f"entry's 'modules' (drifted catalog)"))
        return out


@register
class EnvKnobDeadEntryRule(Rule):
    id = "env-knob-dead-entry"
    help_anchor = "env-knob-census"
    description = (
        "env_knobs.json entry no module reads (dead knob — docs would "
        "advertise a switch wired to nothing), or whose 'modules' list "
        "names a scanned module with no read site")

    def check_project(self, mods: List[ModuleInfo]) -> List[Finding]:
        catalog, path = _load_catalog()
        if catalog is None:
            return []
        scan = _scan_for(mods)
        # only meaningful when the scanned set reads env at all (a
        # single-file fixture scan would mark everything dead)
        if not scan.sites:
            return []
        scanned_rels = {m.rel for m in mods}
        out: List[Finding] = []
        for name in sorted(catalog.get("knobs") or {}):
            entry = catalog["knobs"][name]
            sites = scan.sites.get(name)
            if not sites:
                out.append(Finding(
                    self.id, os.path.basename(path),
                    _catalog_key_line(path, name), 0,
                    f"catalog entry {name!r} is read by no scanned "
                    f"module (dead knob — remove the entry or wire "
                    f"the knob)"))
                continue
            read_rels = {rel for rel, _ in sites}
            for rel in sorted(set(entry.get("modules") or [])):
                if rel in scanned_rels and rel not in read_rels:
                    out.append(Finding(
                        self.id, os.path.basename(path),
                        _catalog_key_line(path, name), 0,
                        f"catalog entry {name!r} lists module {rel} "
                        f"but {rel} never reads it — the 'modules' "
                        f"list drifted"))
        return out


@register
class EnvKnobCaptureStampRule(Rule):
    id = "env-knob-capture-stamp"
    help_anchor = "env-knob-census"
    description = (
        "routing-relevant env knob (env_knobs.json \"capture\": true) "
        "missing from obs/device.py::CAPTURE_ENV_KEYS — bench "
        "captures taken with the knob set would be silently "
        "incomparable")

    def check_project(self, mods: List[ModuleInfo]) -> List[Finding]:
        catalog, _path = _load_catalog()
        if catalog is None:
            return []
        obs_mod = _obs_module(mods)
        if obs_mod is None or obs_mod.tree is None:
            return []
        keys: Optional[Set[str]] = None
        line = 1
        for node in obs_mod.tree.body:
            target = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                target, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) \
                    and node.value is not None:
                target, value = node.target, node.value
            if isinstance(target, ast.Name) \
                    and target.id.lstrip("_") == "CAPTURE_ENV_KEYS" \
                    and isinstance(value, (ast.Tuple, ast.List)):
                keys = {v for v in (_str_const(e) for e in value.elts)
                        if v is not None}
                line = node.lineno
                break
        if keys is None:
            return []
        out: List[Finding] = []
        for name in sorted(catalog.get("knobs") or {}):
            entry = catalog["knobs"][name]
            if entry.get("capture") and name not in keys:
                out.append(Finding(
                    self.id, obs_mod.rel, line, 0,
                    f"routing-relevant env knob {name!r} is not in "
                    f"CAPTURE_ENV_KEYS — add it to the capture-"
                    f"conditions stamp (or drop \"capture\": true "
                    f"from its env_knobs.json entry)"))
        return out
