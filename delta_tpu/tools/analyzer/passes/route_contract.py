"""Route-contract conformance: ``route-contract``.

Every gated device route in this repo carries the same 7-point
contract (docs/architecture.md): a host twin, whole-unit fallback that
increments a cataloged ``*_fallbacks`` counter, an ``obs.device_dispatch``
funnel whose lanes are budgeted (or audited), a ``gate_observation``
calibration join on the host branch, an env override knob, a
capture-conditions stamp for that knob, and an architecture-doc
anchor. Until now the contract was enforced by convention and copied
tests; ROADMAP items 1/2/5/6 each mint new routes, so this pass makes
the contract machine-checked — a route is born conforming or lint
fails, the static twin of the PR 15 runtime transfer-budget audit.

The declarative half lives in ``parallel/gate.py::ROUTES`` (gate name
-> ``RouteSpec(env, fallback_counter, doc_anchor)``); the checker
parses it from the AST (nothing is imported) and cross-checks, per
gate:

1. a ``*_route`` function in the gate module reaches
   ``record_gate_decision`` (directly or through local helpers like
   ``_decide``) with that literal gate name — and every such function
   has a ``ROUTES`` entry (both directions);
2. the route function reads its declared env override knob, by name
   or through the registry (``os.environ.get(ROUTES[gate].env)``),
   itself or in a local helper it calls;
3. the knob is stamped into the obs module's ``CAPTURE_ENV_KEYS``
   (consumed by ``capture_conditions()``);
4. at least one ``device_dispatch(..., gate="<g>")`` funnel exists
   project-wide, and each such site either carries a literal
   ``budget=`` naming a ``transfer_budget.json`` path or sits in a
   function listed under the manifest's budgeted sites /
   ``audited_transfer_sites``;
5. a ``gate_observation("<g>", ...)`` join exists (the host/fallback
   branch prices itself into gate calibration);
6. the declared fallback counter is cataloged in
   ``metric_names.json`` *and* some module creates it with
   ``counter("<name>")`` and calls ``.inc()`` on it;
7. ``docs/architecture.md`` has a heading matching the declared
   anchor slug.

Each finding names the missing contract element. Overrides (fixture
tests):

  DELTA_LINT_GATE_MODULE   rel path of the gate module (default:
                           any scanned ``*/parallel/gate.py``)
  DELTA_LINT_OBS_MODULE    rel path of the obs module holding
                           CAPTURE_ENV_KEYS (default ``*/obs/device.py``)
  DELTA_LINT_ARCH_DOC      path to the architecture doc (default:
                           ``docs/architecture.md`` found by walking up
                           from the gate module)

The budget manifest and metric catalog honor their existing overrides
(``DELTA_LINT_TRANSFER_BUDGET``, ``DELTA_LINT_METRIC_CATALOG``).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from delta_tpu.tools.analyzer.core import (
    Finding,
    ModuleInfo,
    Rule,
    register,
)
from delta_tpu.tools.analyzer.passes._astutil import call_name
from delta_tpu.tools.analyzer.passes.metrics_catalog import (
    _load_catalog as _load_metric_catalog,
)
from delta_tpu.tools.analyzer.passes.transfer_budget import _load_manifest


class _RouteSpec:
    def __init__(self, env: str = "", fallback_counter: str = "",
                 doc_anchor: str = ""):
        self.env = env
        self.fallback_counter = fallback_counter
        self.doc_anchor = doc_anchor


def _gate_module(mods: List[ModuleInfo]) -> Optional[ModuleInfo]:
    want = os.environ.get("DELTA_LINT_GATE_MODULE")
    for mod in mods:
        if want is not None:
            if mod.rel == want:
                return mod
        elif mod.rel.endswith(os.path.join("parallel", "gate.py")):
            return mod
    return None


def _obs_module(mods: List[ModuleInfo]) -> Optional[ModuleInfo]:
    want = os.environ.get("DELTA_LINT_OBS_MODULE")
    for mod in mods:
        if want is not None:
            if mod.rel == want:
                return mod
        elif mod.rel.endswith(os.path.join("obs", "device.py")):
            return mod
    return None


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _parse_routes(tree: ast.Module) -> Tuple[Dict[str, _RouteSpec], int]:
    """The literal ``ROUTES = {...}`` registry -> {gate: spec}, line."""
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            target, value = node.target, node.value
        if not (isinstance(target, ast.Name) and target.id == "ROUTES"
                and isinstance(value, ast.Dict)):
            continue
        out: Dict[str, _RouteSpec] = {}
        for key, val in zip(value.keys, value.values):
            gate = _str_const(key) if key is not None else None
            if gate is None:
                continue
            spec = _RouteSpec()
            if isinstance(val, ast.Call):
                fields = ("env", "fallback_counter", "doc_anchor")
                for i, arg in enumerate(val.args[:3]):
                    setattr(spec, fields[i], _str_const(arg) or "")
                for kw in val.keywords:
                    if kw.arg in fields:
                        setattr(spec, kw.arg, _str_const(kw.value) or "")
            elif isinstance(val, (ast.Tuple, ast.List)):
                fields = ("env", "fallback_counter", "doc_anchor")
                for i, arg in enumerate(val.elts[:3]):
                    setattr(spec, fields[i], _str_const(arg) or "")
            out[gate] = spec
        return out, node.lineno
    return {}, 1


def _local_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def _reaching_record(local: Dict[str, ast.FunctionDef]) -> Set[str]:
    """Local function names that (transitively, within the gate
    module) call ``record_gate_decision``."""
    calls: Dict[str, Set[str]] = {}
    direct: Set[str] = set()
    for name, fn in local.items():
        callees: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                cn = call_name(node)
                if cn is None:
                    continue
                tail = cn.rpartition(".")[2]
                if tail == "record_gate_decision":
                    direct.add(name)
                elif tail in local:
                    callees.add(tail)
        calls[name] = callees
    reaching = set(direct)
    changed = True
    while changed:
        changed = False
        for name, callees in calls.items():
            if name not in reaching and callees & reaching:
                reaching.add(name)
                changed = True
    return reaching


def _module_str_constants(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            val = _str_const(node.value)
            if val is not None:
                out[node.targets[0].id] = val
    return out


# what `_env_name` answers for `ROUTES[<gate>].env`: every registered
# override, whichever gate the subscript names at run time
REGISTRY_ENV = "ROUTES[].env"


def _env_name(arg: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    name = _str_const(arg)
    if name is None and isinstance(arg, ast.Name):
        name = consts.get(arg.id)
    if name is None and isinstance(arg, ast.Attribute) \
            and arg.attr == "env" and isinstance(arg.value, ast.Subscript) \
            and isinstance(arg.value.value, ast.Name) \
            and arg.value.value.id == "ROUTES":
        name = REGISTRY_ENV
    return name


def _env_reads(fn: ast.AST, consts: Dict[str, str],
               local: Optional[Dict[str, ast.FunctionDef]] = None
               ) -> Set[str]:
    """Env-var names this function reads via os.environ.get /
    os.getenv / os.environ[...] (literal or module-constant names, or
    `REGISTRY_ENV`), in its own body or in a `local` function it
    calls."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if local and isinstance(node, ast.Call):
            callee = local.get((call_name(node) or "").rpartition(".")[2])
            if callee is not None and callee is not fn:
                out |= _env_reads(callee, consts)
        if isinstance(node, ast.Call) and node.args:
            cn = call_name(node)
            if cn in ("os.environ.get", "environ.get", "os.getenv",
                      "getenv"):
                name = _env_name(node.args[0], consts)
                if name:
                    out.add(name)
        elif isinstance(node, ast.Subscript):
            if isinstance(node.value, ast.Attribute) \
                    and node.value.attr == "environ":
                name = _env_name(node.slice, consts)
                if name:
                    out.add(name)
            elif isinstance(node.value, ast.Name) \
                    and node.value.id == "environ":
                name = _env_name(node.slice, consts)
                if name:
                    out.add(name)
    return out


def _capture_keys(tree: ast.Module) -> Optional[Set[str]]:
    """The literal CAPTURE_ENV_KEYS tuple, or None when absent."""
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            target, value = node.target, node.value
        if not (isinstance(target, ast.Name)
                and target.id.lstrip("_") == "CAPTURE_ENV_KEYS"):
            continue
        if isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            return {v for v in (_str_const(e) for e in value.elts)
                    if v is not None}
    return None


def _qualname_map(tree: ast.Module) -> Dict[int, str]:
    """id(node) -> qualname of the innermost enclosing function."""
    owner: Dict[int, str] = {}

    def visit(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            q = prefix
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
            owner[id(child)] = q
            visit(child, q)

    visit(tree, "")
    return owner


def _arch_doc_path(gate_mod: ModuleInfo) -> Optional[str]:
    env = os.environ.get("DELTA_LINT_ARCH_DOC")
    if env is not None:
        return env if env and os.path.exists(env) else None
    d = os.path.dirname(os.path.abspath(gate_mod.path))
    for _ in range(6):
        cand = os.path.join(d, "docs", "architecture.md")
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return None


def _doc_slugs(path: str) -> Set[str]:
    slugs: Set[str] = set()
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                if not line.startswith("#"):
                    continue
                text = line.lstrip("#").strip().lower()
                slug = re.sub(r"[^a-z0-9_ -]", "", text)
                slugs.add(re.sub(r" ", "-", slug))
    except OSError:
        pass
    return slugs


@register
class RouteContractRule(Rule):
    id = "route-contract"
    help_anchor = "route-contract"
    description = (
        "gated device route violating the 7-point route contract "
        "(registry entry, env override read, capture-conditions stamp, "
        "budgeted/audited dispatch funnel, gate_observation join, "
        "cataloged+incremented fallback counter, architecture-doc "
        "anchor) declared in parallel/gate.py::ROUTES")

    def check_project(self, mods: List[ModuleInfo]) -> List[Finding]:
        gate_mod = _gate_module(mods)
        if gate_mod is None or gate_mod.tree is None:
            return []
        out: List[Finding] = []
        routes, routes_line = _parse_routes(gate_mod.tree)
        local = _local_functions(gate_mod.tree)
        reaching = _reaching_record(local)
        consts = _module_str_constants(gate_mod.tree)

        # 1. discovery <-> registry, both directions
        discovered: Dict[str, ast.FunctionDef] = {}
        for name, fn in sorted(local.items()):
            if not name.endswith("_route"):
                continue
            gates: Set[str] = set()
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                cn = call_name(node)
                tail = cn.rpartition(".")[2] if cn else ""
                if tail == "record_gate_decision" or tail in reaching:
                    g = _str_const(node.args[0])
                    if g:
                        gates.add(g)
            if not gates:
                out.append(Finding(
                    self.id, gate_mod.rel, fn.lineno, fn.col_offset,
                    f"route function {name}() never reaches "
                    f"record_gate_decision — every routing decision "
                    f"must emit a gate record for calibration"))
                continue
            for g in sorted(gates):
                discovered[g] = fn
                if g not in routes:
                    out.append(Finding(
                        self.id, gate_mod.rel, fn.lineno, fn.col_offset,
                        f"route function {name}() decides gate {g!r} "
                        f"but ROUTES has no {g!r} entry — register the "
                        f"route (env knob, fallback counter, doc "
                        f"anchor) in parallel/gate.py::ROUTES"))
        for g in sorted(set(routes) - set(discovered)):
            out.append(Finding(
                self.id, gate_mod.rel, routes_line, 0,
                f"ROUTES entry {g!r} has no *_route function reaching "
                f"record_gate_decision — stale registry entry"))

        obs_mod = _obs_module(mods)
        capture = (_capture_keys(obs_mod.tree)
                   if obs_mod is not None and obs_mod.tree is not None
                   else None)
        dispatch_gates, observations, counters = self._project_scan(mods)
        manifest = _load_manifest() or {}
        budget_paths = set(manifest.get("paths", {}))
        audited = set(manifest.get("audited_transfer_sites", []))
        audited |= {e.get("site") for e in
                    manifest.get("paths", {}).values()}
        metric_catalog, _ = _load_metric_catalog()
        cataloged_counters = set((metric_catalog or {}).get("counters",
                                                            {}))
        doc = _arch_doc_path(gate_mod)
        slugs = _doc_slugs(doc) if doc else set()

        for g in sorted(routes):
            spec = routes[g]
            fn = discovered.get(g)
            line = fn.lineno if fn is not None else routes_line

            # 2. env override read
            if spec.env and fn is not None and not (
                    {spec.env, REGISTRY_ENV} & _env_reads(fn, consts, local)):
                out.append(Finding(
                    self.id, gate_mod.rel, line, 0,
                    f"route {g!r}: declared env override {spec.env!r} "
                    f"is never read in {fn.name}() — the knob must "
                    f"outrank the economics (tests, bench lanes)"))

            # 3. capture-conditions stamp
            if spec.env and capture is not None \
                    and spec.env not in capture:
                out.append(Finding(
                    self.id, gate_mod.rel, line, 0,
                    f"route {g!r}: env override {spec.env!r} is not in "
                    f"CAPTURE_ENV_KEYS — bench captures with the knob "
                    f"set would be silently incomparable; stamp it "
                    f"into obs/device.py::CAPTURE_ENV_KEYS"))

            # 4. dispatch funnel + budget/audit coverage
            sites = dispatch_gates.get(g, [])
            if not sites:
                out.append(Finding(
                    self.id, gate_mod.rel, line, 0,
                    f"route {g!r}: no device_dispatch funnel anywhere "
                    f"carries gate={g!r} — the device branch runs "
                    f"outside the dispatch profiler and the "
                    f"calibration join"))
            for rel, lineno, qual, budget in sites:
                if budget is not None:
                    if budget_paths and budget not in budget_paths:
                        out.append(Finding(
                            self.id, rel, lineno, 0,
                            f"route {g!r}: dispatch lane budget "
                            f"{budget!r} has no transfer_budget.json "
                            f"path entry"))
                elif audited and f"{rel}::{qual}" not in audited:
                    out.append(Finding(
                        self.id, rel, lineno, 0,
                        f"route {g!r}: gate-tagged dispatch in "
                        f"{qual}() carries no budget= and "
                        f"{rel}::{qual} is not an audited transfer "
                        f"site — budget the lanes or audit the site "
                        f"in transfer_budget.json"))

            # 5. gate_observation calibration join
            if g not in observations:
                out.append(Finding(
                    self.id, gate_mod.rel, line, 0,
                    f"route {g!r}: no gate_observation({g!r}, ...) "
                    f"join anywhere — the host/fallback branch never "
                    f"prices itself into gate calibration"))

            # 6. fallback counter: cataloged and incremented
            c = spec.fallback_counter
            if c:
                if metric_catalog is not None \
                        and c not in cataloged_counters:
                    out.append(Finding(
                        self.id, gate_mod.rel, line, 0,
                        f"route {g!r}: fallback counter {c!r} is not "
                        f"cataloged in metric_names.json"))
                if c not in counters:
                    out.append(Finding(
                        self.id, gate_mod.rel, line, 0,
                        f"route {g!r}: fallback counter {c!r} is "
                        f"never created-and-incremented — the "
                        f"fallback path must bump a counter("
                        f"{c!r}).inc() so operators see route "
                        f"regressions"))

            # 7. architecture-doc anchor
            if spec.doc_anchor and slugs \
                    and not any(spec.doc_anchor in s for s in slugs):
                out.append(Finding(
                    self.id, gate_mod.rel, line, 0,
                    f"route {g!r}: no docs/architecture.md heading "
                    f"matches anchor {spec.doc_anchor!r} — document "
                    f"the route or fix the ROUTES anchor"))
        return out

    @staticmethod
    def _project_scan(mods: List[ModuleInfo]):
        """One walk over every module: gate-tagged dispatch sites,
        gate_observation joins, created-and-incremented counters."""
        dispatch: Dict[str, List[Tuple[str, int, str, Optional[str]]]] = {}
        observations: Set[str] = set()
        counters: Set[str] = set()
        for mod in mods:
            if mod.tree is None:
                continue
            owner = _qualname_map(mod.tree)
            created: Dict[str, str] = {}   # var -> counter name
            incremented: Set[str] = set()  # vars with .inc() calls
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call) \
                        and node.value.args:
                    cn = call_name(node.value)
                    if cn and cn.rpartition(".")[2] == "counter":
                        name = _str_const(node.value.args[0])
                        if name:
                            created[node.targets[0].id] = name
                if not isinstance(node, ast.Call):
                    continue
                cn = call_name(node)
                if cn is None:
                    continue
                tail = cn.rpartition(".")[2]
                if tail == "inc" and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name):
                    incremented.add(node.func.value.id)
                elif tail == "gate_observation" and node.args:
                    g = _str_const(node.args[0])
                    if g:
                        observations.add(g)
                elif tail == "device_dispatch":
                    gate = budget = None
                    for kw in node.keywords:
                        if kw.arg == "gate":
                            gate = _str_const(kw.value)
                        elif kw.arg == "budget":
                            budget = _str_const(kw.value)
                    if gate:
                        qual = owner.get(id(node), "") or "<module>"
                        dispatch.setdefault(gate, []).append(
                            (mod.rel, node.lineno, qual, budget))
            counters.update(name for var, name in created.items()
                            if var in incremented)
        return dispatch, observations, counters
