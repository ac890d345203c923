"""Error-catalog conformance.

The reference implementation keeps every user-facing error in
``delta-error-classes.json`` and raises through typed factories; our
equivalent is ``delta_tpu/resources/error_classes.json`` plus
``error_class`` attributes on ``DeltaError`` subclasses. Three rules
cross-reference raise sites and catalog in both directions, entirely
statically (AST census — nothing is imported):

- ``error-uncataloged`` — an ``error_class`` string used in code
  (class default or explicit ``error_class=`` kwarg at a raise site)
  that has no catalog entry: a typo'd or forgotten class;
- ``error-dead-entry`` — a catalog entry no raise site can produce:
  not any raised type's default, not an ancestor default of a raised
  type, not an explicit kwarg anywhere, not a ``FAMILY.SUBCODE`` of a
  produced family, and not in the audited-unproduced allowlist;
- ``error-untyped-raise`` — a raise of an exception type that is
  neither a cataloged Delta error, an allowed builtin/protocol
  exception, a module-internal (``_``-prefixed) control-flow exception,
  nor a re-raised local.

The catalog path defaults to the installed package resource and can be
overridden with ``DELTA_LINT_CATALOG`` (fixture tests use this).
"""

from __future__ import annotations

import ast
import json
import os
from typing import Dict, List, Optional, Set, Tuple

from delta_tpu.tools.analyzer.core import Finding, ModuleInfo, Rule, register

# exceptions that are NOT user-facing Delta errors: builtins for
# internal invariants, storage-protocol exceptions with documented
# contracts, and parse-layer locals (kept in sync with
# tests/test_error_catalog.py, which exercises the same invariant
# dynamically)
_ALLOWED_NON_DELTA = {
    "ValueError", "TypeError", "KeyError", "IndexError", "RuntimeError",
    "IOError", "OSError", "FileNotFoundError", "FileExistsError",
    "NotImplementedError", "StopIteration", "TimeoutError",
    "AssertionError", "ConnectionError", "InterruptedError",
    "AttributeError", "EOFError", "SystemExit", "ImportError",
    "ModuleNotFoundError", "MemoryError", "OverflowError",
    "ZeroDivisionError", "StopAsyncIteration", "KeyboardInterrupt",
    "FileAlreadyExistsError", "PreconditionFailedError",
    "TableAlreadyExistsError", "TableNotInCatalogError",
    "ParseError", "CommitFailedException",
    "DecodeUnsupported", "DynamoDbError", "StandDown",
    # storage-protocol IOError subclasses: StorageRequestError carries
    # the HTTP status the resilience classifier keys on; ChaosError is
    # the chaos harness's injected (always-transient) fault, and the
    # Device* pair is its dispatch-funnel twin (classified transient
    # via the `retryable` attribute)
    "StorageRequestError", "ChaosError",
    "DeviceChaosError", "DeviceResourceExhaustedError",
}

# catalog entries with no statically-attributable raise site, each
# audited: UnsupportedTableFeatureError narrows to the WRITE class
# inside __init__; the merge clause-ordering trio is raised through a
# data-driven loop (error_class=ec) covered by test_merge_clause_validation
_AUDITED_UNPRODUCED = {
    "DELTA_UNSUPPORTED_FEATURES_FOR_WRITE",
    "DELTA_NON_LAST_MATCHED_CLAUSE_OMIT_CONDITION",
    "DELTA_NON_LAST_NOT_MATCHED_CLAUSE_OMIT_CONDITION",
    "DELTA_NON_LAST_NOT_MATCHED_BY_SOURCE_CLAUSE_OMIT_CONDITION",
    "DELTA_ERROR",  # the family root every DeltaError narrows from
}


def _catalog_path() -> Optional[str]:
    env = os.environ.get("DELTA_LINT_CATALOG")
    if env:
        return env
    try:
        import delta_tpu

        path = os.path.join(os.path.dirname(delta_tpu.__file__),
                            "resources", "error_classes.json")
        return path if os.path.exists(path) else None
    except ImportError:  # pragma: no cover - analyzer ships inside it
        return None


class _CatalogScan:
    """One project-wide census shared by the three rules."""

    def __init__(self, mods: List[ModuleInfo]):
        self.defaults: Dict[str, Tuple[str, str, int]] = {}  # cls -> (ec, rel, line)
        self.bases: Dict[str, List[str]] = {}
        self.raised: Dict[str, List[Tuple[str, int]]] = {}   # type -> sites
        self.kwarg_sites: List[Tuple[str, str, int]] = []    # (ec, rel, line)
        for mod in mods:
            self._scan(mod)

    def _scan(self, mod: ModuleInfo):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef):
                self.bases.setdefault(node.name, [])
                for b in node.bases:
                    base = b.attr if isinstance(b, ast.Attribute) else (
                        b.id if isinstance(b, ast.Name) else None)
                    if base:
                        self.bases[node.name].append(base)
                for st in node.body:
                    targets = []
                    if isinstance(st, ast.Assign):
                        targets = st.targets
                    elif isinstance(st, ast.AnnAssign):  # error_class: str = ...
                        targets = [st.target]
                    for tg in targets:
                        if isinstance(tg, ast.Name) \
                                and tg.id == "error_class" \
                                and isinstance(st.value, ast.Constant) \
                                and isinstance(st.value.value, str):
                            self.defaults[node.name] = (
                                st.value.value, mod.rel, st.lineno)
            elif isinstance(node, ast.Raise) and node.exc is not None:
                exc = node.exc
                if isinstance(exc, ast.Call):
                    for kw in exc.keywords:
                        if kw.arg == "error_class" \
                                and isinstance(kw.value, ast.Constant) \
                                and isinstance(kw.value.value, str):
                            self.kwarg_sites.append(
                                (kw.value.value, mod.rel, node.lineno))
                    exc = exc.func
                name = None
                if isinstance(exc, ast.Name):
                    name = exc.id
                elif isinstance(exc, ast.Attribute):
                    name = exc.attr
                if name:
                    self.raised.setdefault(name, []).append(
                        (mod.rel, node.lineno))

    def ancestors(self, cls: str) -> Set[str]:
        out: Set[str] = set()
        queue = list(self.bases.get(cls, ()))
        while queue:
            b = queue.pop()
            if b in out:
                continue
            out.add(b)
            queue.extend(self.bases.get(b, ()))
        return out

    def produced_classes(self) -> Set[str]:
        produced = {ec for ec, _rel, _line in self.kwarg_sites}
        for typ in self.raised:
            if typ in self.defaults:
                produced.add(self.defaults[typ][0])
            for anc in self.ancestors(typ):
                if anc in self.defaults:
                    produced.add(self.defaults[anc][0])
        return produced


# single-entry cache retaining the mods list: identity-compared, so a
# later run's fresh ModuleInfos can never falsely hit a stale census
# (see the matching comment in passes/locks.py)
_CACHE: List[Tuple[List[ModuleInfo], _CatalogScan]] = []


def _scan_for(mods: List[ModuleInfo]) -> _CatalogScan:
    if _CACHE:
        cached_mods, cached = _CACHE[0]
        if len(cached_mods) == len(mods) \
                and all(a is b for a, b in zip(cached_mods, mods)):
            return cached
    scan = _CatalogScan(mods)
    _CACHE[:] = [(list(mods), scan)]
    return scan


def _load_catalog() -> Tuple[Optional[Dict], Optional[str]]:
    path = _catalog_path()
    if path is None:
        return None, None
    with open(path, encoding="utf-8") as f:
        return json.load(f), path


def _catalog_key_line(path: str, key: str) -> int:
    """Locate a top-level key's line in the JSON text, for clickable
    dead-entry findings."""
    needle = f'"{key}"'
    with open(path, encoding="utf-8") as f:
        for lineno, line in enumerate(f, start=1):
            if line.lstrip().startswith(needle):
                return lineno
    return 1


@register
class ErrorUncatalogedRule(Rule):
    id = "error-uncataloged"
    description = ("error_class string (class default or error_class= "
                   "kwarg) with no entry in error_classes.json")

    def check_project(self, mods):
        catalog, _path = _load_catalog()
        if catalog is None:
            return ()
        scan = _scan_for(mods)
        findings = []
        for cls, (ec, rel, line) in sorted(scan.defaults.items()):
            if ec not in catalog:
                findings.append(Finding(
                    self.id, rel, line, 0,
                    f"class {cls} defaults to error_class {ec!r} which "
                    f"is not in error_classes.json"))
        for ec, rel, line in scan.kwarg_sites:
            if ec not in catalog:
                findings.append(Finding(
                    self.id, rel, line, 0,
                    f"raise site uses error_class={ec!r} which is not "
                    f"in error_classes.json"))
        return findings


@register
class ErrorDeadEntryRule(Rule):
    id = "error-dead-entry"
    description = ("catalog entry in error_classes.json that no raise "
                   "site can produce")

    def check_project(self, mods):
        catalog, path = _load_catalog()
        if catalog is None:
            return ()
        scan = _scan_for(mods)
        # only meaningful when the scanned set actually contains the
        # error taxonomy (a single-file scan would mark everything dead)
        if not scan.defaults:
            return ()
        produced = scan.produced_classes()
        findings = []
        for key in sorted(catalog):
            if key in produced or key in _AUDITED_UNPRODUCED:
                continue
            family = key.split(".", 1)[0]
            if family != key and (family in produced
                                  or family in _AUDITED_UNPRODUCED):
                continue  # subcode of a produced family
            findings.append(Finding(
                self.id, os.path.basename(path), _catalog_key_line(path, key),
                0, f"catalog entry {key!r} is produced by no raise site "
                   f"(dead entry — remove it or raise it)"))
        return findings


@register
class ErrorUntypedRaiseRule(Rule):
    id = "error-untyped-raise"
    description = ("raise of an exception type that is neither a "
                   "cataloged Delta error nor an allowed "
                   "builtin/protocol exception")

    def check_project(self, mods):
        scan = _scan_for(mods)
        findings = []
        for typ, sites in sorted(scan.raised.items()):
            if typ in scan.defaults or typ in _ALLOWED_NON_DELTA:
                continue
            if typ.startswith("_"):
                continue  # module-internal control-flow exception
            if not typ[0].isupper():
                continue  # re-raise of a caught local (e, err, exc, ...)
            if typ in scan.bases:
                # defined in the scanned set without error_class: only
                # allowed when some ancestor carries one
                if any(a in scan.defaults for a in scan.ancestors(typ)):
                    continue
            for rel, line in sites:
                findings.append(Finding(
                    self.id, rel, line, 0,
                    f"raise of {typ} which is neither a cataloged "
                    f"DeltaError nor an allowed builtin (add an "
                    f"error_class or extend the allowlist)"))
        return findings
