"""Retry discipline rule: ``retry-discipline``.

All transient-failure handling goes through ``delta_tpu/resilience``
(``RetryPolicy`` / ``io_call``): one classifier decides what is
retryable, one policy owns backoff/jitter/deadline, and the attempt and
sleep counters land in the shared metrics registry. A hand-rolled retry
loop anywhere else is a discipline leak three ways:

- **unbounded or uncoordinated waiting** — ad-hoc ``time.sleep`` inside
  an exception-handling loop invents its own backoff curve, invisible to
  the wall-clock deadline and the breaker state everything else honours;
- **wrong transient set** — local loops re-decide which errors are worth
  retrying and drift from the catalog-driven classifier;
- **invisible retries** — attempts outside the policy never increment
  ``storage.retry.attempts``, so chaos runs and production incidents
  under-report.

Three shapes are flagged:

1. a ``for``/``while`` loop that both handles exceptions and calls
   ``time.sleep`` — the classic grown-by-hand retry/backoff loop;
2. a ``for _ in range(<literal>)`` loop with a ``try`` directly in its
   body — a hard-coded attempt cap that belongs in ``RetryPolicy``
   (env-tunable), not in the call site;
3. a ``try`` whose body dispatches to the device (a ``device_dispatch``
   call, or a route thunk run through ``guarded`` / ``try_device`` /
   ``shed_retry``) with a handler that neither classifies (``classify`` /
   ``is_transient`` / ``route_failed``) nor bumps a counter
   (``.inc(...)``) nor re-raises, in any order or none: a silent
   device fallback that starves the route breaker and under-reports
   exactly the failures the chaos soak injects.

``delta_tpu/resilience/`` itself is exempt by path — the policy is the
one place allowed to own the loop, and the chaos harness's injected
latency is a sleep by design. Audited exceptions elsewhere (e.g. a
protocol-mandated ``Retry-After`` honoured from a server response) carry
a ``# delta-lint: disable=retry-discipline`` pragma.
"""

from __future__ import annotations

import ast
from typing import List, Set

from delta_tpu.tools.analyzer.core import Finding, ModuleInfo, Rule, register
from delta_tpu.tools.analyzer.passes._astutil import call_name


def _sleep_call_names(tree: ast.Module) -> Set[str]:
    """Dotted call names that resolve to ``time.sleep`` in this module:
    ``import time [as t]`` binds ``t.sleep``; ``from time import sleep
    [as s]`` binds ``s``."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "time":
                for a in node.names:
                    if a.name == "sleep":
                        names.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time":
                    names.add(f"{a.asname or a.name}.sleep")
    return names


def _has_handler(loop: ast.AST) -> bool:
    return any(isinstance(n, ast.ExceptHandler) for n in ast.walk(loop))


def _literal_range_loop(node: ast.For) -> bool:
    """``for _ in range(<number literal>)`` (one argument, constant)."""
    it = node.iter
    if not (isinstance(it, ast.Call) and call_name(it) == "range"):
        return False
    return (len(it.args) == 1
            and isinstance(it.args[0], ast.Constant)
            and isinstance(it.args[0].value, int))


# exception-handler calls that count as "the error was classified":
# the classifier itself, and the breaker report that routes through it
# (parallel/gate.py)
_CLASSIFIER_CALLS = {"classify", "is_transient", "route_failed"}

# calls that mark the try body as a device-route dispatch site
_DISPATCH_CALLS = {"device_dispatch", "shed_retry", "guarded", "try_device"}


def _walk_same_scope(stmts):
    """Walk statements without descending into nested function/class/
    lambda scopes — a dispatch inside a nested def is its own call
    site, not this try's."""
    stack = list(stmts)
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Lambda)):
            continue  # also prunes defs that ARE the try's statements
        stack.extend(ast.iter_child_nodes(n))


def _dispatches_device(stmts) -> bool:
    """True when the statements contain a device-dispatch call."""
    return any(
        isinstance(n, ast.Call)
        and (call_name(n) or "").rpartition(".")[2] in _DISPATCH_CALLS
        for n in _walk_same_scope(stmts))


def _handler_disciplined(handler: ast.ExceptHandler) -> bool:
    """A disciplined device-dispatch handler classifies, counts, or
    re-raises (incl. `except X: raise`-style translation)."""
    for n in ast.walk(handler):
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, ast.Call):
            tail = (call_name(n) or "").rpartition(".")[2]
            if tail in _CLASSIFIER_CALLS or tail == "inc":
                return True
    return False


@register
class RetryDisciplineRule(Rule):
    id = "retry-discipline"
    description = ("hand-rolled retry loop (time.sleep inside an "
                   "exception-handling loop, or a literal attempt cap "
                   "around a try) outside delta_tpu/resilience — use "
                   "RetryPolicy/io_call so backoff, deadlines, and "
                   "retry metrics stay unified")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        tree = mod.tree
        if tree is None:
            return []
        rel = mod.rel.replace("\\", "/")
        # the one package allowed to own retry loops and injected sleeps
        if "delta_tpu/resilience/" in rel or rel.startswith("resilience/"):
            return []
        sleep_names = _sleep_call_names(tree)
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            if not _has_handler(node):
                continue
            sleeps = [
                n.lineno for n in ast.walk(node)
                if isinstance(n, ast.Call) and call_name(n) in sleep_names
            ] if sleep_names else []
            if sleeps:
                out.append(Finding(
                    self.id, mod.rel, node.lineno, node.col_offset,
                    f"loop handles exceptions and sleeps (line "
                    f"{sleeps[0]}): hand-rolled retry/backoff — route "
                    f"through resilience.RetryPolicy (or audit + "
                    f"suppress)"))
                continue  # one finding per loop
            if (isinstance(node, ast.For) and _literal_range_loop(node)
                    and any(isinstance(stmt, ast.Try)
                            for stmt in node.body)):
                out.append(Finding(
                    self.id, mod.rel, node.lineno, node.col_offset,
                    "literal attempt cap around a try block: move the "
                    "retry budget into resilience.RetryPolicy (env-"
                    "tunable) instead of hard-coding it (or audit + "
                    "suppress)"))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Try):
                continue
            if not node.handlers or not _dispatches_device(node.body):
                continue
            for handler in node.handlers:
                if not _handler_disciplined(handler):
                    out.append(Finding(
                        self.id, mod.rel, handler.lineno,
                        handler.col_offset,
                        "device_dispatch exception handler neither "
                        "classifies the error (resilience.classify / "
                        "gate.route_failed), bumps a fallback "
                        "counter, nor re-raises: silent device "
                        "fallbacks starve the route breaker — call "
                        "resilience/device_faults.py::guarded (or "
                        "audit + suppress)"))
        return out
