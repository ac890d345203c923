"""delta-lint (delta_tpu.tools.analyzer) fixture tests.

Every rule gets a positive fixture (the rule must fire) and a negative
fixture (the rule must stay silent), exercised through
``analyze_sources`` so nothing touches disk. The error-catalog rules
run against a temp catalog via the ``DELTA_LINT_CATALOG`` override.
The final test is the tier-1 gate: the analyzer over the installed
``delta_tpu`` package must report ZERO unsuppressed findings.
"""

from __future__ import annotations

import json
import os

import pytest

from delta_tpu.tools.analyzer import analyze_paths, analyze_sources
from delta_tpu.tools.analyzer.cli import main as lint_main
from delta_tpu.tools.analyzer.core import all_rules
from delta_tpu.tools.analyzer.report import render_json
from delta_tpu.tools.analyzer.suppress import parse_suppressions


def _rules_fired(report, rule_id):
    return [f for f in report.findings if f.rule == rule_id]


# ------------------------------------------------------------- lock-order


def test_lock_order_cycle_detected():
    src = """
import threading
A = threading.Lock()
B = threading.Lock()

def ab():
    with A:
        with B:
            pass

def ba():
    with B:
        with A:
            pass
"""
    report = analyze_sources({"m.py": src}, rules=["lock-order"])
    found = _rules_fired(report, "lock-order")
    assert found, "opposite-order acquisition must be flagged"
    assert any("cycle" in f.message for f in found)


def test_lock_order_consistent_is_clean():
    src = """
import threading
A = threading.Lock()
B = threading.Lock()

def ab():
    with A:
        with B:
            pass

def ab2():
    with A:
        with B:
            pass
"""
    report = analyze_sources({"m.py": src}, rules=["lock-order"])
    assert not _rules_fired(report, "lock-order")


def test_lock_order_self_deadlock_direct():
    src = """
import threading
L = threading.Lock()

def f():
    with L:
        with L:
            pass
"""
    report = analyze_sources({"m.py": src}, rules=["lock-order"])
    assert any("self-deadlock" in f.message
               for f in _rules_fired(report, "lock-order"))


def test_lock_order_self_deadlock_through_call():
    src = """
import threading
L = threading.Lock()

def inner():
    with L:
        pass

def outer():
    with L:
        inner()
"""
    report = analyze_sources({"m.py": src}, rules=["lock-order"])
    found = _rules_fired(report, "lock-order")
    assert any("inner" in f.message and "self-deadlock" in f.message
               for f in found)


def test_lock_order_rlock_reentry_allowed():
    src = """
import threading
L = threading.RLock()

def f():
    with L:
        with L:
            pass
"""
    report = analyze_sources({"m.py": src}, rules=["lock-order"])
    assert not _rules_fired(report, "lock-order")


# --------------------------------------------------------------- lock-io


def test_lock_io_direct():
    src = """
import threading
L = threading.Lock()

def f(path):
    with L:
        with open(path) as fh:
            return fh.read()
"""
    report = analyze_sources({"m.py": src}, rules=["lock-io"])
    assert any("open" in f.message
               for f in _rules_fired(report, "lock-io"))


def test_lock_io_through_helper_call():
    src = """
import os
import threading
L = threading.Lock()

def helper(path):
    os.unlink(path)

def f(path):
    with L:
        helper(path)
"""
    report = analyze_sources({"m.py": src}, rules=["lock-io"])
    assert any("helper" in f.message
               for f in _rules_fired(report, "lock-io"))


def test_lock_io_outside_lock_is_clean():
    src = """
import threading
L = threading.Lock()

def f(path):
    with open(path) as fh:
        data = fh.read()
    with L:
        return data
"""
    report = analyze_sources({"m.py": src}, rules=["lock-io"])
    assert not _rules_fired(report, "lock-io")


def test_lock_io_instance_lock():
    src = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()

    def f(self, path):
        with self._lock:
            return open(path).read()
"""
    report = analyze_sources({"m.py": src}, rules=["lock-io"])
    assert _rules_fired(report, "lock-io")


# ------------------------------------------------------- global-mutation


def test_global_mutation_outside_lock():
    src = """
import threading
L = threading.Lock()
CACHE = {}

def put(k, v):
    CACHE[k] = v
"""
    report = analyze_sources({"m.py": src}, rules=["global-mutation"])
    assert any("CACHE" in f.message
               for f in _rules_fired(report, "global-mutation"))


def test_global_mutation_under_lock_is_clean():
    src = """
import threading
L = threading.Lock()
CACHE = {}

def put(k, v):
    with L:
        CACHE[k] = v
"""
    report = analyze_sources({"m.py": src}, rules=["global-mutation"])
    assert not _rules_fired(report, "global-mutation")


def test_global_mutation_method_call():
    src = """
import threading
L = threading.Lock()
SEEN = set()

def mark(x):
    SEEN.add(x)
"""
    report = analyze_sources({"m.py": src}, rules=["global-mutation"])
    assert _rules_fired(report, "global-mutation")


def test_global_mutation_ignored_without_locks():
    # a lock-free module is single-threaded by convention: not flagged
    src = """
CACHE = {}

def put(k, v):
    CACHE[k] = v
"""
    report = analyze_sources({"m.py": src}, rules=["global-mutation"])
    assert not _rules_fired(report, "global-mutation")


# ------------------------------------------------------------ jit purity


def test_jit_impure_clock_in_decorated():
    src = """
import time
import jax

@jax.jit
def kernel(x):
    return x * time.time()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert any("time.time" in f.message
               for f in _rules_fired(report, "jit-impure"))


def test_jit_impure_reaches_helpers():
    src = """
import random
import jax

def helper(x):
    return x + random.random()

@jax.jit
def kernel(x):
    return helper(x)
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert any("random.random" in f.message
               for f in _rules_fired(report, "jit-impure"))


def test_jit_impure_call_form_and_partial_alias():
    src = """
import functools
import time
import jax

_fastjit = functools.partial(jax.jit, static_argnames=("n",))

@_fastjit
def kernel(x, n):
    return x + time.time_ns()

def plain(x):
    return jax.jit(inner)(x)

def inner(x):
    return time.perf_counter()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    msgs = " ".join(f.message for f in _rules_fired(report, "jit-impure"))
    assert "time.time_ns" in msgs and "time.perf_counter" in msgs


def test_jit_impure_unreachable_function_is_clean():
    src = """
import time
import jax

@jax.jit
def kernel(x):
    return x + 1

def host_only():
    return time.time()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert not _rules_fired(report, "jit-impure")


def test_jit_impure_nonlocal_mutation():
    src = """
import jax

def build():
    acc = 0
    @jax.jit
    def kernel(x):
        nonlocal acc
        acc += 1
        return x
    return kernel
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert any("nonlocal" in f.message
               for f in _rules_fired(report, "jit-impure"))


def test_jit_sync_item_and_block_until_ready():
    src = """
import jax

@jax.jit
def kernel(x):
    return x.sum().item()

def host(y):
    return y.block_until_ready()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-sync"])
    msgs = " ".join(f.message for f in _rules_fired(report, "jit-sync"))
    assert ".item()" in msgs and "block_until_ready" in msgs


def test_jit_sync_item_outside_jit_is_clean():
    src = """
def host(x):
    return x.sum().item()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-sync"])
    assert not _rules_fired(report, "jit-sync")


def test_jit_impure_shard_map_factory_body():
    # shard_map(make_kernel(...), ...) — the factory and the body it
    # returns are traced code, even without a jit decorator in sight
    src = """
import time
from jax.experimental.shard_map import shard_map

def make_kernel(width):
    def kernel(ops):
        return ops[0] * time.time()
    return kernel

def launch(mesh, ops):
    fn = shard_map(make_kernel(4), mesh=mesh, in_specs=None, out_specs=None)
    return fn(ops)
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert any("time.time" in f.message
               for f in _rules_fired(report, "jit-impure"))


def test_jit_impure_collective_marks_root():
    # a psum can only execute inside traced device code, so the
    # containing function gets purity rules with no visible wrapper
    src = """
import time
from jax import lax

def shard_body(x):
    total = lax.psum(x, "shard")
    return total + time.time()
"""
    report = analyze_sources({"m.py": src}, rules=["jit-impure"])
    assert any("time.time" in f.message
               for f in _rules_fired(report, "jit-impure"))


# ----------------------------------------------------------- error rules


_CATALOG_FIXTURE_SRC = """
class DeltaError(Exception):
    error_class = "DELTA_ERROR"

class FooError(DeltaError):
    error_class = "DELTA_FOO"

def raise_foo():
    raise FooError("boom")

def raise_typo():
    raise FooError("boom", error_class="DELTA_TYPO")

def raise_untyped():
    raise MysteryError("boom")
"""


@pytest.fixture()
def catalog_env(tmp_path, monkeypatch):
    path = tmp_path / "error_classes.json"
    path.write_text(json.dumps({
        "DELTA_ERROR": {"message": ["e"]},
        "DELTA_FOO": {"message": ["f"]},
        "DELTA_DEAD": {"message": ["d"]},
    }, indent=1))
    monkeypatch.setenv("DELTA_LINT_CATALOG", str(path))
    return path


def test_error_uncataloged_kwarg(catalog_env):
    report = analyze_sources({"m.py": _CATALOG_FIXTURE_SRC},
                             rules=["error-uncataloged"])
    found = _rules_fired(report, "error-uncataloged")
    assert any("DELTA_TYPO" in f.message for f in found)
    assert not any("DELTA_FOO" in f.message for f in found)


def test_error_dead_entry(catalog_env):
    report = analyze_sources({"m.py": _CATALOG_FIXTURE_SRC},
                             rules=["error-dead-entry"])
    found = _rules_fired(report, "error-dead-entry")
    assert any("DELTA_DEAD" in f.message for f in found)
    # DELTA_FOO is produced, DELTA_ERROR is the audited family root
    assert not any("DELTA_FOO" in f.message
                   or "'DELTA_ERROR'" in f.message for f in found)


def test_error_untyped_raise(catalog_env):
    report = analyze_sources({"m.py": _CATALOG_FIXTURE_SRC},
                             rules=["error-untyped-raise"])
    found = _rules_fired(report, "error-untyped-raise")
    assert any("MysteryError" in f.message for f in found)
    assert not any("FooError" in f.message for f in found)


def test_error_rules_allow_builtins_and_subclasses(catalog_env):
    src = """
class DeltaError(Exception):
    error_class = "DELTA_ERROR"

class Narrowed(DeltaError):
    pass

def f():
    raise ValueError("builtin ok")

def g():
    raise Narrowed("inherits an error_class ok")
"""
    report = analyze_sources({"m.py": src}, rules=["error-untyped-raise"])
    assert not _rules_fired(report, "error-untyped-raise")


# ---------------------------------------------------------- metric rules


_METRIC_FIXTURE_SRC = """
from delta_tpu import obs

_HITS = obs.counter("demo.hits")
_TYPO = obs.counter("demo.htis")
_DEPTH = obs.gauge("demo.depth")
_WRONG_KIND = obs.counter("demo.depth")
_DYNAMIC = obs.counter("demo." + suffix)
"""


@pytest.fixture()
def metric_catalog_env(tmp_path, monkeypatch):
    path = tmp_path / "metric_names.json"
    path.write_text(json.dumps({
        "counters": {"demo.hits": "Fixture hits.",
                     "demo.dead": "Fixture dead entry."},
        "histograms": {},
        "gauges": {"demo.depth": "Fixture depth."},
    }, indent=1))
    monkeypatch.setenv("DELTA_LINT_METRIC_CATALOG", str(path))
    return path


def test_metric_uncataloged(metric_catalog_env):
    report = analyze_sources({"m.py": _METRIC_FIXTURE_SRC},
                             rules=["metric-uncataloged"])
    found = _rules_fired(report, "metric-uncataloged")
    assert any("demo.htis" in f.message for f in found)
    # cataloged names under the right kind stay silent
    assert not any("demo.hits" in f.message for f in found)


def test_metric_uncataloged_kind_mismatch(metric_catalog_env):
    report = analyze_sources({"m.py": _METRIC_FIXTURE_SRC},
                             rules=["metric-uncataloged"])
    found = _rules_fired(report, "metric-uncataloged")
    mismatch = [f for f in found if "demo.depth" in f.message]
    assert mismatch and "cataloged as a gauge" in mismatch[0].message


def test_metric_dead_entry(metric_catalog_env):
    report = analyze_sources({"m.py": _METRIC_FIXTURE_SRC},
                             rules=["metric-dead-entry"])
    found = _rules_fired(report, "metric-dead-entry")
    assert any("demo.dead" in f.message for f in found)
    assert not any("demo.hits" in f.message for f in found)


def test_metric_rules_ignore_dynamic_names(metric_catalog_env):
    src = """
from delta_tpu import obs

def make(name):
    return obs.counter("demo." + name)
"""
    report = analyze_sources({"m.py": src}, rules=["metric-uncataloged"])
    assert not _rules_fired(report, "metric-uncataloged")


def test_metric_dead_entry_silent_without_sites(metric_catalog_env):
    # a scan over files with no instrument sites at all must not mark
    # the whole catalog dead (e.g. linting a single non-metric module)
    report = analyze_sources({"m.py": "def f():\n    return 1\n"},
                             rules=["metric-dead-entry"])
    assert not _rules_fired(report, "metric-dead-entry")


# ------------------------------------------------------- except hygiene


def test_except_swallow_flagged():
    src = """
def f():
    try:
        work()
    except Exception:
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert _rules_fired(report, "except-swallow")


def test_except_swallow_bare_except_flagged():
    src = """
def f():
    try:
        work()
    except:
        return None
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert _rules_fired(report, "except-swallow")


@pytest.mark.parametrize("body", [
    "raise",
    "log.warning('failed: %s', e)",
    "print(e)",
    "handle(e)",
])
def test_except_swallow_negative_forms(body):
    src = f"""
import logging
log = logging.getLogger(__name__)

def f():
    try:
        work()
    except Exception as e:
        {body}
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert not _rules_fired(report, "except-swallow")


def test_except_swallow_batch_member_outcome_shape():
    """Pins the group-commit batch-partitioning contract: a member's
    ConcurrentTransactionError must become a typed per-member outcome
    (used handler) — a broad except that silently drops it would turn
    a real conflict into a phantom commit. The GOOD shape mirrors
    `groupcommit._emit_inner`; the BAD shape (outcome assigned without
    using the exception) must be flagged."""
    good = """
def partition(batch, cs):
    for m in batch:
        try:
            cs.resolve(m.txn)
        except ConcurrentTransactionError as e:
            m.outcome = reject(e)
            continue
        m.outcome = accept(m)
"""
    report = analyze_sources({"m.py": good}, rules=["except-swallow"])
    assert not _rules_fired(report, "except-swallow")

    bad = """
def partition(batch, cs):
    for m in batch:
        try:
            cs.resolve(m.txn)
        except Exception:
            continue
        m.outcome = accept(m)
"""
    report = analyze_sources({"m.py": bad}, rules=["except-swallow"])
    assert _rules_fired(report, "except-swallow")


def test_except_swallow_narrow_type_is_clean():
    src = """
def f():
    try:
        work()
    except (OSError, ValueError):
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert not _rules_fired(report, "except-swallow")


def test_mutable_default_flagged():
    src = """
def f(x, acc=[]):
    acc.append(x)
    return acc

def g(*, opts={}):
    return opts

def h(s=set()):
    return s
"""
    report = analyze_sources({"m.py": src}, rules=["mutable-default"])
    assert len(_rules_fired(report, "mutable-default")) == 3


def test_mutable_default_none_is_clean():
    src = """
def f(x, acc=None, n=3, name="x", t=()):
    return acc
"""
    report = analyze_sources({"m.py": src}, rules=["mutable-default"])
    assert not _rules_fired(report, "mutable-default")


# --------------------------------------------------------- undefined-name


def test_undefined_name_flagged():
    src = """
def f(x):
    return missing_helper(x)
"""
    report = analyze_sources({"m.py": src}, rules=["undefined-name"])
    assert any("missing_helper" in f.message
               for f in _rules_fired(report, "undefined-name"))


def test_undefined_name_negative():
    src = """
import os

def helper(x):
    return x

def f(x):
    return helper(os.fspath(x)) + len([])
"""
    report = analyze_sources({"m.py": src}, rules=["undefined-name"])
    assert not _rules_fired(report, "undefined-name")


def test_undefined_name_star_import_skipped():
    src = """
from os.path import *

def f(x):
    return join(x, anything_at_all(x))
"""
    report = analyze_sources({"m.py": src}, rules=["undefined-name"])
    assert not _rules_fired(report, "undefined-name")


# ----------------------------------------------------------- suppression


def test_line_suppression():
    src = """
def f():
    try:
        work()
    except Exception:  # delta-lint: disable=except-swallow — audited
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert not report.findings
    assert len(report.suppressed) == 1
    assert report.suppressed[0].rule == "except-swallow"


def test_standalone_comment_suppresses_next_code_line():
    src = """
def f():
    try:
        work()
    # delta-lint: disable=except-swallow (audited: fixture —
    # rationale may span multiple comment lines)
    except Exception:
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert not report.findings and len(report.suppressed) == 1


def test_file_level_suppression_and_disable_all():
    src = """# delta-lint: file-disable=except-swallow
def f():
    try:
        work()
    except Exception:
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["except-swallow"])
    assert not report.findings and report.suppressed

    per_line, file_level = parse_suppressions(
        "x = 1  # delta-lint: disable=all\n")
    assert "all" in per_line[1] and not file_level


def test_suppression_does_not_leak_to_other_rules():
    src = """
def f(acc=[]):
    try:
        work()
    except Exception:  # delta-lint: disable=jit-impure
        pass
"""
    report = analyze_sources({"m.py": src},
                             rules=["except-swallow", "mutable-default"])
    assert _rules_fired(report, "except-swallow")
    assert _rules_fired(report, "mutable-default")


def test_parse_error_reported():
    report = analyze_sources({"m.py": "def broken(:\n"})
    assert any(f.rule == "parse-error" for f in report.findings)


# ------------------------------------------------------------------- CLI


def test_cli_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in ("lock-order", "lock-io", "jit-impure",
                    "error-uncataloged", "except-swallow",
                    "undefined-name"):
        assert rule_id in out


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    good = tmp_path / "good.py"
    good.write_text("def f(x=None):\n    return x\n")

    assert lint_main([str(good)]) == 0
    assert lint_main([str(bad)]) == 1
    assert lint_main([str(tmp_path / "missing.py")]) == 2
    assert lint_main([str(good), "--rules", "not-a-rule"]) == 2
    capsys.readouterr()


def test_cli_json_format(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    assert lint_main([str(bad), "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    results = doc["runs"][0]["results"]
    assert results and results[0]["ruleId"] == "mutable-default"
    assert doc["runs"][0]["summary"]["findings"] == len(results)


def test_render_json_roundtrip():
    report = analyze_sources({"m.py": "def f(x=[]):\n    return x\n"})
    doc = json.loads(render_json(report))
    assert doc["runs"][0]["tool"]["driver"]["name"] == "delta-lint"


def test_every_registered_rule_has_fixture_coverage():
    """Each of the analysis passes must be exercised above; this
    guards the registry against silently-unregistered rules."""
    expected = {
        "lock-order", "lock-io", "global-mutation",          # locks
        "jit-impure", "jit-sync",                            # purity
        "error-uncataloged", "error-dead-entry",
        "error-untyped-raise",                               # catalog
        "metric-uncataloged", "metric-dead-entry",           # metrics
        "except-swallow", "mutable-default",                 # hygiene
        "undefined-name",                                    # imports
        "obs-span-leak",                                     # obs
        "threadpool-discipline",                             # threads
        "retry-discipline",                                  # retry
        "handler-discipline",                                # serve
        "shared-state-race",                                 # races
        "transfer-budget", "transfer-unbudgeted",            # budget
        "unprofiled-dispatch",                               # device obs
        "resident-ledger-discipline",                        # hbm ledger
        "route-contract",                                    # routes
        "recompile-risk",                                    # recompile
        "env-knob-uncataloged", "env-knob-dead-entry",
        "env-knob-capture-stamp",                            # env census
    }
    assert set(all_rules()) == expected


# ----------------------------------------------------- obs-span-leak


def test_obs_span_leak_bare_call_flagged():
    src = """
from delta_tpu import obs

def load():
    s = obs.span("snapshot.load")  # never entered
    do_work()
    return s
"""
    report = analyze_sources({"m.py": src}, rules=["obs-span-leak"])
    found = _rules_fired(report, "obs-span-leak")
    assert len(found) == 1 and found[0].line == 5


def test_obs_span_leak_from_import_alias_flagged():
    src = """
from delta_tpu.obs import span as _span

def load():
    ctx = _span("snapshot.load")
    with ctx:
        pass
"""
    report = analyze_sources({"m.py": src}, rules=["obs-span-leak"])
    assert _rules_fired(report, "obs-span-leak"), \
        "span bound to a variable first is still a leak (parent is read " \
        "at __enter__, not at construction)"


def test_obs_span_leak_raw_perf_counter_flagged():
    src = """
import time
from delta_tpu import obs

def load():
    t0 = time.perf_counter_ns()
    with obs.span("snapshot.load"):
        pass
    return time.perf_counter_ns() - t0
"""
    report = analyze_sources({"m.py": src}, rules=["obs-span-leak"])
    assert len(_rules_fired(report, "obs-span-leak")) == 2


def test_obs_span_leak_negative():
    # with-statement spans and perf_counter_ns in UNinstrumented
    # modules are both fine
    clean = """
from delta_tpu import obs

def load():
    with obs.span("snapshot.load", table="/t") as sp:
        sp.set_attr("version", 3)
"""
    uninstrumented = """
import time

def bench():
    t0 = time.perf_counter_ns()
    return time.perf_counter_ns() - t0
"""
    report = analyze_sources(
        {"a.py": clean, "b.py": uninstrumented}, rules=["obs-span-leak"])
    assert not report.findings


def test_obs_span_leak_suppression_pragma():
    src = """
import time
from delta_tpu import obs

def measure():
    # delta-lint: disable=obs-span-leak
    t0 = time.perf_counter_ns()
    return t0
"""
    report = analyze_sources({"m.py": src}, rules=["obs-span-leak"])
    assert not report.findings and report.suppressed


# ------------------------------------------ threadpool-discipline rule


def test_threadpool_direct_construction_flagged():
    src = """
from concurrent.futures import ThreadPoolExecutor

def load(paths):
    with ThreadPoolExecutor(max_workers=8) as ex:
        return list(ex.map(len, paths))
"""
    report = analyze_sources({"m.py": src},
                             rules=["threadpool-discipline"])
    assert len(report.findings) == 1
    assert "shared_pool" in report.findings[0].message


def test_threadpool_aliased_imports_flagged():
    src = """
import concurrent.futures as cf
from concurrent import futures

def a():
    return cf.ThreadPoolExecutor(2)

def b():
    return futures.ThreadPoolExecutor(2)
"""
    report = analyze_sources({"m.py": src},
                             rules=["threadpool-discipline"])
    assert len(report.findings) == 2


def test_threadpool_threads_module_exempt():
    src = """
from concurrent.futures import ThreadPoolExecutor

POOL = ThreadPoolExecutor(max_workers=4)
"""
    report = analyze_sources({"delta_tpu/utils/threads.py": src},
                             rules=["threadpool-discipline"])
    assert not report.findings


def test_threadpool_shared_pool_usage_clean():
    src = """
from delta_tpu.utils.threads import parallel_map, shared_pool

def load(paths):
    return parallel_map(len, paths) + shared_pool().map(len, paths)
"""
    report = analyze_sources({"m.py": src},
                             rules=["threadpool-discipline"])
    assert not report.findings


def test_threadpool_suppression_pragma():
    src = """
from concurrent.futures import ThreadPoolExecutor

def oneshot():
    # delta-lint: disable=threadpool-discipline (audited: example)
    with ThreadPoolExecutor(max_workers=1) as ex:
        return ex.submit(int).result()
"""
    report = analyze_sources({"m.py": src},
                             rules=["threadpool-discipline"])
    assert not report.findings and report.suppressed


# ---------------------------------------------- retry-discipline rule


def test_retry_sleep_in_exception_loop_flagged():
    src = """
import time

def fetch(op):
    delay = 0.1
    while True:
        try:
            return op()
        except IOError:
            time.sleep(delay)
            delay *= 2
"""
    report = analyze_sources({"m.py": src}, rules=["retry-discipline"])
    found = _rules_fired(report, "retry-discipline")
    assert found and "RetryPolicy" in found[0].message


def test_retry_sleep_from_import_alias_flagged():
    src = """
from time import sleep as snooze

def fetch(op):
    for _ in range(1000):
        try:
            return op()
        except OSError:
            snooze(0.5)
"""
    report = analyze_sources({"m.py": src}, rules=["retry-discipline"])
    assert _rules_fired(report, "retry-discipline")


def test_retry_literal_attempt_cap_flagged():
    src = """
def fetch(op):
    for attempt in range(3):
        try:
            return op()
        except IOError:
            if attempt == 2:
                raise
"""
    report = analyze_sources({"m.py": src}, rules=["retry-discipline"])
    found = _rules_fired(report, "retry-discipline")
    assert found and "attempt cap" in found[0].message


def test_retry_discipline_negatives_clean():
    # sleep without exception handling (a poller), exception handling
    # without sleep or a literal cap (a scan loop), and a data loop
    # over range with no try — none are retry loops
    src = """
import time

def poll(ready):
    while not ready():
        time.sleep(0.1)

def scan(items, f):
    out = []
    for it in items:
        try:
            out.append(f(it))
        except ValueError:
            pass
    return out

def fill(n):
    return [0 for _ in range(8)]
"""
    report = analyze_sources({"m.py": src}, rules=["retry-discipline"])
    assert not _rules_fired(report, "retry-discipline")


def test_retry_discipline_resilience_package_exempt():
    src = """
import time

def call(fn):
    while True:
        try:
            return fn()
        except IOError:
            time.sleep(0.05)
"""
    report = analyze_sources(
        {"delta_tpu/resilience/policy.py": src},
        rules=["retry-discipline"])
    assert not _rules_fired(report, "retry-discipline")


def test_retry_discipline_suppression_pragma():
    src = """
import time

def fetch(op):
    # delta-lint: disable=retry-discipline (audited: example)
    while True:
        try:
            return op()
        except IOError:
            time.sleep(0.1)
"""
    report = analyze_sources({"m.py": src}, rules=["retry-discipline"])
    assert not report.findings and report.suppressed


def test_retry_silent_device_fallback_flagged():
    # third shape: a device-dispatch try whose handler swallows the
    # error without classifying, counting, or re-raising
    src = """
def read(route, thunk):
    from delta_tpu.resilience import device_faults
    try:
        return device_faults.shed_retry("decode", thunk)
    except Exception:
        return None

def read_guarded(thunk, ctr):
    from delta_tpu.resilience import device_faults
    try:
        return device_faults.guarded("decode", thunk, ctr).value
    except Exception:
        return None
"""
    report = analyze_sources({"delta_tpu/x.py": src},
                             rules=["retry-discipline"])
    found = _rules_fired(report, "retry-discipline")
    assert len(found) == 2
    assert all("starve the route breaker" in f.message for f in found)


def test_retry_dispatch_handler_each_discipline_clean():
    # classify, count, and re-raise each individually satisfy the
    # contract (incl. dotted/method spellings)
    src = """
def a(thunk, gate):
    from delta_tpu.parallel import gate as g
    try:
        return device_dispatch("k", thunk)
    except Exception as e:
        g.route_failed(gate, e)
        return None

def b(thunk, ctr):
    try:
        return device_dispatch("k", thunk)
    except Exception:
        ctr.inc()
        return None

def c(thunk):
    from delta_tpu.errors import DeltaError
    try:
        return device_dispatch("k", thunk)
    except Exception as e:
        raise DeltaError(str(e)) from e

def d(thunk):
    from delta_tpu.resilience import device_faults
    try:
        return device_faults.shed_retry("skip", thunk)
    except Exception as e:
        if route_failed("skip", e) != "transient":
            raise
        return None

def e(thunk, ctr):
    from delta_tpu.resilience import device_faults
    try:
        return device_faults.guarded("skip", thunk, ctr).value
    except FileNotFoundError as e:
        raise LogCorruptedError(str(e))
"""
    report = analyze_sources({"delta_tpu/x.py": src},
                             rules=["retry-discipline"])
    assert not _rules_fired(report, "retry-discipline")


def test_retry_dispatch_in_nested_scope_not_attributed():
    # a dispatch inside a nested def is its own call site — the outer
    # try that merely BUILDS the closure is not a dispatch site
    src = """
def plan(thunk):
    try:
        def later():
            return device_dispatch("k", thunk)
        return later
    except Exception:
        return None
"""
    report = analyze_sources({"delta_tpu/x.py": src},
                             rules=["retry-discipline"])
    assert not _rules_fired(report, "retry-discipline")


def test_retry_silent_fallback_resilience_path_exempt():
    src = """
def absorb(thunk):
    try:
        return device_dispatch("k", thunk)
    except Exception:
        return None
"""
    report = analyze_sources(
        {"delta_tpu/resilience/device_faults.py": src},
        rules=["retry-discipline"])
    assert not _rules_fired(report, "retry-discipline")


# ------------------------------------------------- handler-discipline


def test_handler_discipline_raw_thread_flagged():
    src = """
import threading

def handle(conn):
    t = threading.Thread(target=lambda: None, daemon=True)
    t.start()
"""
    report = analyze_sources({"delta_tpu/serve/handlers.py": src},
                             rules=["handler-discipline"])
    fired = _rules_fired(report, "handler-discipline")
    assert len(fired) == 1 and "pool.spawn" in fired[0].message


def test_handler_discipline_from_import_thread_flagged():
    src = """
from threading import Thread as T

def accept_loop(listener):
    while True:
        T(target=listener.accept).start()
"""
    report = analyze_sources({"delta_tpu/serve/server2.py": src},
                             rules=["handler-discipline"])
    assert _rules_fired(report, "handler-discipline")


def test_handler_discipline_pool_module_exempt():
    src = """
import threading

def spawn(name, target):
    t = threading.Thread(target=target, daemon=True)
    t.start()
    return t
"""
    report = analyze_sources({"delta_tpu/serve/pool.py": src},
                             rules=["handler-discipline"])
    assert not _rules_fired(report, "handler-discipline")


def test_handler_discipline_outside_serve_exempt():
    """The rule is scoped: the same shapes elsewhere in the tree are the
    business of threadpool-discipline / resilience defaults."""
    src = """
import threading
from delta_tpu.resilience import io_call

def elsewhere(store):
    threading.Thread(target=lambda: None).start()
    return io_call("file", lambda: store.read("p"))
"""
    report = analyze_sources({"delta_tpu/storage/other.py": src},
                             rules=["handler-discipline"])
    assert not _rules_fired(report, "handler-discipline")


def test_handler_discipline_naked_io_call_flagged():
    src = """
from delta_tpu.resilience import io_call

def refresh(store):
    return io_call("file", lambda: store.list_from("p"))
"""
    report = analyze_sources({"delta_tpu/serve/cachey.py": src},
                             rules=["handler-discipline"])
    fired = _rules_fired(report, "handler-discipline")
    assert len(fired) == 1 and "deadline" in fired[0].message


def test_handler_discipline_scoped_io_call_ok():
    src = """
from delta_tpu.resilience import deadline_scope, io_call

def refresh(store, budget_s):
    with deadline_scope(budget_s):
        return io_call("file", lambda: store.list_from("p"))
"""
    report = analyze_sources({"delta_tpu/serve/cachey.py": src},
                             rules=["handler-discipline"])
    assert not _rules_fired(report, "handler-discipline")


def test_handler_discipline_module_alias_io_call_flagged():
    src = """
from delta_tpu import resilience

def refresh(store):
    return resilience.io_call("file", lambda: store.read("p"))
"""
    report = analyze_sources({"delta_tpu/serve/cachey.py": src},
                             rules=["handler-discipline"])
    assert _rules_fired(report, "handler-discipline")


def test_handler_discipline_suppression_pragma():
    src = """
import threading

def special(target):
    # delta-lint: disable=handler-discipline (audited: example)
    return threading.Thread(target=target)
"""
    report = analyze_sources({"delta_tpu/serve/x.py": src},
                             rules=["handler-discipline"])
    assert not report.findings and report.suppressed


# ----------------------------------------------- shared-state-race


RACE = ["shared-state-race"]


def test_race_rmw_from_two_thread_roots_flagged():
    src = """
import threading

class Stats:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1

STATS = Stats()

def worker_a():
    STATS.bump()

def worker_b():
    STATS.bump()

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    fired = _rules_fired(report, "shared-state-race")
    assert fired and "Stats.n" in fired[0].message
    assert "thread-root sites" in fired[0].message


def test_race_owning_lock_held_two_call_levels_silent():
    """Held-locks context must propagate interprocedurally: the lock is
    taken two call frames above the mutation."""
    src = """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        with self._lock:
            self._bump_locked()

    def _bump_locked(self):
        self._inc()

    def _inc(self):
        self.n += 1

STATS = Stats()

def worker_a():
    STATS.bump()

def worker_b():
    STATS.bump()

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert not report.findings


def test_race_one_unlocked_path_still_flagged():
    """Meet-over-paths: a lock held on only ONE of two paths from a
    thread root does not protect the mutation."""
    src = """
import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        with self._lock:
            self._inc()

    def bump_unsafe(self):
        self._inc()

    def _inc(self):
        self.n += 1

STATS = Stats()

def worker_a():
    STATS.bump()

def worker_b():
    STATS.bump_unsafe()

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert _rules_fired(report, "shared-state-race")


def test_race_partial_thread_target_resolved():
    src = """
import functools
import threading

class Stats:
    def __init__(self):
        self.n = 0

    def bump(self, k):
        self.n += k

STATS = Stats()

def hit(k=1):
    STATS.bump(k)

def main():
    threading.Thread(target=functools.partial(hit, 2)).start()
    threading.Thread(target=hit).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert _rules_fired(report, "shared-state-race")


def test_race_dict_dispatch_reachability():
    src = """
import threading

LOG = []

def do_a():
    LOG.append("a")

def do_b():
    LOG.append("b")

HANDLERS = {"a": do_a, "b": do_b}

def dispatch(key):
    HANDLERS[key]()

def serve():
    while True:
        threading.Thread(target=dispatch).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    fired = _rules_fired(report, "shared-state-race")
    assert len(fired) == 2  # both dispatch values reached
    assert all("LOG" in f.message for f in fired)


def test_race_executor_submit_is_multi_root():
    """A single submit-in-a-loop site implies concurrency on its own:
    no second root needed."""
    src = """
from concurrent.futures import ThreadPoolExecutor

class Stats:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1

STATS = Stats()

def worker():
    STATS.bump()

def main(items):
    ex = ThreadPoolExecutor(4)
    for _ in items:
        ex.submit(worker)
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert _rules_fired(report, "shared-state-race")


def test_race_obs_wrap_is_thread_root():
    src = """
from delta_tpu import obs

class Stats:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1

STATS = Stats()

def worker():
    STATS.bump()

def main():
    return obs.wrap(worker)
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert _rules_fired(report, "shared-state-race")


def test_race_plain_store_exempt():
    """Attribute rebinding is atomic publication under the GIL — the
    idiomatic lock-free hand-off stays silent."""
    src = """
import threading

class Holder:
    def __init__(self):
        self.latest = None

    def publish(self, x):
        self.latest = x

H = Holder()

def worker_a():
    H.publish(1)

def worker_b():
    H.publish(2)

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert not report.findings


def test_race_threadsafe_attr_type_exempt():
    src = """
import queue
import threading

class Mailbox:
    def __init__(self):
        self.q = queue.Queue()

    def deliver(self, x):
        self.q.update(x)

M = Mailbox()

def worker_a():
    M.deliver(1)

def worker_b():
    M.deliver(2)

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert not report.findings


def test_race_init_mutations_exempt():
    src = """
import threading

class Cache:
    def __init__(self):
        self.store = {}
        self.store["warm"] = True

def worker_a():
    Cache()

def worker_b():
    Cache()

def main():
    threading.Thread(target=worker_a).start()
    threading.Thread(target=worker_b).start()
"""
    report = analyze_sources({"m.py": src}, rules=RACE)
    assert not report.findings


# ------------------------------------------------- transfer budget


def _write_budget(tmp_path, monkeypatch, paths, modules=(), audited=()):
    doc = {"modules": list(modules),
           "audited_transfer_sites": list(audited), "paths": paths}
    p = tmp_path / "budget.json"
    p.write_text(json.dumps(doc))
    monkeypatch.setenv("DELTA_LINT_TRANSFER_BUDGET", str(p))


_SHIP_ENTRY = {
    "site": "pkg/ship.py::ship",
    "unit": "slot",
    "budget_bytes_per_unit": 8,
    "device_put_exhaustive": True,
    "lanes": [
        {"name": "idx", "kind": "dtype", "dtype": "int32"},
        {"name": "val", "kind": "dtype", "dtype": "uint32"},
    ],
}

_SHIP_SRC = """
import numpy as np
import jax

def ship(n):
    idx = np.full((4, n), 0, np.int32)
    val = np.zeros((4, n), np.uint32)
    jax.device_put(idx)
    jax.device_put(val)
    return idx, val
"""


def test_budget_in_budget_site_clean(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"ship": _SHIP_ENTRY})
    report = analyze_sources({"pkg/ship.py": _SHIP_SRC},
                             rules=["transfer-budget"])
    assert not report.findings


def test_budget_widened_dtype_flagged_with_diff(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"ship": _SHIP_ENTRY})
    src = _SHIP_SRC.replace("np.int32", "np.int64")
    report = analyze_sources({"pkg/ship.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "widened" in fired[0].message
    assert "int64" in fired[0].message and "int32" in fired[0].message
    assert "8 B/unit" in fired[0].message \
        and "4 B/unit" in fired[0].message


def test_budget_extra_device_put_lane_flagged(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"ship": _SHIP_ENTRY})
    src = _SHIP_SRC.replace(
        "    return idx, val",
        "    extra = np.zeros(n, np.uint8)\n"
        "    jax.device_put(extra)\n"
        "    return idx, val")
    report = analyze_sources({"pkg/ship.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "not a budgeted lane" in fired[0].message


def test_budget_bitplane_lane_clean(tmp_path, monkeypatch):
    entry = {
        "site": "pkg/plane.py::route",
        "budget_bytes_per_unit": 0.25,
        "lanes": [{"name": "flag_words", "kind": "bitplane"},
                  {"name": "add_words", "kind": "bitplane"}],
    }
    src = """
import numpy as np

def route(flags, adds):
    flag_words = np.packbits(flags, axis=1,
                             bitorder="little").view(np.uint32)
    add_words = np.packbits(adds, axis=1,
                            bitorder="little").view(np.uint32)
    return flag_words, add_words
"""
    _write_budget(tmp_path, monkeypatch, {"plane": entry})
    report = analyze_sources({"pkg/plane.py": src},
                             rules=["transfer-budget"])
    assert not report.findings


def test_budget_unpacked_bitplane_flagged(tmp_path, monkeypatch):
    entry = {
        "site": "pkg/plane.py::route",
        "budget_bytes_per_unit": 0.125,
        "lanes": [{"name": "flag_words", "kind": "bitplane"}],
    }
    src = """
import numpy as np

def route(flags):
    flag_words = np.asarray(flags, np.uint32)
    return flag_words
"""
    _write_budget(tmp_path, monkeypatch, {"plane": entry})
    report = analyze_sources({"pkg/plane.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "no longer a packed bitplane" in fired[0].message


def test_budget_missing_lane_flagged(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"ship": _SHIP_ENTRY})
    src = _SHIP_SRC.replace("idx", "indices")
    report = analyze_sources({"pkg/ship.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "not assigned" in fired[0].message


def test_budget_stale_site_flagged(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"ship": _SHIP_ENTRY})
    src = _SHIP_SRC.replace("def ship", "def ship_v2")
    report = analyze_sources({"pkg/ship.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "not found" in fired[0].message


def test_budget_sum_mismatch_flagged(tmp_path, monkeypatch):
    entry = dict(_SHIP_ENTRY, budget_bytes_per_unit=4)
    _write_budget(tmp_path, monkeypatch, {"ship": entry})
    report = analyze_sources({"pkg/ship.py": _SHIP_SRC},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "!= manifest budget" in fired[0].message


def test_budget_scalar_lane_excluded_from_sum(tmp_path, monkeypatch):
    entry = dict(_SHIP_ENTRY)
    entry = json.loads(json.dumps(entry))  # deep copy
    entry["lanes"].append(
        {"name": "n_op", "kind": "scalar", "dtype": "int32"})
    src = _SHIP_SRC.replace(
        "    return idx, val",
        "    n_op = np.asarray(n, np.int32)\n"
        "    jax.device_put(n_op)\n"
        "    return idx, val")
    _write_budget(tmp_path, monkeypatch, {"ship": entry})
    report = analyze_sources({"pkg/ship.py": src},
                             rules=["transfer-budget"])
    assert not report.findings


def test_unbudgeted_device_put_flagged_and_audit_exempt(
        tmp_path, monkeypatch):
    src = """
import jax
import numpy as np

def rogue(x):
    return jax.device_put(np.asarray(x, np.int64))

def audited(x):
    return jax.device_put(x)
"""
    _write_budget(tmp_path, monkeypatch, {},
                  modules=["pkg/xfer.py"],
                  audited=["pkg/xfer.py::audited"])
    report = analyze_sources({"pkg/xfer.py": src},
                             rules=["transfer-unbudgeted"])
    fired = _rules_fired(report, "transfer-unbudgeted")
    assert len(fired) == 1 and "rogue" in fired[0].message


def test_unbudgeted_ignores_modules_off_manifest(tmp_path, monkeypatch):
    src = """
import jax

def free(x):
    return jax.device_put(x)
"""
    _write_budget(tmp_path, monkeypatch, {}, modules=["pkg/xfer.py"])
    report = analyze_sources({"pkg/elsewhere.py": src},
                             rules=["transfer-unbudgeted"])
    assert not report.findings


# shaped like ops/json_parse.py::parse_window_fields: one padded uint8
# window lane, budgeted at 1 B/unit
_WINDOW_ENTRY = {
    "site": "pkg/jparse.py::parse_window",
    "unit": "padded window byte",
    "budget_bytes_per_unit": 1,
    "device_put_exhaustive": True,
    "lanes": [{"name": "lane_bytes", "kind": "dtype", "dtype": "uint8"}],
}

_WINDOW_SRC = """
import numpy as np
import jax

def parse_window(window, n):
    lane_bytes = np.full(n + 32, 0x20, np.uint8)
    jax.device_put(lane_bytes)
    return lane_bytes
"""


def test_budget_byte_window_lane_clean(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"jparse": _WINDOW_ENTRY})
    report = analyze_sources({"pkg/jparse.py": _WINDOW_SRC},
                             rules=["transfer-budget"])
    assert not report.findings


def test_budget_byte_window_widened_flagged(tmp_path, monkeypatch):
    # the r17 failure mode: a uint8 window lane silently widening to
    # int32 quadruples the parse plane's H2D bytes
    _write_budget(tmp_path, monkeypatch, {"jparse": _WINDOW_ENTRY})
    src = _WINDOW_SRC.replace("np.uint8", "np.int32")
    report = analyze_sources({"pkg/jparse.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "widened" in fired[0].message


# shaped like ops/stats.py::decode_mask_words: mixed-dtype decode lanes
# (int64 bit index + uint32 bitmap words + int32 word positions)
_DECODE_ENTRY = {
    "site": "pkg/dvdec.py::decode_words",
    "unit": "padded decode element",
    "budget_bytes_per_unit": 16,
    "device_put_exhaustive": True,
    "lanes": [
        {"name": "lane_bit_idx", "kind": "dtype", "dtype": "int64"},
        {"name": "lane_bm_words", "kind": "dtype", "dtype": "uint32"},
        {"name": "lane_bm_pos", "kind": "dtype", "dtype": "int32"},
    ],
}

_DECODE_SRC = """
import numpy as np
import jax

def decode_words(bit_idx, bm_words, bm_pos, n_words):
    lane_bit_idx = np.full(8, n_words * 32, np.int64)
    lane_bm_words = np.zeros(8, np.uint32)
    lane_bm_pos = np.full(8, n_words, np.int32)
    jax.device_put(lane_bit_idx)
    jax.device_put(lane_bm_words)
    jax.device_put(lane_bm_pos)
    return lane_bit_idx, lane_bm_words, lane_bm_pos
"""


def test_budget_decode_lanes_clean(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"dvdec": _DECODE_ENTRY})
    report = analyze_sources({"pkg/dvdec.py": _DECODE_SRC},
                             rules=["transfer-budget"])
    assert not report.findings


def test_budget_decode_extra_lane_flagged(tmp_path, monkeypatch):
    _write_budget(tmp_path, monkeypatch, {"dvdec": _DECODE_ENTRY})
    src = _DECODE_SRC.replace(
        "    return lane_bit_idx, lane_bm_words, lane_bm_pos",
        "    lane_runs = np.zeros(8, np.int64)\n"
        "    jax.device_put(lane_runs)\n"
        "    return lane_bit_idx, lane_bm_words, lane_bm_pos")
    report = analyze_sources({"pkg/dvdec.py": src},
                             rules=["transfer-budget"])
    fired = _rules_fired(report, "transfer-budget")
    assert fired and "not a budgeted lane" in fired[0].message


# -------------------------------------------------- scan cache / changed


def test_scan_cache_hit_reproduces_report(tmp_path):
    from delta_tpu.tools.analyzer.cache import analyze_paths_cached

    target = tmp_path / "pkg"
    target.mkdir()
    (target / "a.py").write_text("def f(x=[]):\n    return x\n")
    cache = tmp_path / "cache.json"
    r1, s1 = analyze_paths_cached([str(target)],
                                  cache_path=str(cache))
    assert s1["cache"] == "cold"
    r2, s2 = analyze_paths_cached([str(target)],
                                  cache_path=str(cache))
    assert s2["cache"] == "hit" and s2["changed_files"] == 0
    assert [f.message for f in r2.findings] \
        == [f.message for f in r1.findings]
    assert r2.rules_run == r1.rules_run
    assert r2.files_scanned == r1.files_scanned


def test_scan_cache_invalidated_by_content_change(tmp_path):
    from delta_tpu.tools.analyzer.cache import analyze_paths_cached

    target = tmp_path / "pkg"
    target.mkdir()
    mod = target / "a.py"
    mod.write_text("def f():\n    return 1\n")
    cache = tmp_path / "cache.json"
    r1, _ = analyze_paths_cached([str(target)], cache_path=str(cache))
    assert not r1.findings
    mod.write_text("def f(x=[]):\n    return x\n")
    r2, s2 = analyze_paths_cached([str(target)], cache_path=str(cache))
    assert s2["cache"] == "stale" and s2["changed_files"] == 1
    assert _rules_fired(r2, "mutable-default")


def test_scan_cache_touch_without_change_still_hits(tmp_path):
    from delta_tpu.tools.analyzer.cache import analyze_paths_cached

    target = tmp_path / "pkg"
    target.mkdir()
    mod = target / "a.py"
    mod.write_text("def f():\n    return 1\n")
    cache = tmp_path / "cache.json"
    analyze_paths_cached([str(target)], cache_path=str(cache))
    os.utime(mod)  # mtime moves, bytes identical
    _, stats = analyze_paths_cached([str(target)],
                                    cache_path=str(cache))
    assert stats["cache"] == "hit"


def test_cli_changed_mode_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    cache = tmp_path / "cache.json"
    argv = [str(bad), "--changed", "--cache-file", str(cache)]
    assert lint_main(argv) == 1
    capsys.readouterr()
    assert lint_main(argv) == 1  # cache hit must not mask findings
    bad.write_text("def f(x=None):\n    return x\n")
    capsys.readouterr()
    assert lint_main(argv) == 0


# ------------------------------------------------------------ baseline


def test_baseline_write_then_check_passes(tmp_path, capsys,
                                          monkeypatch):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    bl = tmp_path / "bl.json"
    assert lint_main([str(bad), "--baseline", "write",
                      "--baseline-file", str(bl)]) == 0
    capsys.readouterr()
    assert lint_main([str(bad), "--baseline", "check",
                      "--baseline-file", str(bl)]) == 0
    out = capsys.readouterr().out
    assert "1 baselined" in out


def test_baseline_new_finding_fails(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    bl = tmp_path / "bl.json"
    lint_main([str(bad), "--baseline", "write",
               "--baseline-file", str(bl)])
    bad.write_text("def f(x=[]):\n    return x\n"
                   "def g(y={}):\n    return y\n")
    capsys.readouterr()
    assert lint_main([str(bad), "--baseline", "check",
                      "--baseline-file", str(bl)]) == 1
    out = capsys.readouterr().out
    assert "g()" in out and "1 finding(s)" in out


def test_baseline_fingerprint_survives_line_shift(tmp_path, capsys,
                                                  monkeypatch):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    bl = tmp_path / "bl.json"
    lint_main([str(bad), "--baseline", "write",
               "--baseline-file", str(bl)])
    bad.write_text("# pushed down two lines\n# by these comments\n"
                   "def f(x=[]):\n    return x\n")
    capsys.readouterr()
    assert lint_main([str(bad), "--baseline", "check",
                      "--baseline-file", str(bl)]) == 0


def test_baseline_check_without_file_is_usage_error(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    assert lint_main([str(bad), "--baseline", "check",
                      "--baseline-file",
                      str(tmp_path / "missing.json")]) == 2


# -------------------------------------------------------- SARIF upgrade


def test_sarif_rules_carry_help_uris():
    report = analyze_sources({"m.py": "def f(x=[]):\n    return x\n"})
    doc = json.loads(render_json(report))
    rules = doc["runs"][0]["tool"]["driver"]["rules"]
    by_id = {r["id"]: r for r in rules}
    assert by_id["shared-state-race"]["helpUri"] \
        == "docs/static_analysis.md#shared-state-race"
    assert by_id["transfer-budget"]["helpUri"] \
        == "docs/static_analysis.md#transfer-budget"
    assert by_id["transfer-unbudgeted"]["helpUri"] \
        == "docs/static_analysis.md#transfer-budget"
    assert all("helpUri" in r for r in rules)


def test_sarif_suppressed_results_carry_suppression_records():
    src = ("def f(x=[]):  # delta-lint: disable=mutable-default ok\n"
           "    return x\n")
    report = analyze_sources({"m.py": src})
    doc = json.loads(render_json(report))
    sup = doc["runs"][0]["suppressedResults"]
    assert sup and sup[0]["suppressions"][0]["kind"] == "inSource"


def test_sarif_baseline_states(tmp_path):
    from delta_tpu.tools.analyzer.baseline import (
        apply_baseline,
        load_baseline,
        write_baseline,
    )

    bad = tmp_path / "bad.py"
    bad.write_text("def f(x=[]):\n    return x\n")
    report = analyze_paths([str(bad)])
    bl = tmp_path / "bl.json"
    write_baseline(str(bl), report)
    bad.write_text("def f(x=[]):\n    return x\n"
                   "def g(y={}):\n    return y\n")
    checked = apply_baseline(analyze_paths([str(bad)]),
                             load_baseline(str(bl)))
    doc = json.loads(render_json(checked))
    run = doc["runs"][0]
    assert [r["baselineState"] for r in run["results"]] == ["new"]
    assert [r["baselineState"] for r in run["baselinedResults"]] \
        == ["unchanged"]


# ------------------------------------------------- unprofiled dispatch


_DISPATCH_ENV = "DELTA_LINT_DISPATCH_MODULES"

_FUNNELED_SRC = """
import jax
from delta_tpu import obs

def launch(arr):
    with obs.device_dispatch("k.launch", key=(arr.shape[0],)) as dd:
        dd.h2d("arr", arr)
        return jax.device_put(arr)
"""

_BARE_SRC = """
import jax

def launch(arr):
    return jax.device_put(arr)
"""


def test_dispatch_funneled_clean(monkeypatch):
    monkeypatch.setenv(_DISPATCH_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": _FUNNELED_SRC},
                             rules=["unprofiled-dispatch"])
    assert not report.findings


def test_dispatch_bare_device_put_flagged(monkeypatch):
    monkeypatch.setenv(_DISPATCH_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": _BARE_SRC},
                             rules=["unprofiled-dispatch"])
    fired = _rules_fired(report, "unprofiled-dispatch")
    assert fired and "launch()" in fired[0].message


def test_dispatch_uncovered_module_ignored(monkeypatch):
    monkeypatch.setenv(_DISPATCH_ENV, "pkg/other.py")
    report = analyze_sources({"pkg/k.py": _BARE_SRC},
                             rules=["unprofiled-dispatch"])
    assert not report.findings


def test_dispatch_allowlisted_helper_clean(monkeypatch):
    monkeypatch.setenv(_DISPATCH_ENV, "pkg/k.py")
    monkeypatch.setenv("DELTA_LINT_DISPATCH_ALLOW", "launch")
    report = analyze_sources({"pkg/k.py": _BARE_SRC},
                             rules=["unprofiled-dispatch"])
    assert not report.findings


def test_dispatch_multi_item_with_covers(monkeypatch):
    """`with device_dispatch(...) as dd, other():` still counts, and so
    does a device_put nested deeper inside the block."""
    src = """
import jax
import contextlib
from delta_tpu import obs

def launch(arr, flag):
    with obs.device_dispatch("k.launch") as dd, contextlib.nullcontext():
        if flag:
            for _ in range(2):
                jax.device_put(arr)
    return arr
"""
    monkeypatch.setenv(_DISPATCH_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src},
                             rules=["unprofiled-dispatch"])
    assert not report.findings


# -------------------------------------- resident-ledger-discipline


_LEDGER_ENV = "DELTA_LINT_LEDGER_MODULES"

_LEDGER_CLEAN_SRC = """
import jax
from delta_tpu.obs import hbm

class Lane:
    def __init__(self, arr):
        dev = jax.device_put(arr)
        self._hbm = hbm.register(self, kind="replay-keys", arrays=(dev,))

    def release(self):
        self._hbm.release()
"""


def test_ledger_registered_and_released_clean(monkeypatch):
    monkeypatch.setenv(_LEDGER_ENV, "pkg/owner.py")
    report = analyze_sources({"pkg/owner.py": _LEDGER_CLEAN_SRC},
                             rules=["resident-ledger-discipline"])
    assert not report.findings


def test_ledger_register_without_release_flagged(monkeypatch):
    src = """
from delta_tpu.obs import hbm

class Lane:
    def __init__(self, arr):
        self._hbm = hbm.register(self, kind="replay-keys", arrays=(arr,))
"""
    monkeypatch.setenv(_LEDGER_ENV, "pkg/owner.py")
    report = analyze_sources({"pkg/owner.py": src},
                             rules=["resident-ledger-discipline"])
    fired = _rules_fired(report, "resident-ledger-discipline")
    assert len(fired) == 1 and "'_hbm'" in fired[0].message \
        and "release" in fired[0].message


def test_ledger_discarded_register_flagged(monkeypatch):
    src = """
from delta_tpu.obs import hbm

def make(arr):
    hbm.register(None, kind="stats-index", arrays=(arr,))
"""
    monkeypatch.setenv(_LEDGER_ENV, "pkg/owner.py")
    report = analyze_sources({"pkg/owner.py": src},
                             rules=["resident-ledger-discipline"])
    fired = _rules_fired(report, "resident-ledger-discipline")
    assert len(fired) == 1 and "discarded" in fired[0].message


def test_ledger_unregistered_lane_class_flagged(monkeypatch):
    src = """
import jax

class Lane:
    def upload(self, arr):
        self.dev = jax.device_put(arr)
"""
    monkeypatch.setenv(_LEDGER_ENV, "pkg/owner.py")
    report = analyze_sources({"pkg/owner.py": src},
                             rules=["resident-ledger-discipline"])
    fired = _rules_fired(report, "resident-ledger-discipline")
    assert len(fired) == 1 and "Lane" in fired[0].message \
        and "hbm.register" in fired[0].message


def test_ledger_uncovered_module_ignored(monkeypatch):
    src = """
import jax

class Lane:
    def upload(self, arr):
        self.dev = jax.device_put(arr)
"""
    monkeypatch.setenv(_LEDGER_ENV, "pkg/other.py")
    report = analyze_sources({"pkg/owner.py": src},
                             rules=["resident-ledger-discipline"])
    assert not report.findings


def test_ledger_name_bound_release_clean(monkeypatch):
    """A handle bound to a local name counts when `.release()` is
    called on that name (the transient handoff-lane shape)."""
    src = """
from delta_tpu.obs import hbm

def decode(arr):
    h = hbm.register(None, kind="ckpt-handoff", arrays=(arr,))
    try:
        return arr
    finally:
        h.release()
"""
    monkeypatch.setenv(_LEDGER_ENV, "pkg/owner.py")
    report = analyze_sources({"pkg/owner.py": src},
                             rules=["resident-ledger-discipline"])
    assert not report.findings


def test_ledger_real_owner_modules_clean():
    """The shipped resident owners (replay key lanes, stats-index
    lanes, checkpoint handoff) must satisfy the discipline rule —
    whole-repo zero findings is an acceptance gate for this pass."""
    import delta_tpu

    pkg = os.path.dirname(delta_tpu.__file__)
    sources = {}
    for rel in ("parallel/resident.py", "stats/device_index.py",
                "ops/page_decode.py"):
        with open(os.path.join(pkg, rel), encoding="utf-8") as f:
            sources[f"delta_tpu/{rel}"] = f.read()
    report = analyze_sources(sources, rules=["resident-ledger-discipline"])
    assert not report.findings


# -------------------------------------------------------- route-contract


_GATE_SRC = """
import os
from delta_tpu.obs.device import record_gate_decision

ROUTES = {{
    "demo": RouteSpec(env="DELTA_TPU_DEMO",
                      fallback_counter="demo.fallbacks",
                      doc_anchor="demo-route"),{extra_route}
}}

def _decide(gate, chosen):
    record_gate_decision(gate, chosen, {{}}, None, "x")
    return chosen

def demo_route(n):{env_read}
    if n > 100:
        return _decide("demo", "device")
    return _decide("demo", "host")
"""

_OBS_SRC = "CAPTURE_ENV_KEYS = ({keys})\n"

_WORKER_SRC = """
from delta_tpu import obs

_FB = obs.counter("demo.fallbacks")

def run(x):
    with obs.device_dispatch("demo.launch", gate="demo",
                             budget={budget!r}):
        pass
{extra_dispatch}
def fell_back(err):
    {inc}
    {observe}
"""


def _route_fixture(tmp_path, monkeypatch, *, env_read=True,
                   capture_key=True, budget="demo-lane",
                   extra_route="", extra_dispatch="", inc=True,
                   observe=True, counter_cataloged=True,
                   doc_heading="## Demo route", gate_src=None):
    """Assemble the conformant three-module route fixture, optionally
    mutated, and run the route-contract pass over it."""
    manifest = tmp_path / "budget.json"
    manifest.write_text(json.dumps({
        "modules": [], "audited_transfer_sites": [],
        "paths": {"demo-lane": {"site": "pkg/worker.py::run"}},
    }))
    catalog = tmp_path / "metrics.json"
    catalog.write_text(json.dumps({
        "counters": ({"demo.fallbacks": "route fell back"}
                     if counter_cataloged else {}),
        "histograms": {}, "gauges": {},
    }))
    doc = tmp_path / "architecture.md"
    doc.write_text(f"# Design\n\n{doc_heading}\n\nprose\n")
    monkeypatch.setenv("DELTA_LINT_GATE_MODULE", "pkg/gate.py")
    monkeypatch.setenv("DELTA_LINT_OBS_MODULE", "pkg/obsmod.py")
    monkeypatch.setenv("DELTA_LINT_ARCH_DOC", str(doc))
    monkeypatch.setenv("DELTA_LINT_TRANSFER_BUDGET", str(manifest))
    monkeypatch.setenv("DELTA_LINT_METRIC_CATALOG", str(catalog))
    sources = {
        "pkg/gate.py": gate_src if gate_src is not None
        else _GATE_SRC.format(
            extra_route=extra_route,
            env_read=('\n    env = os.environ.get("DELTA_TPU_DEMO")'
                      if env_read else "")),
        "pkg/obsmod.py": _OBS_SRC.format(
            keys='"DELTA_TPU_DEMO",' if capture_key else ""),
        "pkg/worker.py": _WORKER_SRC.format(
            budget=budget, extra_dispatch=extra_dispatch,
            inc="_FB.inc()" if inc else "pass",
            observe=('obs.gate_observation("demo", 1.0)'
                     if observe else "pass")),
    }
    return analyze_sources(sources, rules=["route-contract"])


def test_route_contract_conformant_route_is_clean(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch)
    assert not report.findings, [f.message for f in report.findings]


def test_route_contract_missing_env_read(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch, env_read=False)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "is never read in demo_route()" in found[0].message


_SHARED_BODY_GATE_SRC = """
import os
from delta_tpu.obs.device import record_gate_decision

ROUTES = {
    "demo": RouteSpec(env="DELTA_TPU_DEMO",
                      fallback_counter="demo.fallbacks",
                      doc_anchor="demo-route"),
}

def _decide(gate, chosen):
    record_gate_decision(gate, chosen, {}, None, "x")
    return chosen

def _two_way(gate, n):
    if os.environ.get(%s):
        return _decide(gate, "device")
    return _decide(gate, "host")

def demo_route(n):
    return _two_way("demo", n)
"""


@pytest.mark.parametrize("read,clean", [
    ("ROUTES[gate].env", True), ('"DELTA_TPU_DEMO"', True),
    ('"DELTA_TPU_OTHER"', False)])
def test_route_contract_env_read_in_a_shared_body(tmp_path, monkeypatch,
                                                  read, clean):
    """The override may be read through the registry, in the one body
    the route functions share."""
    report = _route_fixture(tmp_path, monkeypatch,
                            gate_src=_SHARED_BODY_GATE_SRC % read)
    found = _rules_fired(report, "route-contract")
    assert (not found) == clean, [f.message for f in found]
    if not clean:
        assert "is never read in demo_route()" in found[0].message


def test_route_contract_missing_capture_stamp(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch, capture_key=False)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "not in CAPTURE_ENV_KEYS" in found[0].message


def test_route_contract_unknown_budget_name(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch, budget="no-such-lane")
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "has no transfer_budget.json path entry" in found[0].message
    assert found[0].path == "pkg/worker.py"


def test_route_contract_unaudited_dispatch_site(tmp_path, monkeypatch):
    extra = """
def rogue(x):
    with obs.device_dispatch("demo.rogue", gate="demo"):
        pass
"""
    report = _route_fixture(tmp_path, monkeypatch, extra_dispatch=extra)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "not an audited transfer site" in found[0].message
    assert "rogue" in found[0].message


def test_route_contract_missing_gate_observation(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch, observe=False)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "no gate_observation" in found[0].message


def test_route_contract_fallback_counter_never_incremented(
        tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch, inc=False)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "never created-and-incremented" in found[0].message


def test_route_contract_fallback_counter_uncataloged(
        tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch,
                            counter_cataloged=False)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "not cataloged in metric_names.json" in found[0].message


def test_route_contract_doc_anchor_missing(tmp_path, monkeypatch):
    report = _route_fixture(tmp_path, monkeypatch,
                            doc_heading="## Something else")
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "heading matches anchor" in found[0].message


def test_route_contract_stale_registry_entry(tmp_path, monkeypatch):
    extra = """
    "ghost": RouteSpec(env="DELTA_TPU_GHOST",
                       fallback_counter="",
                       doc_anchor=""),"""
    report = _route_fixture(tmp_path, monkeypatch, extra_route=extra)
    found = _rules_fired(report, "route-contract")
    stale = [f for f in found if "stale registry entry" in f.message]
    assert len(stale) == 1 and "'ghost'" in stale[0].message
    # the ghost route has no dispatch funnel / observation either
    assert all("'demo'" not in f.message for f in found)


def test_route_contract_unregistered_route(tmp_path, monkeypatch):
    gate_src = """
import os
from delta_tpu.obs.device import record_gate_decision

ROUTES = {}

def _decide(gate, chosen):
    record_gate_decision(gate, chosen, {}, None, "x")
    return chosen

def demo_route(n):
    return _decide("demo", "host")
"""
    report = _route_fixture(tmp_path, monkeypatch, gate_src=gate_src)
    found = _rules_fired(report, "route-contract")
    assert len(found) == 1
    assert "ROUTES has no 'demo' entry" in found[0].message


def test_route_contract_route_without_gate_record(tmp_path, monkeypatch):
    gate_src = """
import os

ROUTES = {
    "demo": RouteSpec(env="DELTA_TPU_DEMO",
                      fallback_counter="demo.fallbacks",
                      doc_anchor="demo-route"),
}

def demo_route(n):
    return "host"
"""
    report = _route_fixture(tmp_path, monkeypatch, gate_src=gate_src)
    found = _rules_fired(report, "route-contract")
    msgs = "\n".join(f.message for f in found)
    assert "never reaches record_gate_decision" in msgs
    assert "stale registry entry" in msgs


def test_route_contract_silent_without_gate_module(monkeypatch):
    monkeypatch.setenv("DELTA_LINT_GATE_MODULE", "pkg/gate.py")
    report = analyze_sources({"pkg/other.py": "x = 1\n"},
                             rules=["route-contract"])
    assert not report.findings


def test_route_registry_covers_all_routes():
    """The live registry names the five shipped routes and every env
    override is mirrored into the capture-conditions stamp."""
    from delta_tpu.obs.device import CAPTURE_ENV_KEYS
    from delta_tpu.parallel.gate import ROUTES

    assert set(ROUTES) == {"replay", "parse", "decode", "skip", "sql"}
    for spec in ROUTES.values():
        assert spec.env in CAPTURE_ENV_KEYS


# -------------------------------------------------------- recompile-risk


_RECOMPILE_ENV = "DELTA_LINT_RECOMPILE_MODULES"


def test_recompile_risk_unpadded_length_flagged(monkeypatch):
    src = """
import numpy as np
import jax

@jax.jit
def kern(x):
    return x

def launch(vals):
    n = len(vals)
    arr = np.zeros(n, dtype=np.int32)
    return kern(arr)
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    found = _rules_fired(report, "recompile-risk")
    assert len(found) == 1
    assert "'arr'" in found[0].message and "kern" in found[0].message


def test_recompile_risk_padded_length_is_clean(monkeypatch):
    src = """
import numpy as np
import jax
from delta_tpu.ops.replay import pad_bucket

@jax.jit
def kern(x):
    return x

def launch(vals):
    n = len(vals)
    m = pad_bucket(n)
    arr = np.zeros(m, dtype=np.int32)
    return kern(arr)
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    assert not report.findings


def test_recompile_risk_bucket_complement_is_clean(monkeypatch):
    # pad = m - n is the canonical top-up idiom: the concatenated
    # length is bucket-quantized by construction
    src = """
import numpy as np
import jax
from delta_tpu.ops.replay import pad_bucket

@jax.jit
def kern(x):
    return x

def launch(vals, x):
    n = len(vals)
    m = pad_bucket(n)
    pad = m - n
    arr = np.concatenate([x, np.zeros(pad, dtype=x.dtype)])
    return kern(arr)
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    assert not report.findings


def test_recompile_risk_inline_ctor_flagged_once(monkeypatch):
    src = """
import numpy as np
import jax

@jax.jit
def kern(x):
    return x

def launch(vals):
    n = len(vals)
    return kern(np.arange(n))
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    found = _rules_fired(report, "recompile-risk")
    assert len(found) == 1, "one finding per callsite, no duplicates"
    assert "<inline constructor>" in found[0].message


def test_recompile_risk_scalar_asarray_is_clean(monkeypatch):
    # np.asarray(n) is a 0-d operand: data-dependent *value*, constant
    # shape — no recompile risk
    src = """
import numpy as np
import jax

@jax.jit
def kern(x, n):
    return x

def launch(vals, x):
    n = len(vals)
    return kern(x, np.asarray(n))
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    assert not report.findings


def test_recompile_risk_list_accumulator_flagged(monkeypatch):
    src = """
import numpy as np
import jax

@jax.jit
def kern(x):
    return x

def launch(rows):
    out = []
    for r in rows:
        out.append(r.key)
    arr = np.asarray(out)
    return kern(arr)
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    found = _rules_fired(report, "recompile-risk")
    assert len(found) == 1 and "'arr'" in found[0].message


def test_recompile_risk_typed_exemption_honored(monkeypatch):
    src = """
import numpy as np
import jax

@jax.jit
def kern(x):
    return x

def launch(vals):
    n = len(vals)
    return kern(np.arange(n))
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/k.py")
    monkeypatch.setenv("DELTA_LINT_RECOMPILE_EXEMPT", "pkg/k.py::launch")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    assert not report.findings


def test_recompile_risk_uncovered_module_is_silent(monkeypatch):
    src = """
import numpy as np
import jax

@jax.jit
def kern(x):
    return x

def launch(vals):
    n = len(vals)
    return kern(np.arange(n))
"""
    monkeypatch.setenv(_RECOMPILE_ENV, "pkg/other.py")
    report = analyze_sources({"pkg/k.py": src}, rules=["recompile-risk"])
    assert not report.findings


def test_recompile_risk_exemption_registry_names_live_sites():
    """Every built-in exemption must point at a real function — a
    refactor that moves the site must move the exemption with it."""
    import delta_tpu
    from delta_tpu.tools.analyzer.passes.recompile import _EXEMPTIONS

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(delta_tpu.__file__)))
    for site, (kind, reason) in _EXEMPTIONS.items():
        rel, _, qual = site.partition("::")
        assert kind and reason
        path = os.path.join(root, rel)
        assert os.path.exists(path), f"exempt module {rel} is gone"
        leaf = qual.rpartition(".")[2]
        with open(path, encoding="utf-8") as f:
            assert f"def {leaf}(" in f.read(), \
                f"exempt function {site} is gone"


# ------------------------------------------------------- env-knob census


def _env_catalog(tmp_path, monkeypatch, knobs):
    path = tmp_path / "knobs.json"
    path.write_text(json.dumps({"knobs": knobs}, indent=1))
    monkeypatch.setenv("DELTA_LINT_ENV_CATALOG", str(path))
    return path


_ENV_RULES = ["env-knob-uncataloged", "env-knob-dead-entry",
              "env-knob-capture-stamp"]


def test_env_knob_uncataloged_read_flagged(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {})
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    report = analyze_sources({"pkg/a.py": src}, rules=_ENV_RULES)
    found = _rules_fired(report, "env-knob-uncataloged")
    assert len(found) == 1
    assert "'DELTA_TPU_FOO'" in found[0].message
    assert found[0].line == 2


def test_env_knob_cataloged_read_is_clean(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h"}})
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    report = analyze_sources({"pkg/a.py": src}, rules=_ENV_RULES)
    assert not report.findings


def test_env_knob_module_drift_flagged(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "", "modules": ["pkg/other.py"],
                          "doc": "x", "help": "h"}})
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    other = 'import os\nW = os.environ.get("DELTA_TPU_FOO")\n'
    report = analyze_sources({"pkg/a.py": src, "pkg/other.py": other},
                             rules=["env-knob-uncataloged"])
    found = _rules_fired(report, "env-knob-uncataloged")
    assert len(found) == 1 and found[0].path == "pkg/a.py"
    assert "drifted catalog" in found[0].message


def test_env_knob_dead_entry_flagged(tmp_path, monkeypatch):
    path = _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h"},
        "DELTA_TPU_GHOST": {"default": "", "modules": [],
                            "doc": "x", "help": "h"}})
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    report = analyze_sources({"pkg/a.py": src}, rules=_ENV_RULES)
    found = _rules_fired(report, "env-knob-dead-entry")
    assert len(found) == 1
    assert "'DELTA_TPU_GHOST'" in found[0].message
    assert found[0].path == os.path.basename(str(path))


def test_env_knob_dead_entry_modules_list_drift(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "",
                          "modules": ["pkg/a.py", "pkg/other.py"],
                          "doc": "x", "help": "h"}})
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    report = analyze_sources({"pkg/a.py": src, "pkg/other.py": "x = 1\n"},
                             rules=["env-knob-dead-entry"])
    found = _rules_fired(report, "env-knob-dead-entry")
    assert len(found) == 1
    assert "'modules' list drifted" in found[0].message


def test_env_knob_const_and_helper_reads_resolved(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_BAR": {"default": "", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h"},
        "DELTA_TPU_BAZ": {"default": "1", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h"}})
    src = """
import os

_ENV = "DELTA_TPU_BAR"

def _env_num(name, default):
    return float(os.environ.get(name, default))

V = os.environ.get(_ENV)
W = _env_num("DELTA_TPU_BAZ", 1)
"""
    report = analyze_sources({"pkg/a.py": src}, rules=_ENV_RULES)
    assert not report.findings, [f.message for f in report.findings]


def test_env_knob_read_through_the_route_registry(tmp_path, monkeypatch):
    """`os.environ.get(ROUTES[gate].env)` reads every override the
    module's registry declares, and nothing it does not."""
    knob = {"default": "", "modules": ["pkg/gate.py"], "doc": "x",
            "help": "h"}
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_DEMO": knob, "DELTA_TPU_OTHER": knob,
        "DELTA_TPU_GHOST": knob})
    src = """
import os

ROUTES = {
    "demo": RouteSpec(env="DELTA_TPU_DEMO", fallback_counter="",
                      doc_anchor=""),
    "other": RouteSpec(env="DELTA_TPU_OTHER", fallback_counter="",
                       doc_anchor=""),
}

def _two_way(gate):
    return os.environ.get(ROUTES[gate].env)
"""
    report = analyze_sources({"pkg/gate.py": src}, rules=_ENV_RULES)
    assert ["'DELTA_TPU_GHOST'" in f.message for f in report.findings] \
        == [True], [f.message for f in report.findings]


def test_env_knob_capture_stamp_missing_flagged(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h", "capture": True}})
    monkeypatch.setenv("DELTA_LINT_OBS_MODULE", "pkg/obsmod.py")
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    obsmod = 'CAPTURE_ENV_KEYS = ("DELTA_TPU_OTHER",)\n'
    report = analyze_sources({"pkg/a.py": src, "pkg/obsmod.py": obsmod},
                             rules=["env-knob-capture-stamp"])
    found = _rules_fired(report, "env-knob-capture-stamp")
    assert len(found) == 1
    assert "'DELTA_TPU_FOO'" in found[0].message
    assert found[0].path == "pkg/obsmod.py"


def test_env_knob_capture_stamp_present_is_clean(tmp_path, monkeypatch):
    _env_catalog(tmp_path, monkeypatch, {
        "DELTA_TPU_FOO": {"default": "", "modules": ["pkg/a.py"],
                          "doc": "x", "help": "h", "capture": True}})
    monkeypatch.setenv("DELTA_LINT_OBS_MODULE", "pkg/obsmod.py")
    src = 'import os\nV = os.environ.get("DELTA_TPU_FOO")\n'
    obsmod = 'CAPTURE_ENV_KEYS = ("DELTA_TPU_FOO",)\n'
    report = analyze_sources({"pkg/a.py": src, "pkg/obsmod.py": obsmod},
                             rules=["env-knob-capture-stamp"])
    assert not report.findings


def test_knob_docs_table_is_current():
    """docs/observability.md's generated env-knob table must match
    resources/env_knobs.json — regenerate with
    `python -m delta_tpu.tools.knob_docs` after a catalog edit."""
    from delta_tpu.tools.knob_docs import main as knob_main

    assert knob_main(["--check"]) == 0


def test_capture_conditions_records_route_knobs(monkeypatch):
    """The runtime half of the capture-stamp contract: a knob in
    CAPTURE_ENV_KEYS set in the environment appears in
    capture_conditions()['env']."""
    from delta_tpu.obs.device import capture_conditions

    monkeypatch.setenv("DELTA_TPU_DEVICE_DECODE", "force")
    monkeypatch.setenv("DELTA_TPU_DEVICE_SQL", "1")
    env = capture_conditions()["env"]
    assert env["DELTA_TPU_DEVICE_DECODE"] == "force"
    assert env["DELTA_TPU_DEVICE_SQL"] == "1"


# ------------------------------------- scan cache: catalog soundness


def test_scan_cache_invalidated_by_catalog_edit(tmp_path, monkeypatch):
    """Regression for the stale-cache soundness hole: the pass
    catalogs are scan inputs — editing one must invalidate the cache
    even though no scanned .py file changed."""
    from delta_tpu.tools.analyzer.cache import analyze_paths_cached

    knobs = tmp_path / "knobs.json"
    knobs.write_text(json.dumps({"knobs": {
        "DELTA_TPU_FOO": {"default": "", "modules": [],
                          "doc": "x", "help": "h"}}}))
    monkeypatch.setenv("DELTA_LINT_ENV_CATALOG", str(knobs))
    target = tmp_path / "pkg"
    target.mkdir()
    (target / "a.py").write_text(
        'import os\nV = os.environ.get("DELTA_TPU_FOO")\n')
    cache = tmp_path / "cache.json"
    rules = ["env-knob-uncataloged", "env-knob-dead-entry"]
    r1, s1 = analyze_paths_cached([str(target)], rules=rules,
                                  cache_path=str(cache))
    assert s1["cache"] == "cold" and not r1.findings
    _, s2 = analyze_paths_cached([str(target)], rules=rules,
                                 cache_path=str(cache))
    assert s2["cache"] == "hit"

    # catalog edit, no .py change: must NOT serve the cached report
    knobs.write_text(json.dumps({"knobs": {
        "DELTA_TPU_FOO": {"default": "", "modules": [],
                          "doc": "x", "help": "h"},
        "DELTA_TPU_GHOST": {"default": "", "modules": [],
                            "doc": "x", "help": "h"}}}))
    r3, s3 = analyze_paths_cached([str(target)], rules=rules,
                                  cache_path=str(cache))
    assert s3["cache"] != "hit", \
        "catalog edits must invalidate the scan cache"
    assert _rules_fired(r3, "env-knob-dead-entry")


# ------------------------------------------------------ whole-repo gate


def test_repo_scan_is_clean():
    """The tier-1 gate: zero unsuppressed findings over the installed
    package. Every suppression in the tree is an audited false positive
    or by-design blanket (see docs/static_analysis.md)."""
    import delta_tpu

    pkg = os.path.dirname(os.path.abspath(delta_tpu.__file__))
    report = analyze_paths([pkg], root=os.path.dirname(pkg))
    assert report.files_scanned > 100
    details = "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.message}"
        for f in report.findings)
    assert report.ok, f"unsuppressed delta-lint findings:\n{details}"
