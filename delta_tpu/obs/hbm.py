"""Device-memory observability: the process-wide ResidentLedger.

PRs 7/14/16 moved replay key lanes, scan-planning stats indexes, and
checkpoint decode handoff codes into HBM — and each artifact managed
its own lifecycle with at best an ad-hoc gauge. ROADMAP item 6 (HBM as
a managed fleet cache over thousands of tenant tables) needs one budget
view instead: every device-resident artifact registers here at
creation, carrying ``(table_path, kind, version, nbytes,
rebuild_cost_class, created_at, last_access)``, touches on read, grows
in place on donated-buffer appends, and releases on eviction or
version advance. Three surfaces sit on the ledger:

- **Reconciliation audit** (`audit()`) — the runtime twin of the
  transfer-budget audit: every registered artifact's device arrays are
  weakly referenced, and the audit cross-checks them against
  ``jax.live_arrays()`` — an array gone without `release()`, or a byte
  count that no longer matches what was registered (an unrecorded
  grow), is drift. **Leak detection** rides `weakref.finalize`: an
  owner GC'd without `release()` bumps ``hbm.resident_leaks`` and is
  auto-deregistered so the gauges never go stale; ``strict`` mode
  makes the next `audit()` raise on both drift and leaks.
- **Ledger-derived gauges** — ``hbm.resident_bytes`` /
  ``hbm.resident_artifacts`` / ``hbm.resident_bytes_peak``, plus the
  pre-ledger names ``replay.resident_hbm_bytes`` and
  ``scan.stats_index_hbm_bytes`` re-derived as per-kind totals (same
  exported names, no dashboard break). Release and leak events ride
  the active span into the flight recorder.
- **`delta-hbm` CLI** (`tools/hbm_cli.py`) — rollups by table/kind,
  top-N residents, leak report, all from `dump_ledger()` JSONL.

Gating mirrors `device.py`: ``DELTA_TPU_HBM_OBS=off|on|strict`` — but
the default is **on**: ledger ops run at artifact-lifecycle frequency
(per snapshot load/advance/eviction, not per row), and the subsumed
gauges must stay live by default. ``off`` is a true no-op —
`register()` returns a process-wide stateless singleton handle whose
`touch`/`grow`/`release` do nothing
(`tests/test_hbm_ledger.py::test_off_mode_register_overhead_is_negligible`
holds that path under 5 µs a call).
``strict`` arms raise-on-drift/leak in `audit()` for tests and canary
lanes.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import logging
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

from delta_tpu.obs import trace as _trace
from delta_tpu.obs.registry import counter, gauge

_log = logging.getLogger(__name__)

MODE_OFF = 0
MODE_ON = 1
MODE_STRICT = 2

_MODES = {"off": MODE_OFF, "on": MODE_ON, "strict": MODE_STRICT,
          "0": MODE_OFF, "1": MODE_ON, "2": MODE_STRICT}

# artifact kinds currently registered by the instrumented owners; the
# per-kind gauges below key on these (free-form strings are accepted —
# a new resident subsystem just picks a new kind)
KIND_REPLAY_KEYS = "replay-keys"      # parallel/resident.py
KIND_STATS_INDEX = "stats-index"      # stats/device_index.py
KIND_CKPT_HANDOFF = "ckpt-handoff"    # ops/page_decode.py (transient)
KIND_SQL_OPERANDS = "sql-operands"    # sqlengine/operands.py

UNKNOWN_TABLE = "unknown"

_LEAK_RING = 256


def _mode_from_env() -> int:
    raw = os.environ.get("DELTA_TPU_HBM_OBS", "on").strip().lower()
    mode = _MODES.get(raw)
    if mode is None:
        _log.warning("unknown DELTA_TPU_HBM_OBS=%r; hbm obs stays on", raw)
        return MODE_ON
    return mode


_mode: int = _mode_from_env()


def hbm_obs_mode() -> int:
    return _mode


def hbm_obs_enabled() -> bool:
    return _mode != MODE_OFF


def set_hbm_obs_mode(mode: Optional[str]) -> None:
    """Programmatically set the ledger mode ('off'|'on'|'strict');
    None re-reads `DELTA_TPU_HBM_OBS`. Tests and bench use this;
    production uses the env var."""
    global _mode
    if mode is None:
        _mode = _mode_from_env()
    else:
        try:
            _mode = _MODES[mode.strip().lower()]
        except KeyError:
            raise ValueError(
                f"unknown hbm obs mode {mode!r}; expected off|on|strict"
            ) from None


# -- instruments (resolved once; see resources/metric_names.json) ------------

_REGISTRATIONS = counter("hbm.registrations")
_RELEASES = counter("hbm.releases")
_LEAKS = counter("hbm.resident_leaks")
_SHEDS = counter("hbm.sheds")
_SHED_BYTES = counter("hbm.shed_bytes")


# -- ambient table scope -----------------------------------------------------

# Registration sites deep in the replay/decode stack don't receive the
# table path; `Snapshot` opens this scope around load/update so every
# artifact established inside lands under the right table in rollups.
_SCOPE: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "delta_tpu_hbm_table_scope", default=None)


@contextlib.contextmanager
def table_scope(table_path: Optional[str]):
    """Attribute every `register()` inside the block (that doesn't pass
    an explicit ``table_path``) to `table_path`."""
    token = _SCOPE.set(table_path)
    try:
        yield
    finally:
        _SCOPE.reset(token)


def current_table_scope() -> Optional[str]:
    return _SCOPE.get()


# -- handles -----------------------------------------------------------------


class _NoopHandle:
    """Disabled-path singleton: stateless, reentrant, thread-safe.
    Every lifecycle method is a no-op so instrumented sites read
    identically in both modes."""

    __slots__ = ()

    def touch(self) -> None:
        pass

    def grow(self, arrays: Sequence[object] = (),
             nbytes: Optional[int] = None) -> None:
        pass

    def release(self) -> None:
        pass


_NOOP_HANDLE = _NoopHandle()


def noop_handle() -> _NoopHandle:
    """The shared no-op handle — a safe initial value for owner slots
    (`self._hbm = hbm.noop_handle()`) so touch/release never need a
    None check."""
    return _NOOP_HANDLE


def _sum_nbytes(arrays: Sequence[object]) -> int:
    return sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)


def _wrap_evictor(evictor):
    """Normalize an evictor into a zero-arg resolver -> callable|None.

    Bound methods are held via `weakref.WeakMethod`: a strong reference
    from the ledger to the owner would keep the owner alive forever and
    blind the finalize-based leak detector. Free functions are held
    strongly (they don't pin an owner)."""
    if evictor is None:
        return None
    if getattr(evictor, "__self__", None) is not None:
        return weakref.WeakMethod(evictor)
    return lambda: evictor


# Shed ordering: cheapest-to-rebuild first, then least-recently-used.
# Unknown classes sort with "normal"; "transient" artifacts are
# mid-flight handoffs — evicting one tears an in-progress decode, so
# they rank just above "expensive" and in practice never register an
# evictor at all.
_SHED_COST_RANK = {"cheap": 0, "normal": 1, "transient": 2, "expensive": 3}


class ResidentHandle:
    """Ledger entry for one device-resident artifact. Obtained from
    `register()`; the owner calls `touch()` on read paths, `grow()`
    when a donated in-place append swaps/extends the device buffer,
    and `release()` exactly once at end of life (idempotent)."""

    __slots__ = ("table_path", "kind", "version", "nbytes",
                 "rebuild_cost_class", "created_at", "last_access",
                 "_seq", "_ledger", "_refs", "_finalizer", "_released",
                 "_evictor")

    def __init__(self, ledger: "ResidentLedger", seq: int, table_path: str,
                 kind: str, version: Optional[int], nbytes: int,
                 rebuild_cost_class: str, refs):
        self.table_path = table_path
        self.kind = kind
        self.version = version
        self.nbytes = nbytes
        self.rebuild_cost_class = rebuild_cost_class
        self.created_at = time.time()
        self.last_access = self.created_at
        self._seq = seq
        self._ledger = ledger
        self._refs = refs          # list of weakref.ref | None (untracked)
        self._finalizer = None     # wired by ResidentLedger.register
        self._released = False
        self._evictor = None       # zero-arg resolver -> callable | None

    def touch(self) -> None:
        """Record an access (recency feeds future eviction policy)."""
        if not self._released:
            self.last_access = time.time()
            # plain int add: telemetry tolerance, same trade as Counter
            self._ledger.touches += 1

    def grow(self, arrays: Sequence[object] = (),
             nbytes: Optional[int] = None) -> None:
        """Re-account an in-place buffer swap/growth: `arrays` re-point
        the audit weakrefs (a donated append yields a NEW device array
        object at the same logical artifact), `nbytes` overrides the
        recomputed total."""
        self._ledger._grow(self, arrays, nbytes)

    def release(self) -> None:
        """Deregister (idempotent): the artifact's device memory is
        being dropped on purpose."""
        self._ledger._release(self)

    def to_dict(self) -> Dict[str, object]:
        return {
            "type": "hbm_resident",
            "seq": self._seq,
            "table_path": self.table_path,
            "kind": self.kind,
            "version": self.version,
            "nbytes": self.nbytes,
            "rebuild_cost_class": self.rebuild_cost_class,
            "created_at": self.created_at,
            "last_access": self.last_access,
        }


# -- the ledger --------------------------------------------------------------


class ResidentLedger:
    """Process-wide registry of device-resident artifacts.

    The lock is reentrant on purpose: `weakref.finalize` leak callbacks
    run whenever the cyclic GC happens to fire — including during an
    allocation made while a ledger method already holds the lock."""

    def __init__(self):
        self._lock = threading.RLock()
        self._handles: Dict[int, ResidentHandle] = {}
        self._next_seq = 1
        self._total = 0
        self._peak = 0
        self._leaks: collections.deque = collections.deque(maxlen=_LEAK_RING)
        self.touches = 0

    # -- lifecycle -----------------------------------------------------

    def register(self, owner, *, kind: str, table_path: Optional[str],
                 version: Optional[int], nbytes: Optional[int],
                 rebuild_cost_class: str,
                 arrays: Sequence[object],
                 evictor=None) -> ResidentHandle:
        if nbytes is None:
            nbytes = _sum_nbytes(arrays)
        if table_path is None:
            table_path = _SCOPE.get() or UNKNOWN_TABLE
        refs: Optional[List[weakref.ref]] = []
        for a in arrays:
            try:
                refs.append(weakref.ref(a))
            except TypeError:
                # not weakref-able (host ndarray fixture): the handle
                # stays byte-accounted but exempt from the identity
                # half of the audit
                refs = None
                break
        with self._lock:
            seq = self._next_seq
            self._next_seq += 1
            h = ResidentHandle(self, seq, table_path, kind, version,
                               int(nbytes), rebuild_cost_class, refs)
            h._evictor = _wrap_evictor(evictor)
            self._handles[seq] = h
            self._total += h.nbytes
            if self._total > self._peak:
                self._peak = self._total
        if owner is not None:
            f = weakref.finalize(owner, self._leaked, seq)
            # an exiting process is not leaking HBM — don't fire the
            # whole backlog of pending finalizers at interpreter exit
            f.atexit = False
            h._finalizer = f
        _REGISTRATIONS.inc()
        return h

    def _grow(self, h: ResidentHandle, arrays: Sequence[object],
              nbytes: Optional[int]) -> None:
        with self._lock:
            if h._released:
                return
            new_bytes = int(nbytes if nbytes is not None
                            else _sum_nbytes(arrays))
            if arrays:
                refs: Optional[List[weakref.ref]] = []
                for a in arrays:
                    try:
                        refs.append(weakref.ref(a))
                    except TypeError:
                        refs = None
                        break
                h._refs = refs
            self._total += new_bytes - h.nbytes
            h.nbytes = new_bytes
            if self._total > self._peak:
                self._peak = self._total
            h.last_access = time.time()

    def _release(self, h: ResidentHandle) -> None:
        with self._lock:
            if h._released:
                return
            h._released = True
            self._handles.pop(h._seq, None)
            self._total -= h.nbytes
        if h._finalizer is not None:
            h._finalizer.detach()
        _RELEASES.inc()
        _trace.add_event("hbm.release", kind=h.kind, table=h.table_path,
                         nbytes=h.nbytes)

    def shed(self, max_artifacts: Optional[int] = None,
             need_bytes: Optional[int] = None) -> Tuple[int, int]:
        """Evict resident artifacts under HBM pressure; returns
        ``(artifacts_evicted, bytes_freed)``.

        Candidates are the handles registered with an ``evictor`` whose
        owner is still alive, ordered cheapest-to-rebuild first
        (`rebuild_cost_class`), then least recently used. Evictors run
        outside the ledger lock and must end in the handle's
        ``release()`` — an eviction only counts once the handle reports
        released. Stops after ``max_artifacts`` evictions or once
        ``need_bytes`` have been freed (whichever comes first)."""
        with self._lock:
            cands = []
            for h in self._handles.values():
                ev = h._evictor() if h._evictor is not None else None
                if ev is not None:
                    cands.append(
                        (_SHED_COST_RANK.get(h.rebuild_cost_class, 1),
                         h.last_access, h._seq, h, ev))
        cands.sort(key=lambda t: t[:3])
        n = freed = 0
        for _, _, _, h, ev in cands:
            if max_artifacts is not None and n >= max_artifacts:
                break
            if need_bytes is not None and freed >= need_bytes:
                break
            nbytes = h.nbytes
            ev()
            if h._released:
                n += 1
                freed += nbytes
                _trace.add_event("hbm.shed", kind=h.kind,
                                 table=h.table_path, nbytes=nbytes)
        if n:
            _SHEDS.inc(n)
            _SHED_BYTES.inc(freed)
        return n, freed

    def _leaked(self, seq: int) -> None:
        """Finalizer callback: the owner was GC'd with the handle still
        registered. Deregister (the device arrays die with the owner by
        refcount, so keeping the entry would make every gauge lie) and
        record the leak."""
        with self._lock:
            h = self._handles.pop(seq, None)
            if h is None or h._released:
                return
            h._released = True
            self._total -= h.nbytes
            rec = {
                "type": "hbm_leak",
                "seq": seq,
                "table_path": h.table_path,
                "kind": h.kind,
                "version": h.version,
                "nbytes": h.nbytes,
                "created_at": h.created_at,
                "last_access": h.last_access,
                "ts": time.time(),
            }
            self._leaks.append(rec)
        _LEAKS.inc()
        _log.warning(
            "hbm leak: %s artifact of %s (%d B) owner GC'd without "
            "release() — call release_snapshot_resident (or the owner's "
            "release) before dropping the last reference",
            h.kind, h.table_path, h.nbytes)
        _trace.add_event("hbm.leak", kind=h.kind, table=h.table_path,
                         nbytes=h.nbytes)

    # -- read side -----------------------------------------------------

    def total_bytes(self) -> int:
        return self._total

    def peak_bytes(self) -> int:
        return self._peak

    def artifact_count(self) -> int:
        return len(self._handles)

    def kind_bytes(self, kind: str) -> int:
        with self._lock:
            return sum(h.nbytes for h in self._handles.values()
                       if h.kind == kind)

    def op_count(self) -> int:
        """Ledger operations so far (register + release + leak +
        touch) — the multiplier for the bench's disabled-path overhead
        projection."""
        return (_REGISTRATIONS.value + _RELEASES.value + _LEAKS.value
                + self.touches)

    def residents(self, top: Optional[int] = None) -> List[dict]:
        """Registered artifacts as dicts, largest first."""
        with self._lock:
            out = [h.to_dict() for h in self._handles.values()]
        out.sort(key=lambda d: (-int(d["nbytes"]), d["seq"]))
        return out[:top] if top else out

    def leak_records(self) -> List[dict]:
        with self._lock:
            return list(self._leaks)

    def rollup(self, by: str = "table") -> Dict[str, dict]:
        """Per-table (or per-kind) byte/artifact totals with the cross
        dimension nested: ``{key: {nbytes, artifacts, by_kind|by_table:
        {sub: nbytes}}}``."""
        if by not in ("table", "kind"):
            raise ValueError(f"rollup by {by!r}; expected 'table' or 'kind'")
        sub_key = "by_kind" if by == "table" else "by_table"
        out: Dict[str, dict] = {}
        with self._lock:
            handles = list(self._handles.values())
        for h in handles:
            key = h.table_path if by == "table" else h.kind
            sub = h.kind if by == "table" else h.table_path
            ent = out.setdefault(key, {"nbytes": 0, "artifacts": 0,
                                       sub_key: {}})
            ent["nbytes"] += h.nbytes
            ent["artifacts"] += 1
            ent[sub_key][sub] = ent[sub_key].get(sub, 0) + h.nbytes
        return out

    # -- reconciliation audit ------------------------------------------

    def audit(self) -> Dict[str, object]:
        """Cross-check the ledger against `jax.live_arrays()`: every
        registered artifact's weakly-referenced device arrays must
        still be live, and their actual byte counts must sum to the
        registered figure (byte-exact — an unrecorded `grow()` is
        drift, not noise). Handles registered without weakref-able
        arrays are byte-accounted but identity-exempt (reported under
        ``unverified_bytes``)."""
        drift: List[str] = []
        by_device: Dict[str, int] = {}
        verified = 0
        unverified = 0
        live_ids: Optional[set] = None
        try:
            import jax

            live_ids = {id(a) for a in jax.live_arrays()}
        # delta-lint: disable=except-swallow (audited: a host without a
        # configured jax backend still runs the ledger; the audit then
        # checks weakref liveness only, never crashes)
        except Exception:
            pass
        with self._lock:
            handles = list(self._handles.values())
            total = self._total
            leaks = list(self._leaks)
        for h in handles:
            if h._refs is None:
                unverified += h.nbytes
                continue
            got = 0
            dead = False
            for r in h._refs:
                a = r()
                if a is None or (live_ids is not None
                                 and id(a) not in live_ids):
                    dead = True
                    break
                got += int(getattr(a, "nbytes", 0) or 0)
                for dev, nb in _attribute_devices(a):
                    by_device[dev] = by_device.get(dev, 0) + nb
            if dead:
                drift.append(
                    f"{h.kind} artifact of {h.table_path} "
                    f"({h.nbytes} B): registered device array is no "
                    f"longer live but the handle was never released")
            elif got != h.nbytes:
                drift.append(
                    f"{h.kind} artifact of {h.table_path}: ledger says "
                    f"{h.nbytes} B but live arrays hold {got} B "
                    f"(unrecorded grow/shrink — call handle.grow())")
            else:
                verified += got
        return {
            "ok": not drift and not leaks,
            "ledger_bytes": total,
            "verified_bytes": verified,
            "unverified_bytes": unverified,
            "artifacts": len(handles),
            "by_device": by_device,
            "drift": drift,
            "leaks": leaks,
        }

    def reset(self) -> None:
        """Forget every handle and leak record (tests/bench). Detaches
        finalizers so owners created before the reset can't report
        stale leaks into the fresh epoch."""
        with self._lock:
            handles = list(self._handles.values())
            self._handles.clear()
            self._total = 0
            self._peak = 0
            self._leaks.clear()
            self.touches = 0
        for h in handles:
            h._released = True
            if h._finalizer is not None:
                h._finalizer.detach()


def _attribute_devices(a) -> List[Tuple[str, int]]:
    """(device label, nbytes) attribution for one live array — exact
    per-shard when the array exposes addressable shards, whole-array
    otherwise."""
    try:
        shards = a.addressable_shards
        out: Dict[str, int] = {}
        for s in shards:
            dev = str(s.device)
            out[dev] = out.get(dev, 0) + int(s.data.nbytes)
        if out:
            return list(out.items())
    # delta-lint: disable=except-swallow (audited: device attribution
    # is reporting garnish; an exotic array type degrades to a single
    # "unknown" bucket rather than failing the audit)
    except Exception:
        pass
    return [("unknown", int(getattr(a, "nbytes", 0) or 0))]


_LEDGER = ResidentLedger()


def ledger() -> ResidentLedger:
    return _LEDGER


# -- module-level API (what instrumented sites call) -------------------------


def register(owner, *, kind: str, table_path: Optional[str] = None,
             version: Optional[int] = None, nbytes: Optional[int] = None,
             rebuild_cost_class: str = "normal",
             arrays: Sequence[object] = (),
             evictor=None):
    """Register one device-resident artifact; returns its handle (the
    shared no-op handle when the ledger is off).

    ``owner``   the Python object whose lifetime bounds the artifact —
                GC'd without `release()` counts as a leak;
    ``kind``    artifact kind (`KIND_*` or a new string);
    ``arrays``  the device arrays backing the artifact (weakly held,
                audited against `jax.live_arrays()`);
    ``nbytes``  registered size; computed from `arrays` when omitted;
    ``table_path`` rollup key; the ambient `table_scope()` when omitted;
    ``evictor`` optional zero-arg callable `shed()` may invoke under
                HBM pressure — must drop the artifact's device memory
                and end in the handle's ``release()``; bound methods
                are weakly held so the ledger never pins the owner.
    """
    if _mode == MODE_OFF:
        return _NOOP_HANDLE
    return _LEDGER.register(owner, kind=kind, table_path=table_path,
                            version=version, nbytes=nbytes,
                            rebuild_cost_class=rebuild_cost_class,
                            arrays=arrays, evictor=evictor)


def shed(max_artifacts: Optional[int] = None,
         need_bytes: Optional[int] = None) -> Tuple[int, int]:
    """Evict cheapest-to-rebuild resident artifacts under HBM pressure
    (the shed half of shed-and-retry; see
    `resilience/device_faults.py`). No-op ``(0, 0)`` when the ledger is
    off — without byte accounting there is nothing principled to shed.
    ``DELTA_TPU_HBM_SHED_MAX`` (default 4) caps evictions per call when
    ``max_artifacts`` is omitted."""
    if _mode == MODE_OFF:
        return (0, 0)
    if max_artifacts is None:
        max_artifacts = int(os.environ.get("DELTA_TPU_HBM_SHED_MAX") or 4)
    return _LEDGER.shed(max_artifacts=max_artifacts, need_bytes=need_bytes)


def audit() -> Dict[str, object]:
    """Run the reconciliation audit; in ``strict`` mode raise on any
    drift or recorded leak."""
    result = _LEDGER.audit()
    if _mode >= MODE_STRICT and not result["ok"]:
        problems = list(result["drift"])
        problems += [f"leaked {r['kind']} artifact of {r['table_path']} "
                     f"({r['nbytes']} B)" for r in result["leaks"]]
        raise RuntimeError("hbm ledger reconciliation failed: "
                           + "; ".join(problems))
    return result


def rollup(by: str = "table") -> Dict[str, dict]:
    return _LEDGER.rollup(by=by)


def residents(top: Optional[int] = None) -> List[dict]:
    return _LEDGER.residents(top=top)


def leak_records() -> List[dict]:
    return _LEDGER.leak_records()


def ledger_op_count() -> int:
    return _LEDGER.op_count()


def health_summary() -> Dict[str, object]:
    """Compact ledger view for serve health: totals, peak, leak count,
    per-kind bytes."""
    return {
        "resident_bytes": _LEDGER.total_bytes(),
        "resident_artifacts": _LEDGER.artifact_count(),
        "peak_bytes": _LEDGER.peak_bytes(),
        "leaks": _LEAKS.value,
        "by_kind": {k: e["nbytes"]
                    for k, e in _LEDGER.rollup(by="kind").items()},
    }


def dump_ledger(path: str) -> int:
    """Write every resident record and leak record as JSONL; returns
    the record count. The `delta-hbm` CLI consumes this artifact."""
    import json

    records = _LEDGER.residents() + _LEDGER.leak_records()
    with open(path, "w", encoding="utf-8") as f:
        for rec in records:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    return len(records)


def reset_hbm_obs() -> None:
    """Clear the ledger (handles, leaks, peak, touch count) for tests
    and bench epochs; registry counters are reset separately."""
    _LEDGER.reset()


# -- ledger-derived gauges ---------------------------------------------------

# The pre-ledger ad-hoc gauges (`replay.resident_hbm_bytes` in
# parallel/resident.py, `scan.stats_index_hbm_bytes` in
# stats/device_index.py) are subsumed: same exported names, now derived
# from per-kind ledger totals at scrape time. Callbacks take the ledger
# lock briefly; scrape frequency makes that free.
gauge("hbm.resident_bytes").set_fn(_LEDGER.total_bytes)
gauge("hbm.resident_artifacts").set_fn(_LEDGER.artifact_count)
gauge("hbm.resident_bytes_peak").set_fn(_LEDGER.peak_bytes)
gauge("replay.resident_hbm_bytes").set_fn(
    lambda: _LEDGER.kind_bytes(KIND_REPLAY_KEYS))
gauge("scan.stats_index_hbm_bytes").set_fn(
    lambda: _LEDGER.kind_bytes(KIND_STATS_INDEX))
gauge("sql.operand_cache_bytes").set_fn(
    lambda: _LEDGER.kind_bytes(KIND_SQL_OPERANDS))
