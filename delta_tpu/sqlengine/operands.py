"""Resident SQL operand cache: per-`(table, version, column)` device
join/group key lanes, uploaded once and reused across queries.

The motivating workload is the TPC-DS star schema: every one of the
corpus queries joins the same dimension columns (`d_date_sk`,
`s_store_sk`, `i_item_sk`, ...) against a fact table, and before this
cache the device spine re-shipped those lanes from scratch on every
query. Here the build side of an equi-join becomes a device-resident
artifact on `SnapshotState` (field `operand_cache`, guarded by the
state's dedicated `_operand_cache_lock`), so a warm query uploads only
the probe side.

Two lane kinds, both stored as one padded int64 device lane:

- ``int``   raw int64 values (integer / bool / datetime64 columns) —
            the join sorts the values themselves, skipping the host
            factorize entirely;
- ``codes`` sorted-ordinal dictionary codes for string columns, with
            the host-side dictionary kept for probe-side remapping
            (`pd.Index.get_indexer`).

The lane for a column is built from the series the join actually
probes against — after `executor._merge_null_safe`'s null-key
exclusion. For a single-key join that exclusion is deterministic
("origin rows minus this column's nulls"), so the lane aligns with
every query's null-dropped build frame; nullable integer FKs (which
arrow hands to pandas as float64-with-NaN) therefore cache fine.
Columns that still can't encode after the drop — non-integral floats,
nulls inside string/nullable-int series reaching the encoder, pad
collisions, exotic dtypes — are negative-cached.

Lifecycle mirrors `stats/device_index.py::ResidentStatsIndex`: built
at most once per `SnapshotState`, advanced by
`replay/state.py::advance_state` (carried over verbatim on empty
deltas, released otherwise — a version advance invalidates every
artifact), released on serve-cache eviction through
`parallel/resident.py::release_snapshot_resident`. Device bytes are
accounted in the resident ledger (`obs/hbm.py`, kind
``sql-operands``) under one handle grown per column upload; uploads
ride the dispatch funnel (`sql.operand_upload`, budget
``sql-operand-lanes``) so the transfer-budget audit prices them
byte-exactly.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

import numpy as np
import pandas as pd

from delta_tpu import obs
from delta_tpu.obs import hbm

_HITS = obs.counter("sql.operand_cache_hits")
_MISSES = obs.counter("sql.operand_cache_misses")

# pad sentinel: sorts after every real key. A column whose max value
# IS int64-max would collide, so such columns are negative-cached.
PAD_I64 = np.int64(np.iinfo(np.int64).max)


class ColumnLane:
    """One cached column: a padded int64 device lane plus the host
    metadata consumers need (`ops/sqlops.py::join_pairs_lanes` takes
    `dev`/`n` directly; string probes remap through `dictionary`)."""

    __slots__ = ("kind", "dev", "n", "dictionary", "least", "most")

    def __init__(self, kind: str, dev, n: int,
                 dictionary: Optional[pd.Index], least=None, most=None):
        self.kind = kind          # "int" | "codes"
        self.dev = dev            # int64 device lane, pad_bucket(n) long
        self.n = n                # real row count
        self.dictionary = dictionary  # codes kind only
        self.least = least        # of the real values; None of no rows:
        self.most = most          # the join's sort keys by their range


def _encode_column(series: pd.Series):
    """(int64 values, dictionary|None) for a cacheable column;
    None = uncacheable (nulls, floats, exotic dtypes)."""
    v = series.to_numpy()
    if v.dtype.kind in "ui" or v.dtype == bool:
        vals = v.astype(np.int64, copy=False)
        if len(vals) and int(vals.max()) == int(PAD_I64):
            return None
        return vals, None
    if v.dtype.kind == "M":
        v_ns = v.astype("datetime64[ns]")
        if np.isnat(v_ns).any():
            return None
        return v_ns.view(np.int64), None
    if v.dtype.kind == "f":
        # nullable integer column, null-key rows already excluded by
        # the caller: an integral remainder (bounded to the
        # float64-exact range, which also rules out a PAD collision)
        # maps exactly onto the int64 domain
        if len(v) and (not np.isfinite(v).all()
                       or (v != np.floor(v)).any()
                       or np.abs(v).max() >= 2 ** 53):
            return None
        return v.astype(np.int64), None
    if v.dtype.kind in "OUS":
        codes, uniq = pd.factorize(v, sort=True)
        if len(codes) and int(codes.min()) < 0:  # nulls present
            return None
        return codes.astype(np.int64), pd.Index(uniq)
    if str(series.dtype) in ("Int64", "Int32", "boolean"):
        if series.isna().any():
            return None
        vals = series.to_numpy(np.int64)
        if len(vals) and int(vals.max()) == int(PAD_I64):
            return None
        return vals, None
    return None


class ResidentOperandCache:
    """Per-snapshot-version operand lanes with lazy per-column upload.
    One ledger handle covers the whole cache, grown per column."""

    def __init__(self, table_path: Optional[str] = None,
                 version: Optional[int] = None):
        self._lock = threading.Lock()
        self._lanes: Dict[str, Optional[ColumnLane]] = {}
        self._arrays: list = []
        self._nbytes = 0
        self._registered = False
        self.table_path = table_path
        self.version = version
        self.released = False
        self._hbm = hbm.noop_handle()

    def join_lane(self, column: str,
                  series: pd.Series) -> Optional[ColumnLane]:
        """The device lane for `column`, whose full contents `series`
        holds; uploads on first use, negative-caches uncacheable
        columns. None -> caller uses its non-resident path."""
        with self._lock:
            if self.released:
                return None
            if column in self._lanes:
                lane = self._lanes[column]
                if lane is not None:
                    _HITS.inc()
                    self._hbm.touch()
                return lane
            _MISSES.inc()
            lane = self._upload_locked(column, series)
            self._lanes[column] = lane
            return lane

    def peek(self, column: str) -> Optional[ColumnLane]:
        """Already-uploaded lane for `column`, without counters or
        upload — route planning looks before it leaps (a peek must not
        skew hit/miss accounting or trigger H2D work on the host path)."""
        with self._lock:
            if self.released:
                return None
            return self._lanes.get(column)

    def _upload_locked(self, column: str,
                       series: pd.Series) -> Optional[ColumnLane]:
        enc = _encode_column(series)
        if enc is None:
            return None
        import jax

        from delta_tpu.ops.replay import pad_bucket
        from delta_tpu.ops.sqlops import _ensure_x64

        raw, dictionary = enc
        n = len(raw)
        npad = pad_bucket(max(n, 1))
        vals = np.full(npad, PAD_I64, np.int64)
        vals[:n] = raw
        kind = "int" if dictionary is None else "codes"
        with obs.device_dispatch("sql.operand_upload", key=(kind, npad),
                                 budget="sql-operand-lanes", units=npad,
                                 gate="sql") as dd:
            dd.h2d("vals", vals)
            _ensure_x64()
            dev = jax.device_put(vals)
        self._arrays.append(dev)
        self._nbytes += int(dev.nbytes)
        if not self._registered:
            self._hbm = hbm.register(
                self, kind=hbm.KIND_SQL_OPERANDS,
                table_path=self.table_path, version=self.version,
                arrays=tuple(self._arrays),
                rebuild_cost_class="cheap",  # lazy re-upload from host
                # shed under HBM pressure: release() marks the cache
                # dead and snapshot_operand_cache builds a fresh one on
                # the next query
                evictor=self.release,
            )
            self._registered = True
        else:
            self._hbm.grow(arrays=tuple(self._arrays),
                           nbytes=self._nbytes)
        return ColumnLane(kind, dev, n, dictionary,
                          int(raw.min()) if n else None,
                          int(raw.max()) if n else None)

    def resident_bytes(self) -> int:
        with self._lock:
            return self._nbytes

    def release(self) -> None:
        """Drop every column lane (version advance or serve-cache
        eviction). jax arrays are refcounted, so an in-flight join
        holding a lane finishes safely; the next query rebuilds."""
        with self._lock:
            self._lanes.clear()
            self._arrays = []
            self._nbytes = 0
            self._hbm.release()
            self._hbm = hbm.noop_handle()
            self.released = True


def snapshot_operand_cache(state) -> Optional[ResidentOperandCache]:
    """The state's resident operand cache, created on first use;
    None when `state` can't host one (duck-typed like
    `stats/device_index.py::snapshot_stats_index`)."""
    lock = getattr(state, "_operand_cache_lock", None)
    if lock is None:
        return None
    with lock:
        cache = state.operand_cache
        if cache is not None and not cache.released:
            return cache
        cache = ResidentOperandCache(
            table_path=getattr(state, "table_path", None),
            version=getattr(state, "version", None))
        state.operand_cache = cache
        # the cache is built implicitly by ordinary SQL queries, so a
        # state dropped outside the explicit-release paths (serve
        # eviction, version advance) must not read as a ledger leak:
        # the state's own GC releases the lanes (idempotent with the
        # explicit paths)
        weakref.finalize(state, ResidentOperandCache.release, cache)
        return cache


def release_state_operand_cache(state) -> None:
    """Release a state's operand cache, if any (duck-typed like
    `parallel/resident.py::release_snapshot_resident`)."""
    cache = getattr(state, "operand_cache", None)
    if cache is not None:
        cache.release()
        state.operand_cache = None
