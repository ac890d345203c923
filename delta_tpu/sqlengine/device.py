"""DeviceSpine: routes the SQL executor's relational core — equi-join,
GROUP BY aggregation, ORDER BY / window sorts — through the device
kernels in `ops/sqlops.py`.

Role parity: this is the substrate the reference obtains from Spark
(`spark/src/main/scala/io/delta/sql/DeltaSparkSessionExtension.scala:84-173`
injects Delta's rules into Spark's distributed columnar engine; the
queries themselves then execute on that engine). Here the pandas
executor keeps planning/expression duties and the heavy relational
algebra runs on the accelerator. `HostEngine` keeps the pure-pandas
path, which stays the bit-for-bit parity oracle (the TPC-DS corpus in
tests/test_tpcds.py runs on both substrates).

Division of labor per operator:
- host: dictionary-encode keys (pandas factorize), reconstruct output
  frames with O(output) takes/gathers;
- device: sorts, segment reductions, scans (`ops/sqlops.py`).

Anything the device path does not support (object-dtype aggregation,
exotic aggs) falls back to pandas per-call — never per-query — so a
single unsupported aggregate does not evict the whole query from the
device."""

from __future__ import annotations

import functools
import logging
from typing import List, Optional, Sequence

import numpy as np
import pandas as pd

from delta_tpu import obs
from delta_tpu.obs.device import gate_fell_back
from delta_tpu.parallel.gate import sql_route

_log = logging.getLogger(__name__)

# route-contract instruments: the fallback counter bumps whenever the
# gate chose "device" but an operator input forced the pandas path
# mid-flight; device_queries counts queries that resolved to the spine
_FALLBACKS = obs.counter("sql.device_fallbacks")
_QUERIES = obs.counter("sql.device_queries")

sqlops = None  # set on first DeviceSpine construction (defers jax)


def _absorbing(method):
    """The route contract around one public operator entry point
    (`device_faults.guarded`): a transient device failure is absorbed
    and the operator returns None, so the executor keeps its pandas
    path; a permanent one surfaces; a non-None return reports success
    to the sql route breaker."""

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        from delta_tpu.resilience import device_faults

        return device_faults.guarded(
            "sql", lambda: method(self, *args, **kwargs), _FALLBACKS).value

    return wrapper


def _load_sqlops():
    """Lazy: `spine_for` must be importable (and cheap) in pure-host
    deployments — the jax-backed kernels load only when a spine is
    actually constructed."""
    global sqlops
    if sqlops is None:
        from delta_tpu.ops import sqlops as _ops

        sqlops = _ops
    return sqlops


_SUPPORTED_AGGS = {"sum", "count", "avg", "min", "max",
                   "stddev_samp", "var_samp"}


def _joint_codes(cols: Sequence[np.ndarray]) -> tuple[np.ndarray, int]:
    """Densify one or more aligned key columns into uint32 codes.
    NaN/None get real codes (pandas groupby(dropna=False) / NaN-joins
    semantics). Same radix-combine pattern as
    `ops/join.py::equi_join_device`."""
    codes = None
    for col in cols:
        c, _ = pd.factorize(col, sort=False, use_na_sentinel=False)
        c = c.astype(np.uint64)
        if codes is None:
            codes = c
        else:
            codes = codes * np.uint64(int(c.max(initial=0)) + 1) + c
        if int(codes.max(initial=0)) >= 1 << 32:
            _, codes = np.unique(codes, return_inverse=True)
            codes = codes.astype(np.uint64)
    if len(cols) > 1:
        # radix-combined codes are sparse; consumers (GroupAggregator
        # segment counts, first-occurrence reconstruction) need DENSE
        _, codes = np.unique(codes, return_inverse=True)
    codes = codes.astype(np.uint32)
    return codes, int(codes.max(initial=0)) + 1 if len(codes) else 0


def _series_values(s: pd.Series):
    """(numeric ndarray, valid mask, kind) for an aggregation input.
    kind: 'int' | 'float' | 'datetime' | None (unsupported)."""
    v = s.to_numpy()
    if v.dtype.kind in "ui" or v.dtype == bool:
        return v, np.ones(len(v), bool), "int"
    if v.dtype.kind == "f":
        return v, ~np.isnan(v), "float"
    if v.dtype.kind == "M":
        # normalize to ns ticks: consumers reconstruct results with
        # .view("datetime64[ns]"), so s/us/ms columns must not leak
        # their raw ticks through
        v_ns = v.astype("datetime64[ns]")
        return v_ns.view(np.int64), ~np.isnat(v_ns), "datetime"
    if str(s.dtype) in ("Int64", "Int32", "boolean"):
        valid = s.notna().to_numpy()
        return s.fillna(0).to_numpy(np.int64), valid, "int"
    return None, None, None


def _int64_lane(s: pd.Series) -> Optional[np.ndarray]:
    """Probe-side join key as a raw int64 lane (the dtypes
    `sqlengine/operands.py::_encode_column` caches as kind 'int').
    None -> the lane join can't apply; callers fall through to the
    joint-factorize path."""
    v = s.to_numpy()
    if v.dtype.kind in "ui" or v.dtype == bool:
        return v.astype(np.int64, copy=False)
    if v.dtype.kind == "M":
        return v.astype("datetime64[ns]").view(np.int64)
    if v.dtype.kind == "f":
        # nullable integer keys arrive from arrow as float64; the
        # null-key exclusion already dropped the NaNs, so an integral
        # remainder maps exactly onto the int64 domain (bounded to the
        # float64-exact range)
        if len(v) and (not np.isfinite(v).all()
                       or (v != np.floor(v)).any()
                       or np.abs(v).max() >= 2 ** 53):
            return None
        return v.astype(np.int64)
    if str(s.dtype) in ("Int64", "Int32", "boolean"):
        if s.isna().any():
            return None
        return s.to_numpy(np.int64)
    return None


class DeviceSpine:
    """Per-query device routing plus source-frame provenance: the
    executor registers each full-table materialized frame here
    (`register_source`), so joins whose build side is such a frame can
    consume the snapshot's resident operand cache instead of
    re-shipping key lanes. Each operator entry point resolves through
    `parallel/gate.py::sql_route` with its real operand sizes; a "host"
    verdict returns None and the executor keeps its pandas path."""

    def __init__(self, device=None):
        _load_sqlops()
        self.device = device
        # id(frame) -> (frame strong-ref, ResidentOperandCache,
        #               {qualified column -> raw column}); per-query, so
        # ids can't be recycled out from under us
        self._sources: dict = {}

    def register_source(self, frame: pd.DataFrame, state) -> None:
        """Record that `frame` is a full, unfiltered materialization of
        the snapshot whose loaded state is `state` (columns already
        alias-qualified). Only such frames may consume the per-version
        operand cache — a filtered frame's rows no longer align with
        the cached full-column lanes."""
        from delta_tpu.sqlengine.operands import snapshot_operand_cache

        cache = snapshot_operand_cache(state)
        if cache is None:
            return
        colmap = {c: c.split(".", 1)[1] for c in frame.columns
                  if isinstance(c, str) and "." in c}
        self._sources[id(frame)] = (frame, cache, colmap)

    def _route(self, op: str, n_rows: int, nbytes: int) -> bool:
        return sql_route(op, n_rows, nbytes,
                         engine_enabled=True) == "device"

    @staticmethod
    def _fell_back(reason: str) -> None:
        """The gate chose device but this operator's inputs forced the
        pandas path mid-flight. Returns None so callers can
        `return self._fell_back(...)`."""
        _FALLBACKS.inc()
        gate_fell_back("sql", "host", reason)
        return None

    # ------------------------------------------------------ group-by --

    @_absorbing
    def groupby(self, work: pd.DataFrame, names: List[str],
                agg_specs: dict) -> Optional[pd.DataFrame]:
        """Device GROUP BY over `work` (key cols `names`, one
        `__arg_<k>` column per non-star aggregate). Returns the
        aggregate frame matching the pandas path's shape, or None when
        an input needs the fallback."""
        if not names or not agg_specs:
            return None
        n = len(work)
        # operand estimate: int32 codes + ~(8B values + 1B valid) per agg
        if not self._route("group-agg", n, (4 + 9 * len(agg_specs)) * n):
            return None
        plans = []
        for k, f in agg_specs.items():
            if f.name not in _SUPPORTED_AGGS:
                return self._fell_back(f"unsupported-agg:{f.name}")
            if f.star:
                plans.append((k, f, None, None, None))
                continue
            v, valid, kind = _series_values(work[f"__arg_{k}"])
            if kind is None:
                return self._fell_back("unsupported-agg-dtype")
            if f.name in ("sum", "avg", "stddev_samp", "var_samp") \
                    and kind == "datetime":
                return self._fell_back("datetime-sum")
            if f.distinct and f.name != "count":
                return self._fell_back("distinct-non-count")
            plans.append((k, f, v, valid, kind))

        key_vals = [work[n].to_numpy() for n in names]
        codes, n_groups = _joint_codes(key_vals)
        if n_groups == 0:
            out = pd.DataFrame({n: pd.Series([], dtype=work[n].dtype)
                                for n in names})
            for k, f, *_ in plans:
                out[k] = []
            return out
        ga = sqlops.GroupAggregator(codes, n_groups, device=self.device)
        _, first_idx = np.unique(codes, return_index=True)

        out = pd.DataFrame({
            n: pd.Series(kv[first_idx]) for n, kv in
            zip(names, key_vals)})
        for k, f, v, valid, kind in plans:
            if f.star:
                out[k] = ga.sizes()
                continue
            if f.name == "count" and f.distinct:
                vc, _ = pd.factorize(work[f"__arg_{k}"], sort=False,
                                     use_na_sentinel=False)
                out[k] = ga.count_distinct(vc, valid)
                continue
            if f.name == "count":
                _, cnt = ga.reduce(np.zeros(len(codes), np.int64),
                                   valid, "count")
                out[k] = cnt
                continue
            if f.name in ("stddev_samp", "var_samp"):
                var, _ = ga.var(v, valid)
                out[k] = np.sqrt(var) if f.name == "stddev_samp" \
                    else var
                continue
            if f.name == "avg":
                s, cnt = ga.reduce(np.asarray(v, np.float64), valid,
                                   "sum")
                with np.errstate(invalid="ignore"):
                    out[k] = np.where(cnt > 0, s / np.maximum(cnt, 1),
                                      np.nan)
                continue
            agg, cnt = ga.reduce(v, valid, f.name)
            empty = cnt == 0
            if kind == "datetime":
                col = agg.view("datetime64[ns]").copy()
                col[empty] = np.datetime64("NaT")
                out[k] = col
            elif kind == "int" and not empty.any():
                out[k] = agg
            else:
                col = agg.astype(np.float64)
                col[empty] = np.nan
                out[k] = col
        return out

    # --------------------------------------------------------- joins --

    @_absorbing
    def merge(self, left: pd.DataFrame, right: pd.DataFrame, how: str,
              lk: List[str], rk: List[str],
              right_origin: Optional[pd.DataFrame] = None
              ) -> Optional[pd.DataFrame]:
        """Equi-join with pandas-merge output shape (all columns of
        both frames). Callers guarantee null-free keys (SQL null-key
        exclusion happens in `_merge_null_safe`). None -> the route
        chose the host merge.

        When the build side traces to a registered source frame
        (`right` itself, or `right_origin` when the caller's null-key
        exclusion derived `right` from it) and the join has one key,
        the snapshot's resident operand cache supplies the build lane
        — a warm cache ships only the probe side, and the route sees
        those bytes as already paid. Lane/frame alignment holds across
        queries because the single-key null-drop is deterministic:
        `right` is always "origin rows minus the key column's nulls",
        and the lane caches exactly that remainder."""
        n_l, n_r = len(left), len(right)
        cache = raw = None
        if len(rk) == 1:
            src = self._sources.get(
                id(right) if right_origin is None else id(right_origin))
            if src is not None:
                _frame, cache, colmap = src
                raw = colmap.get(rk[0])
                if raw is None:
                    cache = None
        hot = cache is not None and cache.peek(raw) is not None
        obs.set_attr("hot", hot)    # on the executor's `sql.join` span
        nbytes = 8 * n_l + (0 if hot else 8 * n_r)
        if not self._route("join", n_l + n_r, nbytes):
            return None
        if cache is not None:
            lane = cache.join_lane(raw, right[rk[0]])
            if lane is not None:
                out = self._merge_lanes(left, right, how, lk[0], lane)
                if out is not None:
                    return out
        with obs.span("join.encode", kind="codes", rows=n_l + n_r):
            codes, _ = _joint_codes([
                np.concatenate([left[a].to_numpy(), right[b].to_numpy()])
                for a, b in zip(lk, rk)])
        l_idx, r_idx = sqlops.join_pairs(codes[:n_l], codes[n_l:],
                                         how=how, device=self.device)
        return self._gather(left, right, how, l_idx, r_idx)

    def _merge_lanes(self, left: pd.DataFrame, right: pd.DataFrame,
                     how: str, lcol: str, lane) -> Optional[pd.DataFrame]:
        """Join `left[lcol]` against a resident build lane. The probe
        side encodes host-side to the lane's int64 domain; None when it
        can't (dtype mismatch) and the caller re-joins via the joint
        factorize path."""
        with obs.span("join.encode", kind="lanes", rows=len(left)):
            if lane.kind == "codes":
                lv = left[lcol].to_numpy()
                if lv.dtype.kind not in "OUS":
                    return None
                probe = lane.dictionary.get_indexer(lv)
                # probe values absent from the build dictionary can
                # never match: remap the -1 misses past every real code
                # (the pad sentinel stays reserved for padding)
                l_vals = np.where(probe < 0, len(lane.dictionary),
                                  probe).astype(np.int64)
            else:
                l_vals = _int64_lane(left[lcol])
                if l_vals is None:
                    return None
        pairs = sqlops.join_pairs_lanes(
            l_vals, r_resident=(lane.dev, lane.n, lane.least, lane.most),
            how=how, device=self.device)
        if pairs is None:
            return None
        return self._gather(left, right, how, *pairs)

    @staticmethod
    def _gather(left: pd.DataFrame, right: pd.DataFrame, how: str,
                l_idx: np.ndarray, r_idx: np.ndarray) -> pd.DataFrame:
        """Reconstruct the pandas-merge-shaped output from matched row
        index pairs (-1 = null-extended side)."""
        with obs.span("join.gather", rows=len(l_idx),
                      columns=left.shape[1] + right.shape[1]):
            lpart = left.take(np.where(l_idx >= 0, l_idx, 0)) \
                .reset_index(drop=True)
            rpart = right.take(np.where(r_idx >= 0, r_idx, 0)) \
                .reset_index(drop=True)
            if how in ("right", "outer"):
                lpart = lpart.where(pd.Series(l_idx >= 0))
            if how in ("left", "outer"):
                rpart = rpart.where(pd.Series(r_idx >= 0))
            return pd.concat([lpart, rpart], axis=1)

    # --------------------------------------------------------- sorts --

    def _order_lanes(self, s: pd.Series, asc: bool) -> list:
        """Encode one ORDER BY key into ascending device lanes:
        a null lane per Spark's rule (NULLS FIRST when asc, LAST when
        desc) and a direction-folded value lane."""
        v = s.to_numpy()
        if v.dtype.kind in "OUS":  # strings: ordinal codes
            codes, uniq = pd.factorize(v, sort=True)
            isna = codes < 0
            vals = np.where(isna, 0, codes).astype(np.int64)
        elif v.dtype.kind == "M":
            vals = v.view(np.int64)
            isna = np.isnat(v.astype("datetime64[ns]"))
            vals = np.where(isna, 0, vals)
        elif v.dtype.kind == "f":
            isna = np.isnan(v)
            vals = np.where(isna, 0.0, v)
        elif v.dtype.kind in "ui" or v.dtype == bool:
            isna = np.zeros(len(v), bool)
            vals = v.astype(np.int64)
        elif str(s.dtype) in ("Int64", "Int32", "boolean", "Float64"):
            isna = s.isna().to_numpy()
            vals = s.fillna(0).to_numpy(np.float64)
        else:
            return None  # unsupported dtype -> pandas fallback
        null_lane = np.where(isna, 0 if asc else 1, 1 if asc else 0) \
            .astype(np.uint8)
        if not asc:
            vals = -vals
        return [null_lane, vals]

    @_absorbing
    def sort_frame(self, frame: pd.DataFrame, cols: List[str],
                   ascs: List[bool]) -> Optional[pd.DataFrame]:
        """`_sql_sort` on device: multi-key stable sort with Spark
        null ordering. Preserves the original index values (like
        sort_values). None -> fallback."""
        if not len(frame):
            return frame
        n = len(frame)
        # per key: 8B value lane + 1B null lane; + 8B result iota
        if not self._route("sort", n, (9 * len(cols) + 8) * n):
            return None
        lanes = []
        for c, asc in zip(cols, ascs):
            ln = self._order_lanes(frame[c], asc)
            if ln is None:
                return self._fell_back("unsupported-sort-dtype")
            lanes.extend(ln)
        perm = sqlops.sort_permutation(lanes, device=self.device)
        return frame.iloc[perm]

    # ------------------------------------------------------- windows --

    @_absorbing
    def partition_transform(self, parts: List[pd.Series], s: pd.Series,
                            fn: str) -> Optional[pd.Series]:
        """groupby(parts).transform(fn) on device: aggregate per
        partition, broadcast back by group code."""
        # int32 codes + 8B values + 1B valid per row
        if not self._route("group-agg", len(s), 13 * len(s)):
            return None
        v, valid, kind = _series_values(s)
        if kind is None or (kind == "datetime" and fn in ("sum", "mean")):
            return self._fell_back("unsupported-window-agg")
        codes, n_groups = _joint_codes([p.to_numpy() for p in parts])
        if n_groups == 0:
            return pd.Series([], dtype=float, index=s.index)
        ga = sqlops.GroupAggregator(codes, n_groups, device=self.device)
        if fn == "count":
            _, cnt = ga.reduce(np.zeros(len(codes), np.int64), valid,
                               "count")
            return pd.Series(cnt[codes], index=s.index)
        if fn == "mean":
            sm, cnt = ga.reduce(np.asarray(v, np.float64), valid, "sum")
            with np.errstate(invalid="ignore"):
                agg = np.where(cnt > 0, sm / np.maximum(cnt, 1), np.nan)
            return pd.Series(agg[codes], index=s.index)
        agg, cnt = ga.reduce(v, valid, fn)
        empty = cnt[codes] == 0
        if kind == "datetime":
            out = agg[codes].view("datetime64[ns]").copy()
            out[empty] = np.datetime64("NaT")
        elif kind == "int" and not empty.any():
            out = agg[codes]  # keep int64 (exact, schema-parity)
        else:
            out = agg[codes].astype(np.float64)
            out[empty] = np.nan
        return pd.Series(out, index=s.index)

    def _window_order(self, parts: List[pd.Series],
                      order_items: list, n: int):
        """Shared window preamble: device sort by (partition, order
        keys); returns (perm, pb, kb) in sorted order, or None."""
        lanes = []
        part_codes = None
        if parts:
            part_codes, _ = _joint_codes([p.to_numpy() for p in parts])
            lanes.append(part_codes)
        key_lanes = []
        for s, asc in order_items:
            ln = self._order_lanes(s, asc)
            if ln is None:
                return None
            key_lanes.extend(ln)
        lanes.extend(key_lanes)
        perm = sqlops.sort_permutation(lanes, device=self.device)
        pb = np.zeros(n, bool)
        pb[0] = True
        if part_codes is not None:
            pc = part_codes[perm]
            pb[1:] = pc[1:] != pc[:-1]
        kb = pb.copy()
        for lane in key_lanes:
            kl = np.asarray(lane)[perm]
            kb[1:] |= kl[1:] != kl[:-1]
        return perm, pb, kb

    @_absorbing
    def window_rank(self, parts: List[pd.Series], order_items: list,
                    which: str, n: int,
                    index) -> Optional[pd.Series]:
        if n == 0:
            return pd.Series(np.empty(0, np.int64), index=index)
        nkeys = len(parts) + len(order_items)
        if not self._route("sort", n, (9 * max(nkeys, 1) + 8) * n):
            return None
        pre = self._window_order(parts, order_items, n)
        if pre is None:
            return self._fell_back("unsupported-sort-dtype")
        perm, pb, kb = pre
        rn, rk, dr = sqlops.window_ranks(pb, kb, device=self.device)
        picked = {"row_number": rn, "rank": rk, "dense_rank": dr}[which]
        out = np.empty(n, np.int64)
        out[perm] = picked
        return pd.Series(out, index=index)

    @_absorbing
    def window_running(self, parts: List[pd.Series], order_items: list,
                       s: pd.Series, fn: str, frame_kind: str,
                       index) -> Optional[pd.Series]:
        """Running sum/mean/min/max/count with the SQL default frame;
        `frame_kind` 'range' shares values across order-key peers,
        'rows' does not."""
        n = len(s)
        if n == 0:
            return pd.Series(np.empty(0, np.float64), index=index)
        nkeys = len(parts) + len(order_items)
        if not self._route("sort", n, (9 * max(nkeys, 1) + 17) * n):
            return None
        v, valid, kind = _series_values(s)
        if kind is None or kind == "datetime":
            return self._fell_back("unsupported-window-agg")
        pre = self._window_order(parts, order_items, n)
        if pre is None:
            return self._fell_back("unsupported-sort-dtype")
        perm, pb, kb = pre
        vals, cnts = sqlops.window_running(
            np.asarray(v, np.float64)[perm], valid[perm], pb, fn,
            device=self.device)
        if frame_kind == "range":
            vals, cnts = sqlops.window_peer_last(vals, cnts, kb,
                                                 device=self.device)
        res = vals.copy()
        if fn == "count":
            res = cnts.astype(np.float64)
        else:
            res[cnts == 0] = np.nan
        out = np.empty(n, np.float64)
        out[perm] = res
        return pd.Series(out, index=index)


def _link_supports_sql_offload() -> bool:
    """SQL operators ship full columns both ways, so the device has to
    be there at all before the link economics (`gate.sql_route`) are
    worth consulting: true when JAX has a usable backend."""
    try:
        import jax

        jax.default_backend()
        return True
    except (ImportError, RuntimeError) as e:
        # no usable jax backend at all — offload is simply unavailable
        _log.debug("device backend unavailable for SQL offload "
                   "(%s: %s)", type(e).__name__, e)
        return False


def spine_for(engine, catalog=None) -> Optional[DeviceSpine]:
    """Resolve whether this query runs the device spine, through the
    route gate (`parallel/gate.py::sql_route`, op "query"): the
    DELTA_TPU_DEVICE_SQL override outranks everything, then a failed
    link probe forces host — recorded as a `probe-failed` gate
    decision, never a silent None — then the engine's `use_device_sql`
    opt-in (TpuEngine: on) and the link economics decide."""
    eng = engine
    if eng is None and catalog is not None:
        eng = getattr(catalog, "engine", None)
    if eng is None:
        # tables opened with engine=None resolve to default_engine()
        # (TpuEngine) — the spine decision must mirror that
        use = True
    else:
        use = bool(getattr(eng, "use_device_sql", False))
    probe_failed = use and not _link_supports_sql_offload()
    route = sql_route("query", 1, 0, engine_enabled=use,
                      probe_failed=probe_failed)
    if route != "device":
        return None
    _QUERIES.inc()
    return DeviceSpine()
