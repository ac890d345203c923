"""Data skipping: prune files whose min/max/nullCount stats prove a
predicate can't match (reference `stats/DataSkippingReader.scala:287`
constructDataFilters).

The stats index is columnar: the `stats` JSON strings of all surviving
AddFiles are parsed in ONE `pyarrow.json.read_json` call into struct
columns (`numRecords`, `minValues.*`, `maxValues.*`, `nullCount.*`).
When the caller supplies the snapshot's `SnapshotState`, the parsed
stats are further columnarized once per version into the resident
device lanes of `stats/device_index.py`, and every compilable conjunct
is evaluated in one batched dispatch (`ops/skipping.py`, jit kernel or
bit-identical numpy twin per `parallel/gate.py::skip_route`); anything
the compiler can't express — string and complex columns, inexact
literals — falls back to the per-conjunct Arrow ladder in this module.

Semantics: a file is SKIPPED only when stats *prove* no row can match.
Missing stats (null stats string, missing column, or unparseable value)
always keep the file. NULL handling: `col op lit` can only match non-null
rows, so files where nullCount == numRecords are skippable for such
conjuncts — but only when both counts are present.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.json as pa_json

from delta_tpu import obs
from delta_tpu.expressions.tree import (
    Column,
    Comparison,
    Expression,
    In,
    IsNotNull,
    IsNull,
    Literal,
    Not,
    Or,
)

_DEVICE_PLANS = obs.counter("scan.device_plans")
_DEVICE_FALLBACKS = obs.counter("scan.device_fallbacks")


class StatsIndex:
    """Parsed stats for a batch of files."""

    def __init__(self, table: Optional[pa.Table], n: int):
        self._table = table
        self.n = n

    @staticmethod
    def from_stats_column(stats_col: pa.ChunkedArray,
                          schema: Optional[pa.Schema] = None) -> "StatsIndex":
        """Parse one stats string a row. With `schema` (the parsed
        schema of rows these will stand behind, `stats/device_index.py`)
        nothing is inferred and nothing rewritten: a value that does
        not read as the schema's type, or a key the schema lacks, gives
        an index with no table."""
        n = len(stats_col)
        arr = stats_col.combine_chunks() if isinstance(stats_col, pa.ChunkedArray) else stats_col
        if n == 0 or arr.null_count == n:
            return StatsIndex(None, n)
        # one-shot parse: substitute "{}" for null rows to keep row alignment
        filled = pc.fill_null(arr, "{}")
        # pretty-printed stats embed raw newlines, which would desync the
        # one-row-per-line framing below (parsed.num_rows != n -> ALL
        # skipping silently disabled). Raw newlines are illegal inside a
        # JSON string value (they must be escaped as \n), so every literal
        # newline in a stats row is structural whitespace — flatten it.
        filled = pc.replace_substring(filled, pattern="\r", replacement=" ")
        filled = pc.replace_substring(filled, pattern="\n", replacement=" ")
        options = None if schema is None else pa_json.ParseOptions(
            explicit_schema=schema, unexpected_field_behavior="error")
        joined = ("\n".join(filled.to_pylist()) + "\n").encode()
        try:
            parsed = pa_json.read_json(pa.BufferReader(joined),
                                       parse_options=options)
        except pa.ArrowInvalid:
            if schema is not None:
                return StatsIndex(None, n)
            # A non-finite float stat serializes as the string "NaN" /
            # "Infinity" / "-Infinity" (see collection.py); ONE such
            # file makes Arrow's JSON inference see a string/number mix
            # and refuse the column — which used to disable skipping
            # for the whole table. Nulling those tokens loses only
            # precision (a null stat means unknown -> keep), never
            # correctness: a raw `:"NaN"` byte sequence cannot occur
            # inside a JSON string value (its quote would be escaped),
            # so only whole stat values can match.
            for tok in ('"NaN"', '"Infinity"', '"-Infinity"'):
                filled = pc.replace_substring_regex(
                    filled, pattern=r":\s*" + tok, replacement=":null")
            joined = ("\n".join(filled.to_pylist()) + "\n").encode()
            try:
                parsed = pa_json.read_json(pa.BufferReader(joined))
            except pa.ArrowInvalid:
                return StatsIndex(None, n)
        if parsed.num_rows != n:
            return StatsIndex(None, n)
        return StatsIndex(parsed, n)

    def _leaf(self, group: str, name_path: tuple) -> Optional[np.ndarray]:
        """Return (values, valid) for e.g. group='minValues', col path.
        None when the column isn't in the index."""
        if self._table is None or group not in self._table.column_names:
            return None
        arr = self._table.column(group).combine_chunks()
        if not pa.types.is_struct(arr.type):
            return None
        for part in name_path:
            if not pa.types.is_struct(arr.type) or arr.type.get_field_index(part) < 0:
                return None
            arr = pc.struct_field(arr, part)
        return arr

    def num_records(self):
        if self._table is None or "numRecords" not in self._table.column_names:
            return None
        return self._table.column("numRecords").combine_chunks()

    def min_values(self, name_path):
        return self._leaf("minValues", name_path)

    def max_values(self, name_path):
        return self._leaf("maxValues", name_path)

    def null_count(self, name_path):
        return self._leaf("nullCount", name_path)


def _max_truncated(maxv) -> Optional[pa.Array]:
    """Per-file "this string max MAY be truncated" mask. The collector
    caps string maxValues at MAX_STRING_PREFIX_LENGTH with an upward
    tie-break (stats/collection.py), and foreign writers do the same,
    so any stored max AT the cap may differ from the true column max —
    comparisons that rely on the max being exact must keep such files."""
    if maxv is None or not (pa.types.is_string(maxv.type)
                            or pa.types.is_large_string(maxv.type)):
        return None
    from delta_tpu.stats.collection import MAX_STRING_PREFIX_LENGTH

    return pc.greater_equal(pc.utf8_length(maxv),
                            pa.scalar(MAX_STRING_PREFIX_LENGTH))


def _cmp_keep(op: str, minv, maxv, lit_arr) -> Optional[pa.Array]:
    """Keep-condition (nullable bool Arrow array) for `col op lit` given
    min/max arrays; None = cannot decide (keep).

    String maxValues get prefix-aware semantics: a truncated max is only
    a lower bound on the true max (tie-broken upward), so `maxv >= lit`
    may be false while rows above `lit` exist — every max-dependent
    verdict is widened to keep possibly-truncated files. minValues need
    no guard: a truncated min prefix sorts <= the true min, so min-side
    comparisons are already conservative."""
    try:
        trunc = _max_truncated(maxv)
        if op == "=":
            if minv is None or maxv is None:
                return None
            hi = pc.greater_equal(maxv, lit_arr)
            if trunc is not None:
                hi = pc.or_kleene(hi, trunc)
            return pc.and_kleene(pc.less_equal(minv, lit_arr), hi)
        if op == "<":
            return None if minv is None else pc.less(minv, lit_arr)
        if op == "<=":
            return None if minv is None else pc.less_equal(minv, lit_arr)
        if op == ">":
            if maxv is None:
                return None
            keep = pc.greater(maxv, lit_arr)
            return keep if trunc is None else pc.or_kleene(keep, trunc)
        if op == ">=":
            if maxv is None:
                return None
            keep = pc.greater_equal(maxv, lit_arr)
            return keep if trunc is None else pc.or_kleene(keep, trunc)
        if op == "!=":
            if minv is None or maxv is None:
                return None
            # skip only when min == max == lit (every row equals lit) —
            # and the max is exact, not a truncation-bumped prefix
            all_eq = pc.and_kleene(pc.equal(minv, lit_arr),
                                   pc.equal(maxv, lit_arr))
            if trunc is not None:
                all_eq = pc.and_kleene(all_eq, pc.invert(trunc))
            return pc.invert(all_eq)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, pa.ArrowTypeError):
        return None
    return None


def _conjunct_keep(conj: Expression, index: StatsIndex) -> Optional[pa.Array]:
    """Nullable keep-mask for one conjunct; None/null = keep."""
    if isinstance(conj, Or):
        left = _conjunct_keep(conj.left, index)
        right = _conjunct_keep(conj.right, index)
        if left is None or right is None:
            return None
        return pc.or_kleene(left, right)
    if isinstance(conj, Comparison):
        sides = (conj.left, conj.right)
        if isinstance(sides[0], Column) and isinstance(sides[1], Literal):
            colref, lit, op = sides[0], sides[1], conj.op
        elif isinstance(sides[1], Column) and isinstance(sides[0], Literal):
            flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}
            colref, lit, op = sides[1], sides[0], flip[conj.op]
        else:
            return None
        if lit.value is None:
            return None
        minv = index.min_values(colref.name_path)
        maxv = index.max_values(colref.name_path)
        try:
            lit_arr = pa.scalar(lit.value)
        except pa.ArrowInvalid:
            return None
        keep = _cmp_keep(op, minv, maxv, lit_arr)
        # additionally: an all-null column can't match col op lit
        nc = index.null_count(colref.name_path)
        nr = index.num_records()
        if nc is not None and nr is not None:
            try:
                not_all_null = pc.less(nc, nr)
                keep = not_all_null if keep is None else pc.and_kleene(keep, not_all_null)
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError, pa.ArrowTypeError):
                pass
        return keep
    if isinstance(conj, IsNull):
        child = conj.child
        if isinstance(child, Column):
            nc = index.null_count(child.name_path)
            if nc is None:
                return None
            try:
                return pc.greater(nc, pa.scalar(0))
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                return None
        return None
    if isinstance(conj, IsNotNull):
        child = conj.child
        if isinstance(child, Column):
            nc = index.null_count(child.name_path)
            nr = index.num_records()
            if nc is None or nr is None:
                return None
            try:
                return pc.less(nc, nr)
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
                return None
        return None
    if isinstance(conj, In):
        if isinstance(conj.child, Column) and conj.values:
            if any(v is None for v in conj.values):
                return None
            # range prefilter: one pass with min(values)/max(values)
            # bounds instead of len(values) passes — any file outside
            # [min, max] can't contain any listed value
            pre = None
            try:
                lo, hi = min(conj.values), max(conj.values)
            except TypeError:  # mixed uncomparable values
                lo = hi = None
            if lo is not None:
                k_lo = _conjunct_keep(
                    Comparison(">=", conj.child, Literal(lo)), index)
                k_hi = _conjunct_keep(
                    Comparison("<=", conj.child, Literal(hi)), index)
                if k_lo is not None and k_hi is not None:
                    pre = pc.and_kleene(k_lo, k_hi)
                elif k_lo is not None or k_hi is not None:
                    pre = k_lo if k_lo is not None else k_hi
            if pre is not None:
                # large lists: the range bound IS the verdict (still
                # conservative — a superset of the exact per-value OR)
                if len(conj.values) > 64:
                    return pre
                if not pc.any(pc.fill_null(pre, True)).as_py():
                    return pre  # nothing survives the range — done
            keeps = None
            for v in conj.values:
                k = _conjunct_keep(Comparison("=", conj.child, Literal(v)), index)
                if k is None:
                    return pre
                keeps = k if keeps is None else pc.or_kleene(keeps, k)
            if keeps is not None and pre is not None:
                keeps = pc.and_kleene(keeps, pre)
            return keeps
        return None
    if isinstance(conj, Not):
        inner = conj.child
        if isinstance(inner, Comparison):
            neg = {"=": "!=", "!=": "=", "<": ">=", "<=": ">", ">": "<=", ">=": "<"}
            return _conjunct_keep(
                Comparison(neg[inner.op], inner.left, inner.right), index
            )
        if isinstance(inner, IsNull):
            return _conjunct_keep(IsNotNull(inner.child), index)
        if isinstance(inner, IsNotNull):
            return _conjunct_keep(IsNull(inner.child), index)
        return None
    return None


def _to_physical(expr: Expression, schema) -> Optional[Expression]:
    """Rewrite logical column paths to physical names (stats JSON keys use
    physical names under column mapping). None = untranslatable -> keep."""
    from delta_tpu.columnmapping import physical_name_path

    if isinstance(expr, Column):
        phys = physical_name_path(schema, expr.name_path)
        return Column(phys) if phys is not None else None
    children = expr.children()
    if not children:
        return expr
    import dataclasses

    new_children = []
    for c in children:
        nc = _to_physical(c, schema)
        if nc is None:
            return None
        new_children.append(nc)
    field_names = [
        f.name for f in dataclasses.fields(expr)
        if isinstance(getattr(expr, f.name), Expression)
    ]
    replacements = dict(zip(field_names, new_children))
    return dataclasses.replace(expr, **replacements)


def skipping_mask(
    files: pa.Table,
    conjuncts: List[Expression],
    metadata,
    engine=None,
    state=None,
) -> np.ndarray:
    """Boolean keep-mask over `files` rows. True = must read the file.

    With `state` (the snapshot's `SnapshotState`), skipping plans
    through the resident stats index (`stats/device_index.py`): every
    compilable conjunct is evaluated in ONE batched dispatch over the
    encoded int64 lanes — jit kernel or its bit-identical numpy twin,
    chosen by `parallel/gate.py::skip_route` — and only the remainder
    (string/complex/missing-stats columns, inexact literals) walks the
    per-conjunct Arrow ladder below. Both routes AND into the same
    mask, so the result is route-independent by construction."""
    n = files.num_rows
    keep = np.ones(n, dtype=bool)
    if n == 0 or not conjuncts:
        return keep
    rs = None
    if state is not None:
        from delta_tpu.stats.device_index import snapshot_stats_index

        rs = snapshot_stats_index(state, files)
    index = rs.arrow_index if rs is not None \
        else StatsIndex.from_stats_column(files.column("stats"))
    if index._table is None:
        return keep
    if (
        metadata is not None
        and metadata.configuration.get("delta.columnMapping.mode", "none") != "none"
    ):
        schema = metadata.schema
        translated = []
        for conj in conjuncts:
            t = _to_physical(conj, schema)
            if t is not None:
                translated.append(t)
        conjuncts = translated
    fallback = conjuncts
    # read once: a version advance on another thread releases the index
    # (its fields go to None) while this plan still holds the arrays
    vals, valid = (rs.vals, rs.valid) if rs is not None else (None, None)
    if vals is not None and valid is not None:
        from delta_tpu.ops import skipping as ops_skipping
        from delta_tpu.parallel.gate import skip_route
        from delta_tpu.stats.device_index import compile_conjuncts

        block, fallback = compile_conjuncts(conjuncts, rs)
        if block is not None:
            route = skip_route(
                n, block.n_atoms,
                engine_enabled=bool(getattr(engine, "use_device_skip", False)),
            )
            if route == "device":
                from delta_tpu.parallel import gate as gate_mod
                from delta_tpu.resilience import device_faults
                try:
                    lanes = device_faults.shed_retry(
                        "skip", rs.device_lanes)
                    if lanes is None:
                        obs.gate_fell_back("skip", "host",
                                           reason="no-resident-lanes")
                        route = "host"
                    else:
                        keep &= device_faults.shed_retry(
                            "skip",
                            lambda: ops_skipping.skip_mask_block(
                                lanes[0], lanes[1], block, n))
                        gate_mod.route_ok("skip")
                        _DEVICE_PLANS.inc()
                        if fallback:
                            _DEVICE_FALLBACKS.inc(len(fallback))
                except Exception as e:
                    # disciplined fallback: classify (feeds the route
                    # breaker), bump the cataloged counter, host twin
                    if not device_faults.absorb_route_failure("skip", e):
                        raise
                    _DEVICE_FALLBACKS.inc()
                    obs.gate_fell_back(
                        "skip", "host",
                        reason=f"device-error:{type(e).__name__}")
                    route = "host"
            if route == "host":
                with obs.gate_observation("skip", "host"):
                    keep &= ops_skipping.host_skip_mask(
                        vals, valid, block, n)
            obs.set_attrs(skip_route=route, skip_atoms=block.n_atoms,
                          skip_fallback_conjuncts=len(fallback))
    for conj in fallback:
        mask = _conjunct_keep(conj, index)
        if mask is None:
            continue
        # null (missing stats for that file) -> keep
        filled = pc.fill_null(mask, True)
        keep &= np.asarray(filled, dtype=bool)
    return keep
