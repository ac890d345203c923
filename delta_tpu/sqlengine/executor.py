"""SELECT executor: name resolution, scan pushdown, join planning,
vectorized evaluation over pandas, aggregation, ordering.

Pipeline (the single-node mirror of the reference's Spark plan):

1. Resolve every table ref to a Delta snapshot (or sub-select frame),
   collect the referenced column set per table, and scan with column
   projection + pushed-down single-table predicates (partition pruning
   and stats skipping ride `Snapshot.scan(filter=...)`, the same path
   the reference drives through `PrepareDeltaScan`).
2. Join: explicit JOIN ... ON clauses in order, then the implicit
   comma-list via equi-join edges mined from WHERE conjuncts (the
   TPC-DS style `from a, b where a.k = b.k`); unconnected tables fall
   back to cross joins.
3. Residual WHERE on the joined frame, aggregate (GROUP BY / HAVING)
   with Spark null semantics (null group keys kept, sum of all-null ->
   null), ORDER BY (nulls first when ascending, last when descending),
   LIMIT, projection.

WHERE pushdown never applies to the null-supplying side of an outer
join (rows there may be null-extended, so pre-filtering the scan would
change which outer rows survive residual predicates — the anti-join
idiom `WHERE b.x IS NULL`).
"""

from __future__ import annotations

import datetime
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu import obs
from delta_tpu.errors import AmbiguousColumnError, CatalogTableError, DeltaError, SqlParseError, SubqueryShapeError, UnresolvedColumnError, UnsupportedSqlError
from delta_tpu.sqlengine.parser import (
    And, Between, BinOp, CaseWhen, Cast, Cmp, Col, Exists, Func, InList,
    InSelect, Interval, IsNull, JoinClause, Like, Lit, Neg, Not, Or,
    Query, ScalarSelect, Select, SelectItem, Star, TableRef, Window,
    parse_query,
)

_AGGS = {"count", "sum", "min", "max", "avg", "stddev_samp", "var_samp"}
_NULL_SUPPLYING = {"left outer": ("right",), "right outer": ("left",),
                   "full outer": ("left", "right")}


# ---------------------------------------------------------------- API --

_SCAN_FILES = obs.counter("sql.scan_files")


def execute_select(statement_or_ast, engine=None, catalog=None,
                   ctes=None, name: Optional[str] = None) -> pa.Table:
    """Run one SELECT. `name` is the caller's name for the query: it
    goes on the `sql.query` span and nowhere else."""
    with obs.span("sql.query") as sp:
        if name:
            sp.set_attr("name", name)
        if isinstance(statement_or_ast, Select):
            q = Query(selects=[statement_or_ast])
        elif isinstance(statement_or_ast, Query):
            q = statement_or_ast
        else:
            q = parse_query(statement_or_ast)
        df, names = _run_query(q, engine, catalog, dict(ctes or {}))
        out = pa.Table.from_pandas(df, preserve_index=False)
        sp.set_attr("rows", out.num_rows)
        return out.rename_columns(names)


def _run_query(q: Query, engine, catalog, ctes) -> Tuple[pd.DataFrame,
                                                         List[str]]:
    """Execute a full query: materialize WITH bindings in order (a CTE
    sees the ones before it), run each UNION ALL branch against the
    same bindings, concatenate positionally, then apply the trailing
    ORDER BY/LIMIT on the union result."""
    for name, sub in q.ctes:
        # a CTE body sees the bindings before it, not its siblings' —
        # pass a copy so its own nested WITHs never leak outward
        df, names = _run_query(sub, engine, catalog, dict(ctes))
        df = df.copy()
        df.columns = names
        ctes[name.lower()] = df
    frames = []
    out_names: List[str] = []
    for i, sel in enumerate(q.selects):
        if isinstance(sel, Query):  # parenthesized nested set-op
            df, names = _run_query(sel, engine, catalog, dict(ctes))
        else:
            df, names = _Exec(engine, catalog, ctes).run(sel)
        if i == 0:
            out_names = names
        elif len(names) != len(out_names):
            raise SqlParseError(
                f"UNION ALL branches have different widths "
                f"({len(out_names)} vs {len(names)})",
                error_class="DELTA_UNION_WIDTH_MISMATCH")
        df = df.copy()
        df.columns = [f"__c{j}" for j in range(len(names))]
        frames.append(df)
    result = frames[0]
    for op, f in zip(q.union_ops, frames[1:]):
        if op in ("all", "distinct"):
            result = pd.concat([result, f], ignore_index=True)
            if op == "distinct":
                result = result.drop_duplicates(ignore_index=True)
            continue
        # set semantics (SQL INTERSECT/EXCEPT are distinct, and NULLs
        # compare EQUAL for set operations — pandas merge's NaN
        # matching is the right behavior here, unlike joins)
        a = result.drop_duplicates(ignore_index=True)
        b = f.drop_duplicates(ignore_index=True)
        cols = list(a.columns)
        if op == "intersect":
            result = a.merge(b, how="inner", on=cols)
        else:  # except
            marked = a.merge(b, how="left", on=cols, indicator=True)
            result = marked[marked["_merge"] == "left_only"] \
                .drop(columns="_merge").reset_index(drop=True)
    if q.order_by:
        for i in range(len(q.order_by) - 1, -1, -1):
            e, asc = q.order_by[i]
            lower_names = [n.lower() for n in out_names]
            if isinstance(e, Lit) and isinstance(e.value, int) \
                    and 1 <= e.value <= len(out_names):
                pos = e.value - 1
            elif (isinstance(e, Col) and len(e.parts) == 1
                    and e.parts[0].lower() in lower_names):
                pos = lower_names.index(e.parts[0].lower())
            else:
                raise UnsupportedSqlError(
                    "ORDER BY after UNION ALL must reference output "
                    f"column names or ordinals; got {type(e).__name__}",
                    error_class="DELTA_ORDER_BY_AFTER_UNION")
            result = _sql_sort(result, [f"__c{pos}"], [asc])
    if q.limit is not None:
        result = result.head(q.limit)
    return result.reset_index(drop=True), out_names


# ------------------------------------------------------------ helpers --

def _canon(e, resolve) -> str:
    """Canonical key for an expression with columns resolved to their
    physical names — `dt.d_year` and a bare `d_year` that resolves to
    the same physical column share a key."""
    if isinstance(e, Col):
        return f"col:{resolve(e)}"
    if isinstance(e, Lit):
        return f"lit:{e.value!r}"
    if isinstance(e, BinOp):
        return f"({_canon(e.left, resolve)}{e.op}{_canon(e.right, resolve)})"
    if isinstance(e, Cmp):
        return f"({_canon(e.left, resolve)}{e.op}{_canon(e.right, resolve)})"
    if isinstance(e, And):
        return "and(" + ",".join(_canon(x, resolve) for x in e.items) + ")"
    if isinstance(e, Or):
        return "or(" + ",".join(_canon(x, resolve) for x in e.items) + ")"
    if isinstance(e, Not):
        return f"not({_canon(e.item, resolve)})"
    if isinstance(e, Neg):
        return f"neg({_canon(e.item, resolve)})"
    if isinstance(e, Func):
        inner = "*" if e.star else ",".join(
            _canon(a, resolve) for a in e.args)
        d = "distinct " if e.distinct else ""
        return f"{e.name}({d}{inner})"
    if isinstance(e, CaseWhen):
        parts = [f"when {_canon(c, resolve)} then {_canon(v, resolve)}"
                 for c, v in e.whens]
        if e.else_ is not None:
            parts.append(f"else {_canon(e.else_, resolve)}")
        return "case(" + ";".join(parts) + ")"
    if isinstance(e, Between):
        neg = "not " if e.negated else ""
        return (f"{neg}between({_canon(e.item, resolve)},"
                f"{_canon(e.lo, resolve)},{_canon(e.hi, resolve)})")
    if isinstance(e, InList):
        neg = "not " if e.negated else ""
        return (f"{neg}in({_canon(e.item, resolve)};"
                + ",".join(_canon(v, resolve) for v in e.values) + ")")
    if isinstance(e, IsNull):
        return f"isnull({_canon(e.item, resolve)},{e.negated})"
    if isinstance(e, Like):
        return f"like({_canon(e.item, resolve)},{e.pattern!r},{e.negated})"
    if isinstance(e, Cast):
        return f"cast({_canon(e.item, resolve)} as {e.type_name})"
    if isinstance(e, Interval):
        return f"interval:{e.n}:{e.unit}"
    if isinstance(e, Window):
        parts = ",".join(_canon(p, resolve) for p in e.partition_by)
        orders = ",".join(f"{_canon(o, resolve)}:{a}"
                          for o, a in e.order_by)
        return (f"win({_canon(e.func, resolve)};part={parts};"
                f"ord={orders};{e.frame})")
    if isinstance(e, (InSelect, Exists, ScalarSelect)):
        return f"subquery:{id(e)}"
    raise UnsupportedSqlError(f"cannot canonicalize {type(e).__name__}")


def _has_agg(e) -> bool:
    found = False

    def chk(x):
        nonlocal found
        if isinstance(x, Func) and x.name in _AGGS:
            found = True

    _walk_exprs(e, chk)
    return found


def _split_and(e) -> list:
    if isinstance(e, And):
        out = []
        for x in e.items:
            out.extend(_split_and(x))
        return out
    return [e] if e is not None else []


def _walk_exprs(e, fn):
    """Visit e and sub-expressions (does not descend into subqueries)."""
    if e is None:
        return
    fn(e)
    if isinstance(e, (BinOp, Cmp)):
        _walk_exprs(e.left, fn)
        _walk_exprs(e.right, fn)
    elif isinstance(e, (And, Or)):
        for x in e.items:
            _walk_exprs(x, fn)
    elif isinstance(e, (Not, Neg, IsNull, Like, Cast)):
        _walk_exprs(e.item, fn)
    elif isinstance(e, Func):
        for a in e.args:
            _walk_exprs(a, fn)
    elif isinstance(e, CaseWhen):
        for c, v in e.whens:
            _walk_exprs(c, fn)
            _walk_exprs(v, fn)
        _walk_exprs(e.else_, fn)
    elif isinstance(e, Between):
        _walk_exprs(e.item, fn)
        _walk_exprs(e.lo, fn)
        _walk_exprs(e.hi, fn)
    elif isinstance(e, (InList,)):
        _walk_exprs(e.item, fn)
        for v in e.values:
            _walk_exprs(v, fn)
    elif isinstance(e, InSelect):
        _walk_exprs(e.item, fn)
    elif isinstance(e, Window):
        # visit the window func's ARGS (not the func itself: an outer
        # avg in `avg(sum(x)) over ...` is not a row aggregate, but
        # its sum(x) argument is) plus partition/order expressions
        for a in e.func.args:
            _walk_exprs(a, fn)
        for p in e.partition_by:
            _walk_exprs(p, fn)
        for o, _ in e.order_by:
            _walk_exprs(o, fn)


def _render(e) -> str:
    """Spark-style output name for an unaliased expression."""
    if isinstance(e, Col):
        return e.parts[-1]
    if isinstance(e, Func):
        if e.star:
            return f"{e.name}(*)"
        d = "distinct " if e.distinct else ""
        return f"{e.name}({d}{', '.join(_render(a) for a in e.args)})"
    if isinstance(e, Lit):
        return repr(e.value)
    if isinstance(e, BinOp):
        return f"({_render(e.left)} {e.op} {_render(e.right)})"
    return type(e).__name__.lower()


def _merge_null_safe(left: pd.DataFrame, right: pd.DataFrame, how: str,
                     lk: List[str], rk: List[str],
                     spine=None) -> pd.DataFrame:
    """SQL join: NULL keys never match (pandas merge matches NaN/None
    to each other). Rows with a null key are excluded from matching;
    sides preserved by `how` get them re-appended null-extended.
    With a DeviceSpine the match itself runs on the device join
    kernel; the null-key bookkeeping stays identical."""
    from delta_tpu.obs.device import gate_observation

    def match(lm, rm, origin):
        """The join of the null-free sides: on the device where the
        gate sends it, else the pandas merge, which runs under the
        observation scope so its cost joins the decision record."""
        if spine is None:
            return lm.merge(rm, how=how, left_on=lk, right_on=rk)
        merged = spine.merge(lm, rm, how, lk, rk, right_origin=origin)
        if merged is not None:
            sp.set_attr("route", "device")
            return merged
        with gate_observation("sql", "host"):
            return lm.merge(rm, how=how, left_on=lk, right_on=rk)

    with obs.span("sql.join", how=how, n_left=len(left),
                  n_right=len(right), route="host", hot=False) as sp:
        # a phase of its own where the device may take the match (the
        # gate is asked after it); without a spine the join is its
        # merge, and this is a span under `verbose` alone
        with obs.span("join.nulls", _verbose=spine is None):
            lnull = left[lk].isna().any(axis=1)
            rnull = right[rk].isna().any(axis=1)
            l_any, r_any = lnull.any(), rnull.any()
            # keep the original object when a side is already null-free
            # (the spine's operand-cache lookup keys on frame identity),
            # and pass the pre-exclusion right as provenance: for a
            # single-key join the null-drop is exactly "rows minus that
            # column's nulls", so the cached lane built from one query's
            # rm aligns with every other query's rm
            lm = left[~lnull] if l_any else left
            rm = right[~rnull] if r_any else right
        if not l_any and not r_any:  # hot path: no copies
            merged = match(left, right, None)
        else:
            merged = match(lm, rm, right)
            extra = []
            if how in ("left", "outer") and l_any:
                extra.append(left[lnull])
            if how in ("right", "outer") and r_any:
                extra.append(right[rnull])
            if extra:
                merged = pd.concat([merged] + extra, ignore_index=True)
        sp.set_attr("rows", len(merged))
        return merged


_EXACT_DIGITS = 15     # 10**15 < 2**53: the unscaled value is a double


def _decimals_as_float64(table: pa.Table) -> pa.Table:
    """The frame computes in float64, so a `decimal(p,s)` column is
    turned into one here, a buffer at a time, and never passes through a
    Python `Decimal` a value. At p <= 15 the unscaled value is exact in
    a double and so is 10**s, so their quotient is the double nearest
    the decimal: what `float(Decimal)` gives, a sum of cents exact to
    the cent. (Arrow's own cast multiplies by an inexact 10**-s and is
    an ulp off on one value in eight.) Past 15 digits that cast is what
    there is, and the double is only near."""
    for i, field in enumerate(table.schema):
        kind = field.type
        if not pa.types.is_decimal(kind):
            continue
        column = table.column(i)
        if kind.precision <= _EXACT_DIGITS:
            wide = pa.decimal128(kind.precision, kind.scale)
            column = pa.chunked_array(
                [_exact_float64(chunk.cast(wide), kind.scale)
                 for chunk in column.chunks], pa.float64())
        else:
            column = pc.cast(column, pa.float64())
        table = table.set_column(i, field.name, column)
    return table


def _exact_float64(chunk: pa.Array, scale: int) -> pa.Array:
    """A `decimal128` chunk of at most 15 digits as float64: the low
    word of each little-endian value is the unscaled integer."""
    words = np.frombuffer(chunk.buffers()[1], np.int64,
                          2 * len(chunk), 16 * chunk.offset)
    values = words[::2].astype(np.float64) / float(10 ** scale)
    if not chunk.null_count:
        return pa.array(values)
    return pa.array(values, mask=chunk.is_null().to_numpy(
        zero_copy_only=False))


def _normalize_frame(df: pd.DataFrame) -> pd.DataFrame:
    """Post-to_pandas cleanup: date32 -> datetime64."""
    for c in df.columns:
        s = df[c]
        if s.dtype == object and len(s):
            first = s.dropna().head(1)
            if len(first) and isinstance(first.iloc[0], datetime.date) \
                    and not isinstance(first.iloc[0], datetime.datetime):
                df[c] = pd.to_datetime(s)
    return df


# -------------------------------------------------------- the executor --

class _Exec:
    def __init__(self, engine, catalog, ctes=None):
        self.engine = engine
        self.catalog = catalog
        self.ctes = ctes or {}
        from delta_tpu.sqlengine.device import spine_for

        self.spine = spine_for(engine, catalog)

    # -- table materialization ------------------------------------------
    def _snapshot(self, ref: TableRef):
        from delta_tpu.table import Table

        if ref.kind == "path":
            from delta_tpu.sql import _PATH_GUARD

            guard = _PATH_GUARD.get()
            if guard is not None:
                guard(ref.value)
            table = Table.for_path(ref.value, self.engine)
        else:
            if self.catalog is None:
                raise CatalogTableError(
                    f"table name {ref.value!r} requires a catalog "
                    "(pass catalog=)")
            table = self.catalog.table(ref.value)
        if ref.tt_version is not None:
            return table.snapshot_at(ref.tt_version)
        if ref.tt_timestamp is not None:
            from delta_tpu.sql import _timestamp_ms

            return table.snapshot_as_of_timestamp(
                _timestamp_ms(ref.tt_timestamp))
        return table.latest_snapshot()

    def run(self, sel: Select) -> Tuple[pd.DataFrame, List[str]]:
        # ---- source inventory -----------------------------------------
        sources: List[dict] = []  # {alias, ref, snap|frame, cols}
        seen_aliases = set()
        for i, ref in enumerate(list(sel.froms)
                                + [j.ref for j in sel.joins]):
            if ref.kind == "subquery":
                if isinstance(ref.value, Query):
                    sub_df, sub_names = _run_query(
                        ref.value, self.engine, self.catalog,
                        dict(self.ctes))
                else:
                    sub_df, sub_names = _Exec(self.engine, self.catalog,
                                              self.ctes).run(ref.value)
                sub_df.columns = sub_names
                alias = ref.alias or f"_s{i}"
                src = {"alias": alias, "frame": sub_df,
                       "cols": list(sub_df.columns), "snap": None}
            elif ref.kind == "name" and ref.value.lower() in self.ctes:
                # WITH binding: shared frame, copied per reference
                # (q47-style self-joins alias the same CTE 3x and the
                # materializer renames columns in place)
                cte_df = self.ctes[ref.value.lower()].copy()
                alias = ref.alias or ref.value
                src = {"alias": alias, "frame": cte_df,
                       "cols": list(cte_df.columns), "snap": None}
            else:
                snap = self._snapshot(ref)
                alias = ref.alias or (
                    ref.value.split(".")[-1] if ref.kind == "name"
                    else f"_t{i}")
                src = {"alias": alias, "snap": snap, "frame": None,
                       "cols": [f.name for f in snap.schema.fields]}
            if alias.lower() in seen_aliases:
                raise AmbiguousColumnError(f"duplicate table alias {alias!r}")
            seen_aliases.add(alias.lower())
            sources.append(src)
        # sources[len(froms) + k] belongs to sel.joins[k]
        join_aliases = [sources[len(sel.froms) + k]["alias"]
                        for k in range(len(sel.joins))]

        by_alias = {s["alias"]: s for s in sources}
        # case-insensitive like Spark: SR_FEE resolves to sr_fee
        lower_alias = {s["alias"].lower(): s["alias"] for s in sources}
        col_owners: Dict[str, List[tuple]] = {}
        for s in sources:
            s["lower_cols"] = {c.lower(): c for c in s["cols"]}
            for c in s["cols"]:
                col_owners.setdefault(c.lower(), []).append(
                    (s["alias"], c))

        def resolve(col: Col) -> str:
            if len(col.parts) >= 2:
                alias, name = col.parts[-2], col.parts[-1]
                alias = lower_alias.get(alias.lower())
                if alias is None:
                    raise UnresolvedColumnError(
                        f"table alias {col.parts[-2]!r} not found "
                        f"for column {col.text!r}")
                actual = by_alias[alias]["lower_cols"].get(name.lower())
                if actual is None:
                    raise UnresolvedColumnError(
                        f"column {col.text!r} not found in {alias!r}")
                return f"{alias}.{actual}"
            name = col.parts[0]
            owners = col_owners.get(name.lower(), [])
            if len(owners) == 1:
                alias, actual = owners[0]
                return f"{alias}.{actual}"
            if not owners:
                raise UnresolvedColumnError(
                    f"column {name!r} not found; not in scope of any "
                    f"table ({sorted(by_alias)})")
            raise AmbiguousColumnError(
                f"column {name!r} is ambiguous "
                f"(in {[a for a, _ in owners]}); qualify "
                "with a table alias — not in scope unqualified")

        self._resolve = resolve
        self._outer_aliases = set(by_alias)

        # ---- referenced columns per alias (projection) ----------------
        needed: Dict[str, set] = {s["alias"]: set() for s in sources}
        select_star = any(isinstance(it.expr, Star) for it in sel.items)

        def note(e):
            if isinstance(e, Col):
                try:
                    phys = resolve(e)
                except DeltaError:
                    return  # surfaces with a proper error during eval
                alias, name = phys.split(".", 1)
                needed[alias].add(name)
            elif isinstance(e, (ScalarSelect, InSelect, Exists)):
                # correlated subquery: outer columns referenced inside
                # the subquery's WHERE must survive projection (inner
                # names that don't resolve out here no-op in note)
                for c in _split_and(e.select.where):
                    def sub_note(x):
                        if isinstance(x, Col):
                            note(x)
                    _walk_exprs(c, sub_note)

        for it in sel.items:
            _walk_exprs(it.expr, note)
        for e in ([sel.where, sel.having] + sel.group_by
                  + [o for o, _ in sel.order_by]
                  + [j.on for j in sel.joins]):
            _walk_exprs(e, note)
        if select_star:
            for s in sources:
                needed[s["alias"]] = set(s["cols"])

        # ---- pushdown classification ----------------------------------
        conjuncts = _split_and(sel.where)
        null_supplying = set()
        for k, j in enumerate(sel.joins):
            sides = _NULL_SUPPLYING.get(j.kind, ())
            if "right" in sides:
                null_supplying.add(join_aliases[k])
            if "left" in sides:
                # everything joined before this clause can be
                # null-extended by it
                null_supplying.update(
                    s["alias"] for s in sources[:len(sel.froms) + k])
        pushed: Dict[str, list] = {s["alias"]: [] for s in sources}
        frame_pushed: Dict[str, list] = {s["alias"]: [] for s in sources}
        frame_aliases = {s["alias"] for s in sources
                         if s["frame"] is not None}
        for conj in conjuncts:
            target = self._sole_alias(conj, resolve)
            if target and target not in null_supplying:
                if target in frame_aliases:
                    # derived-table selection pushdown: filter the
                    # CTE/subquery frame BEFORE joining (q4's 6-way
                    # year_total self-join otherwise multiplies 6x per
                    # merge before the year/type filters ever apply)
                    frame_pushed[target].append(conj)
                    continue
                tree = self._to_tree(conj, resolve, target)
                if tree is not None:
                    pushed[target].append(tree)

        # ---- materialize frames ---------------------------------------
        for s in sources:
            if s["frame"] is not None:
                df = s["frame"]
                df.columns = [f"{s['alias']}.{c}" for c in df.columns]
                for conj in frame_pushed[s["alias"]]:
                    df = df[self._truth(self._eval(conj, df))]
                s["frame"] = df
                continue
            filt = None
            for t in pushed[s["alias"]]:
                filt = t if filt is None else (filt & t)
            # schema order, not sorted: SELECT * must present columns
            # in table order
            cols = [c for c in s["cols"] if c in needed[s["alias"]]] \
                or s["cols"][:1]
            full_rows = filt is None
            with obs.span("sql.scan", table=s["alias"],
                          columns=len(cols),
                          pushed=len(pushed[s["alias"]])) as sp:
                scan = s["snap"].scan(filter=filt, columns=cols)
                try:
                    arrow = scan.to_arrow()
                except pa.lib.ArrowNotImplementedError:
                    # type-mismatched pushdown (e.g. date32 column vs
                    # the query's string literal): drop the scan filter
                    # — the residual WHERE still applies the predicate
                    # with the executor's coercions
                    scan = s["snap"].scan(filter=None, columns=cols)
                    arrow = scan.to_arrow()
                    full_rows = True
                with obs.span("sql.frame", rows=arrow.num_rows,
                              columns=arrow.num_columns) as fsp:
                    if fsp.recording:
                        # not `nbytes`: that walks every chunk's slices,
                        # 40-70 ms for a fact table's 1,824 batches
                        fsp.set_attrs(
                            decimal_columns=sum(
                                pa.types.is_decimal(f.type)
                                for f in arrow.schema),
                            bytes=arrow.get_total_buffer_size())
                    df = _normalize_frame(
                        _decimals_as_float64(arrow).to_pandas())
                files = scan.add_files_table().num_rows   # the plan's, kept
                _SCAN_FILES.inc(files)
                sp.set_attrs(files=files, rows=len(df))
            df.columns = [f"{s['alias']}.{c}" for c in df.columns]
            s["frame"] = df
            if full_rows and self.spine is not None:
                # full-table materialization: eligible for the
                # snapshot's resident operand cache (the scan above
                # already loaded the state)
                state = getattr(s["snap"], "_state", None)
                if state is not None:
                    self.spine.register_source(df, state)

        # ---- joins ----------------------------------------------------
        implicit = [s["alias"] for s in sources
                    if s["alias"] not in set(join_aliases)]
        # equi-edges from WHERE (implicit joins only)
        edges = []   # (alias_a, col_a, alias_b, col_b, conj)
        consumed = set()
        def _col_eq(c):
            if (isinstance(c, Cmp) and c.op == "="
                    and isinstance(c.left, Col)
                    and isinstance(c.right, Col)):
                try:
                    return resolve(c.left), resolve(c.right)
                except DeltaError:
                    return None
            return None

        for conj in conjuncts:
            eq = _col_eq(conj)
            if eq:
                pa_, pb_ = eq
                aa, ab = pa_.split(".", 1)[0], pb_.split(".", 1)[0]
                if aa != ab:
                    edges.append((aa, pa_, ab, pb_, conj))
            elif isinstance(conj, Or):
                # factor join equalities common to EVERY branch of an
                # OR (TPC-DS q48 style: each branch repeats
                # `cd_demo_sk = ss_cdemo_sk`); the OR itself stays in
                # the residual filter, but the implied equality is a
                # valid equi-join edge — without it the planner falls
                # back to an exploding cross join
                branch_sets = []
                for br in conj.items:
                    eqs = set()
                    for c in _split_and(br):
                        e2 = _col_eq(c)
                        if e2:
                            eqs.add(tuple(sorted(e2)))
                    branch_sets.append(eqs)
                for pa_, pb_ in set.intersection(*branch_sets) \
                        if branch_sets else ():
                    aa = pa_.split(".", 1)[0]
                    ab = pb_.split(".", 1)[0]
                    if aa != ab:
                        edges.append((aa, pa_, ab, pb_, None))

        # eager residual application: a WHERE conjunct whose aliases
        # are all joined (and that contains no subquery) filters the
        # intermediate frame IMMEDIATELY instead of after every join —
        # q72's `inv_quantity_on_hand < cs_quantity` otherwise rides a
        # 50M-row intermediate through four more merges
        applied = set()

        def conj_aliases(conj):
            aliases = set()
            blocked = []

            def chk(x):
                if isinstance(x, Col):
                    try:
                        aliases.add(resolve(x).split(".", 1)[0])
                    except DeltaError:
                        blocked.append(x)
                elif isinstance(x, (InSelect, Exists, ScalarSelect)):
                    blocked.append(x)
            _walk_exprs(conj, chk)
            return None if blocked else aliases

        def apply_eager(frame):
            for conj in conjuncts:
                if id(conj) in consumed or id(conj) in applied:
                    continue
                al = conj_aliases(conj)
                if al is None or not al or not al <= joined:
                    continue
                if al & null_supplying:
                    continue  # outer-join semantics: filter at the end
                m = self._truth(self._eval(conj, frame))
                if not isinstance(m, bool):
                    frame = frame[m]
                elif not m:
                    frame = frame.iloc[0:0]
                applied.add(id(conj))
            return frame

        first_alias = sources[0]["alias"]
        current = by_alias[first_alias]["frame"]
        joined = {first_alias}
        remaining = [a for a in implicit if a != first_alias]
        while remaining:
            # greedy order: most connecting equi-edges first (a 2-key
            # join is far more selective than either key alone — q72's
            # inventory joins on (item_sk, date_sk) once d2 is in),
            # tie-broken by smallest right frame so big fact tables
            # join after the filtering dims. Edges whose aliases a
            # later outer join can null-extend never become join keys
            # (they must stay residual WHERE filters).
            pick = None
            best_score = None
            for a in remaining:
                keys = [(pl, pr) if al in joined else (pr, pl)
                        for (al, pl, ar, pr, c) in edges
                        if ((al in joined and ar == a)
                            or (ar in joined and al == a))
                        and not ({al, ar} & null_supplying)]
                if keys:
                    score = (len(keys), -len(by_alias[a]["frame"]))
                    if best_score is None or score > best_score:
                        best_score = score
                        pick = (a, keys)
            if pick is None:  # no connecting predicate: cross join
                a = remaining[0]
                current = current.merge(by_alias[a]["frame"], how="cross")
                joined.add(a)
                remaining.remove(a)
                continue
            a, keys = pick
            lk = [k for k, _ in keys]
            rk = [k for _, k in keys]
            current = _merge_null_safe(current, by_alias[a]["frame"],
                                       "inner", lk, rk,
                                       spine=self.spine)
            for (al, pl, ar, pr, c) in edges:
                if c is not None and {al, ar} <= joined | {a} \
                        and not ({al, ar} & null_supplying):
                    consumed.add(id(c))
            joined.add(a)
            remaining.remove(a)
            current = apply_eager(current)

        def _on_keys(a, j):
            """ON conjuncts of explicit join `a` as (left, right) key
            pairs; None when a non-`a` side is not joined yet (the
            join cannot run at this point)."""
            lk, rk = [], []
            for conj in _split_and(j.on):
                if not (isinstance(conj, Cmp) and conj.op == "="
                        and isinstance(conj.left, Col)
                        and isinstance(conj.right, Col)):
                    raise UnsupportedSqlError(
                        "JOIN ON supports conjunctions of column = "
                        f"column equalities; got {_render(conj)!r}",
                        error_class="DELTA_UNSUPPORTED_JOIN_CONDITION")
                pl, pr = resolve(conj.left), resolve(conj.right)
                if pl.split(".", 1)[0] == a and pr.split(".", 1)[0] != a:
                    pl, pr = pr, pl
                if pr.split(".", 1)[0] != a:
                    raise UnsupportedSqlError(
                        f"JOIN keys {pl!r}/{pr!r} do not span the "
                        "two sides")
                if pl.split(".", 1)[0] not in joined:
                    return None
                lk.append(pl)
                rk.append(pr)
            return lk, rk

        # inner-join PREFIX commutes: reorder it greedily like the
        # implicit pool (most keys first — WHERE equi-edges count, so
        # q72's inventory waits for d2 and then joins on BOTH
        # (item_sk, date_sk via week) — tie-break smallest frame).
        # Outer/cross joins and everything after them keep clause order.
        explicit = list(zip(join_aliases, sel.joins))
        n_inner = 0
        for a, j in explicit:
            if j.kind != "inner":
                break
            n_inner += 1
        pool = explicit[:n_inner]
        tail = explicit[n_inner:]
        while pool:
            best = None
            best_score = None
            for a, j in pool:
                on = _on_keys(a, j)
                if on is None:
                    continue
                # WHERE edges fold into keys ONLY when no later
                # outer join can null-extend their aliases — filtering
                # before a RIGHT/FULL join would resurrect unmatched
                # rows the residual WHERE must drop
                wk = [(pl, pr) if al in joined else (pr, pl)
                      for (al, pl, ar, pr, c) in edges
                      if ((al in joined and ar == a)
                          or (ar in joined and al == a))
                      and not ({al, ar} & null_supplying)]
                keys = [(l, r) for l, r in zip(on[0], on[1])] + wk
                score = (len(keys), -len(by_alias[a]["frame"]))
                if best_score is None or score > best_score:
                    best_score = score
                    best = (a, j, keys)
            if best is None:
                # every pool member's ON references an alias joined
                # after it — impossible for clause-ordered SQL
                raise UnsupportedSqlError(
                    "JOIN ON ordering is unsatisfiable: every "
                    "remaining join references aliases joined later")
            a, j, keys = best
            lk = [l for l, _ in keys]
            rk = [r for _, r in keys]
            current = _merge_null_safe(current, by_alias[a]["frame"],
                                       "inner", lk, rk,
                                       spine=self.spine)
            for (al, pl, ar, pr, c) in edges:
                if c is not None and {al, ar} <= joined | {a} \
                        and not ({al, ar} & null_supplying):
                    consumed.add(id(c))
            joined.add(a)
            pool = [(pa, pj) for pa, pj in pool if pa != a]
            current = apply_eager(current)

        for a, j in tail:
            right = by_alias[a]["frame"]
            how = {"inner": "inner", "left outer": "left",
                   "right outer": "right", "full outer": "outer",
                   "cross": "cross"}[j.kind]
            if j.kind == "cross":
                current = current.merge(right, how="cross")
                joined.add(a)
                continue
            on = _on_keys(a, j)
            if on is None:
                raise UnsupportedSqlError(
                    f"JOIN ON for {a!r} references aliases joined "
                    "after it")
            lk, rk = on
            current = _merge_null_safe(current, right, how, lk, rk,
                                       spine=self.spine)
            joined.add(a)
            current = apply_eager(current)

        # ---- residual WHERE -------------------------------------------
        residual = [c for c in conjuncts
                    if id(c) not in consumed and id(c) not in applied]
        if residual:
            mask = None
            for conj in residual:
                m = self._truth(self._eval(conj, current))
                mask = m if mask is None else (mask & m)
            if isinstance(mask, bool):  # e.g. a lone EXISTS(...)
                current = current if mask else current.iloc[0:0]
            else:
                current = current[mask]

        return self._project(sel, current, resolve)

    # -- projection / aggregation / order -------------------------------
    def _project(self, sel: Select, df: pd.DataFrame, resolve):
        has_agg = False

        def check_agg(e):
            nonlocal has_agg
            if isinstance(e, Func) and e.name in _AGGS:
                has_agg = True

        for it in sel.items:
            _walk_exprs(it.expr, check_agg)
        _walk_exprs(sel.having, check_agg)
        for o, _ in sel.order_by:
            _walk_exprs(o, check_agg)

        if sel.having is not None and not sel.group_by and not has_agg:
            raise SqlParseError(
                "HAVING without GROUP BY requires an aggregate",
                error_class="DELTA_HAVING_WITHOUT_GROUP_BY")

        alias_map = {it.alias: it.expr for it in sel.items if it.alias}

        if has_agg or sel.group_by:
            df = self._aggregate(sel, df, resolve)
            env = self._agg_env
        else:
            env = {}

        # output column evaluation
        out_cols: List[pd.Series] = []
        out_names: List[str] = []
        for item_idx, it in enumerate(sel.items):
            if isinstance(it.expr, Star):
                if has_agg or sel.group_by:
                    raise SqlParseError("SELECT * cannot combine with "
                                     "GROUP BY/aggregates",
                                     error_class="DELTA_STAR_WITH_AGGREGATE")
                for c in df.columns:
                    out_cols.append(df[c])
                    out_names.append(c.split(".", 1)[1] if "." in c else c)
                continue
            # lateral alias resolution (Spark semantics): an item may
            # reference EARLIER items' aliases (q36's lochierarchy in
            # a later rank() window), but a real source column of the
            # same name always wins over an alias
            expr = it.expr
            lateral = {}
            for prev in sel.items[:item_idx]:
                if not prev.alias:
                    continue
                try:
                    resolve(Col((prev.alias,)))
                    continue  # real column shadows the alias
                except DeltaError:
                    lateral[prev.alias] = prev.expr
            if lateral:
                expr = self._sub_aliases(expr, lateral)
            s = self._eval_out(expr, df, env, resolve)
            if not isinstance(s, pd.Series):  # scalar -> broadcast
                s = pd.Series([s] * len(df), index=df.index)
            out_cols.append(s)
            if it.alias:
                out_names.append(it.alias)
            elif isinstance(it.expr, Col):
                out_names.append(it.expr.parts[-1])
            elif isinstance(it.expr, Func):
                out_names.append(_render(it.expr))
            else:
                out_names.append(it.text or _render(it.expr))

        # HAVING
        if sel.having is not None:
            mask = self._truth(self._eval_out(
                self._sub_aliases(sel.having, alias_map), df, env, resolve))
            if isinstance(mask, bool):  # constant predicate
                mask = pd.Series(mask, index=df.index)
            df = df[mask]
            out_cols = [c[mask] for c in out_cols]

        result = pd.DataFrame(
            {f"__c{i}": c.reset_index(drop=True)
             for i, c in enumerate(out_cols)})
        if sel.distinct:
            result = result.drop_duplicates()

        # ORDER BY
        if sel.order_by:
            sort_series = []
            for e, asc in sel.order_by:
                e = self._sub_aliases(e, alias_map)
                # select-list alias / ordinal / output column reference
                s = None
                if isinstance(e, Lit) and isinstance(e.value, int) \
                        and 1 <= e.value <= len(out_names):
                    s = result[f"__c{e.value - 1}"]  # ORDER BY 2,1,3
                elif isinstance(e, Col) and len(e.parts) == 1:
                    if e.parts[0] in out_names:
                        s = result[f"__c{out_names.index(e.parts[0])}"]
                if s is None:
                    ref = self._eval_out(e, df, env, resolve)
                    if not isinstance(ref, pd.Series):  # constant
                        ref = pd.Series([ref] * len(df), index=df.index)
                    s = ref.reset_index(drop=True)
                sort_series.append((s, asc))
            tmp = result.copy()
            for i, (s, asc) in enumerate(sort_series):
                tmp[f"__s{i}"] = s.values
            scols = [f"__s{i}" for i in range(len(sort_series))]
            sascs = [asc for _s, asc in sort_series]
            with obs.span("sql.sort", rows=len(tmp), keys=len(scols),
                          route="host") as sp:
                sorted_dev = (self.spine.sort_frame(tmp, scols, sascs)
                              if self.spine is not None else None)
                if sorted_dev is not None:
                    sp.set_attr("route", "device")
                tmp = sorted_dev if sorted_dev is not None \
                    else _sql_sort(tmp, scols, sascs)
            result = tmp.drop(columns=[f"__s{i}"
                                       for i in range(len(sort_series))])

        if sel.limit is not None:
            result = result.head(sel.limit)
        result = result.reset_index(drop=True)
        return result, out_names

    def _aggregate(self, sel: Select, df: pd.DataFrame, resolve):
        canon = lambda e: _canon(e, resolve)  # noqa: E731
        key_exprs = list(sel.group_by)
        rollup = None
        if len(key_exprs) == 1 and isinstance(key_exprs[0], Func) \
                and key_exprs[0].name == "rollup":
            # GROUP BY ROLLUP (a, b, c): aggregate at every key prefix
            # level and union, with grouping(k)=1 on rolled-up keys
            rollup = list(key_exprs[0].args)
            key_exprs = rollup
        key_cols = {}
        for e in key_exprs:
            key_cols[canon(e)] = self._eval(e, df)

        agg_specs: Dict[str, Func] = {}

        def collect(e):
            if isinstance(e, Func) and e.name in _AGGS:
                agg_specs.setdefault(canon(e), e)

        for it in sel.items:
            _walk_exprs(it.expr, collect)
        _walk_exprs(sel.having, collect)
        for o, _ in sel.order_by:
            _walk_exprs(o, collect)

        work = pd.DataFrame(index=df.index)
        for k, s in key_cols.items():
            work[k] = s
        for k, f in agg_specs.items():
            if not f.star:
                if len(f.args) != 1:
                    raise SqlParseError(
                        f"{f.name} takes exactly one argument")
                work[f"__arg_{k}"] = self._eval(f.args[0], df)

        def agg_over(names):
            """Aggregate `work` grouped by the given key columns
            (global single row when empty)."""
            with obs.span("sql.groupby", rows=len(work), keys=len(names),
                          route="host") as sp:
                out = grouped(names, sp)
                sp.set_attr("groups", len(out))
                return out

        def grouped(names, sp):
            if names and self.spine is not None:
                dev = self.spine.groupby(work, names, agg_specs)
                if dev is not None:
                    sp.set_attr("route", "device")
                    return dev
            if names:
                gb = work.groupby(names, dropna=False, sort=False)
                out = gb.size().rename("__size").reset_index()
                for k, f in agg_specs.items():
                    if f.star:
                        out[k] = gb.size().values
                        continue
                    col = f"__arg_{k}"
                    if f.distinct and f.name != "count":
                        # sum(DISTINCT x) etc.: dedupe per group first
                        # (silently dropping the flag would return the
                        # plain aggregate — wrong answers)
                        dd = work[names + [col]].drop_duplicates()
                        dgb = dd.groupby(names, dropna=False,
                                         sort=False)[col]
                        agg = {"sum": lambda g: g.sum(min_count=1),
                               "avg": "mean", "min": "min",
                               "max": "max", "stddev_samp": "std",
                               "var_samp": "var"}[f.name]
                        vals = (dgb.agg(agg) if callable(agg)
                                else getattr(dgb, agg)())
                        # align to the gb group order
                        order = gb.size().index
                        out[k] = vals.reindex(order).values
                        continue
                    if f.name == "count" and f.distinct:
                        vals = gb[col].nunique()
                    elif f.name == "count":
                        vals = gb[col].count()
                    elif f.name == "sum":
                        vals = gb[col].sum(min_count=1)
                    elif f.name == "avg":
                        vals = gb[col].mean()
                    elif f.name == "min":
                        vals = gb[col].min()
                    elif f.name == "max":
                        vals = gb[col].max()
                    elif f.name == "stddev_samp":
                        vals = gb[col].std()
                    elif f.name == "var_samp":
                        vals = gb[col].var()
                    out[k] = vals.values
                return out.drop(columns="__size")
            row = {}
            for k, f in agg_specs.items():
                if f.star:
                    row[k] = len(work)
                    continue
                s = work[f"__arg_{k}"]
                if f.distinct and f.name != "count":
                    s = s.drop_duplicates()
                if f.name == "count" and f.distinct:
                    row[k] = s.nunique()
                elif f.name == "count":
                    row[k] = s.count()
                elif f.name == "sum":
                    row[k] = s.sum(min_count=1)
                elif f.name == "avg":
                    row[k] = s.mean()
                elif f.name == "min":
                    row[k] = s.min() if len(s) else None
                elif f.name == "max":
                    row[k] = s.max() if len(s) else None
                elif f.name == "stddev_samp":
                    row[k] = s.std()
                elif f.name == "var_samp":
                    row[k] = s.var()
            return pd.DataFrame([row])

        names = list(key_cols)
        if rollup is not None:
            frames = []
            for level in range(len(names), -1, -1):
                sub = agg_over(names[:level])
                for j, kn in enumerate(names):
                    if j >= level:
                        sub[kn] = None
                    sub[f"grouping({kn})"] = 1 if j >= level else 0
                frames.append(sub)
            out = pd.concat(frames, ignore_index=True)
        elif names:
            out = agg_over(names)
        else:
            out = agg_over([])
        self._agg_env = {k: k for k in out.columns}
        return out

    def _sub_aliases(self, e, alias_map):
        """Recursively replace select-list alias references (HAVING
        total > 5 where total aliases SUM(v))."""
        import dataclasses

        if isinstance(e, Col) and len(e.parts) == 1 \
                and e.parts[0] in alias_map:
            return alias_map[e.parts[0]]
        if isinstance(e, (BinOp, Cmp)):
            return dataclasses.replace(
                e, left=self._sub_aliases(e.left, alias_map),
                right=self._sub_aliases(e.right, alias_map))
        if isinstance(e, (And, Or)):
            return dataclasses.replace(e, items=tuple(
                self._sub_aliases(x, alias_map) for x in e.items))
        if isinstance(e, (Not, Neg, IsNull, Cast, Like)):
            return dataclasses.replace(
                e, item=self._sub_aliases(e.item, alias_map))
        if isinstance(e, Between):
            return dataclasses.replace(
                e, item=self._sub_aliases(e.item, alias_map),
                lo=self._sub_aliases(e.lo, alias_map),
                hi=self._sub_aliases(e.hi, alias_map))
        if isinstance(e, InList):
            return dataclasses.replace(
                e, item=self._sub_aliases(e.item, alias_map),
                values=tuple(self._sub_aliases(v, alias_map)
                             for v in e.values))
        if isinstance(e, Window):
            return dataclasses.replace(
                e,
                func=self._sub_aliases(e.func, alias_map),
                partition_by=tuple(self._sub_aliases(p, alias_map)
                                   for p in e.partition_by),
                order_by=tuple((self._sub_aliases(o, alias_map), asc)
                               for o, asc in e.order_by))
        if isinstance(e, Func):
            return dataclasses.replace(
                e, args=tuple(self._sub_aliases(a, alias_map)
                              for a in e.args))
        if isinstance(e, CaseWhen):
            return dataclasses.replace(
                e,
                whens=tuple((self._sub_aliases(c, alias_map),
                             self._sub_aliases(v, alias_map))
                            for c, v in e.whens),
                else_=self._sub_aliases(e.else_, alias_map)
                if e.else_ is not None else None)
        return e

    def _eval_out(self, e, df, env, resolve):
        """Evaluate in the post-aggregation environment when env is
        non-empty; else plain row environment."""
        if env:
            canon = _canon(e, resolve)
            if canon in env:
                return df[env[canon]]
            if isinstance(e, Col):
                raise SqlParseError(
                    f"column {e.text!r} in SELECT/HAVING/ORDER BY must "
                    "appear in GROUP BY or inside an aggregate")
            if isinstance(e, Lit):
                # raw scalar: every consumer broadcasts, and scalar
                # function args (substr's start/length) must stay ints
                return e.value
            if isinstance(e, BinOp):
                l = self._eval_out(e.left, df, env, resolve)
                r = self._eval_out(e.right, df, env, resolve)
                return _binop(e.op, l, r)
            if isinstance(e, Cmp):
                l = self._eval_out(e.left, df, env, resolve)
                r = self._eval_out(e.right, df, env, resolve)
                return _cmp(e.op, l, r)
            if isinstance(e, And):
                out = None
                for x in e.items:
                    m = _as_kleene(
                        self._eval_out(x, df, env, resolve), df.index)
                    out = m if out is None else (out & m)
                return out
            if isinstance(e, Or):
                out = None
                for x in e.items:
                    m = _as_kleene(
                        self._eval_out(x, df, env, resolve), df.index)
                    out = m if out is None else (out | m)
                return out
            if isinstance(e, Not):
                return ~_as_kleene(
                    self._eval_out(e.item, df, env, resolve), df.index)
            if isinstance(e, CaseWhen):
                conds = [np.asarray(self._truth(
                    self._eval_out(c, df, env, resolve)))
                    for c, _ in e.whens]
                vals = [self._eval_out(v, df, env, resolve)
                        for _, v in e.whens]
                default = self._eval_out(e.else_, df, env, resolve) \
                    if e.else_ is not None else None
                return _case_from_values(conds, vals, default, len(df),
                                         df.index)
            if isinstance(e, Neg):
                return -self._eval_out(e.item, df, env, resolve)
            if isinstance(e, Cast):
                return _cast(self._eval_out(e.item, df, env, resolve),
                             e.type_name)
            if isinstance(e, IsNull):
                s = self._eval_out(e.item, df, env, resolve)
                if isinstance(s, pd.Series):
                    isna = s.isna()
                    return ~isna if e.negated else isna
                isna = bool(pd.isna(s))
                return (not isna) if e.negated else isna
            if isinstance(e, Between):
                v = self._eval_out(e.item, df, env, resolve)
                lo = self._eval_out(e.lo, df, env, resolve)
                hi = self._eval_out(e.hi, df, env, resolve)
                m = _as_kleene(_cmp(">=", v, lo), df.index) \
                    & _as_kleene(_cmp("<=", v, hi), df.index)
                return ~m if e.negated else m
            if isinstance(e, InList):
                v = self._eval_out(e.item, df, env, resolve)
                vals = [self._eval_out(x, df, env, resolve)
                        for x in e.values]
                has_null = any(not isinstance(x, pd.Series)
                               and pd.isna(x) for x in vals)
                vals = [x for x in vals
                        if isinstance(x, pd.Series) or not pd.isna(x)]
                m = _in_membership(v, vals, has_null, df.index)
                return ~m if e.negated else m
            if isinstance(e, ScalarSelect):
                if self._correlation(e.select):
                    raise UnsupportedSqlError(
                        "correlated scalar subquery over an aggregated "
                        "result is not supported")
                out = execute_select(e.select, self.engine,
                                     self.catalog, ctes=self.ctes)
                if out.num_columns != 1 or out.num_rows > 1:
                    raise SubqueryShapeError(
                        "scalar subquery must return one value")
                return (None if out.num_rows == 0
                        else out.column(0)[0].as_py())
            if isinstance(e, Window):
                return self._window_eval(
                    e, df, lambda x: self._eval_out(x, df, env, resolve))
            if isinstance(e, Func) and e.name not in _AGGS:
                # scalar function over aggregated values (abs, round…)
                return self._apply_func(
                    e, [self._eval_out(a, df, env, resolve)
                        for a in e.args], df)
            if isinstance(e, Func) and e.name in _AGGS:
                # canon miss should not happen (collected above)
                raise UnsupportedSqlError(f"aggregate {e.name} not computed")
            raise UnsupportedSqlError(
                f"unsupported expression over aggregated result: "
                f"{_render(e)}")
        return self._eval(e, df)

    # -- row-environment evaluation -------------------------------------
    def _eval(self, e, df: pd.DataFrame):
        if isinstance(e, Lit):
            return e.value
        if isinstance(e, Col):
            return df[self._resolve(e)]
        if isinstance(e, Neg):
            return -self._eval(e.item, df)
        if isinstance(e, BinOp):
            return _binop(e.op, self._eval(e.left, df),
                          self._eval(e.right, df))
        if isinstance(e, Cmp):
            return _cmp(e.op, self._eval(e.left, df),
                        self._eval(e.right, df))
        if isinstance(e, And):
            out = None
            for x in e.items:
                m = _as_kleene(self._eval(x, df), df.index)
                out = m if out is None else (out & m)
            return out
        if isinstance(e, Or):
            out = None
            for x in e.items:
                m = _as_kleene(self._eval(x, df), df.index)
                out = m if out is None else (out | m)
            return out
        if isinstance(e, Not):
            return ~_as_kleene(self._eval(e.item, df), df.index)
        if isinstance(e, IsNull):
            s = self._eval(e.item, df)
            if isinstance(s, pd.Series):
                isna = s.isna()
                return ~isna if e.negated else isna
            isna = bool(pd.isna(s))
            return (not isna) if e.negated else isna
        if isinstance(e, Between):
            v = self._eval(e.item, df)
            lo = self._eval(e.lo, df)
            hi = self._eval(e.hi, df)
            m = _as_kleene(_cmp(">=", v, lo), df.index) \
                & _as_kleene(_cmp("<=", v, hi), df.index)
            return ~m if e.negated else m
        if isinstance(e, InList):
            v = self._eval(e.item, df)
            vals = [self._eval(x, df) for x in e.values]
            has_null_val = any(not isinstance(x, pd.Series) and pd.isna(x)
                               for x in vals)
            vals = [x for x in vals
                    if isinstance(x, pd.Series) or not pd.isna(x)]
            m = _in_membership(v, vals, has_null_val, df.index)
            return ~m if e.negated else m
        if isinstance(e, Like):
            import re as _re

            s = self._eval(e.item, df)
            pat = "^" + "".join(
                ".*" if ch == "%" else "." if ch == "_" else _re.escape(ch)
                for ch in e.pattern) + "$"
            m = _as_kleene(s.str.match(pat, na=False), df.index)
            m = m.mask(s.isna(), pd.NA)
            return ~m if e.negated else m
        if isinstance(e, CaseWhen):
            conds = [np.asarray(self._truth(self._eval(c, df)))
                     for c, _ in e.whens]
            vals = [self._eval(v, df) for _, v in e.whens]
            default = self._eval(e.else_, df) if e.else_ is not None \
                else None
            return _case_from_values(conds, vals, default, len(df),
                                     df.index)
        if isinstance(e, Window):
            return self._window_eval(e, df,
                                     lambda x: self._eval(x, df))
        if isinstance(e, Cast):
            v = self._eval(e.item, df)
            return _cast(v, e.type_name)
        if isinstance(e, Interval):
            return pd.Timedelta(days=e.n)
        if isinstance(e, ScalarSelect):
            corr = self._correlation(e.select)
            if corr:
                return self._correlated_scalar(e.select, corr, df)
            out = execute_select(e.select, self.engine, self.catalog,
                                 ctes=self.ctes)
            if out.num_columns != 1:
                raise SqlParseError("scalar subquery must return one column")
            if out.num_rows == 0:
                return None
            if out.num_rows > 1:
                raise SubqueryShapeError("scalar subquery returned >1 row")
            return out.column(0)[0].as_py()
        if isinstance(e, InSelect):
            corr = self._correlation(e.select)
            if corr:
                m = self._correlated_semi(e.select, corr, df,
                                          item=e.item)
                return ~m if e.negated else m
            out = execute_select(e.select, self.engine, self.catalog,
                                 ctes=self.ctes)
            if out.num_columns != 1:
                raise SqlParseError("IN subquery must return one column")
            raw = out.column(0).to_pylist()
            has_null = any(x is None for x in raw)
            vals = set(x for x in raw if x is not None)
            v = self._eval(e.item, df)
            m = _in_membership(v, vals, has_null, df.index)
            return ~m if e.negated else m
        if isinstance(e, Exists):
            corr = self._correlation(e.select)
            if corr:
                m = self._correlated_semi(e.select, corr, df)
                return ~m if e.negated else m
            out = execute_select(e.select, self.engine, self.catalog,
                                 ctes=self.ctes)
            flag = out.num_rows > 0
            if e.negated:
                flag = not flag
            return flag
        if isinstance(e, Func):
            if e.name in _AGGS:
                raise SqlParseError(
                    f"aggregate {e.name}(...) is not allowed here",
                    error_class="DELTA_AGGREGATION_NOT_SUPPORTED")
            return self._scalar_func(e, df)
        if isinstance(e, Star):
            raise SqlParseError("* is only allowed as a lone select item")
        raise UnsupportedSqlError(f"unsupported expression {type(e).__name__}")

    # -- correlated subqueries (equality decorrelation) -----------------

    @staticmethod
    def _inner_aliases(sub: Select) -> set:
        out = set()
        for ref in list(sub.froms) + [j.ref for j in sub.joins]:
            if ref.alias:
                out.add(ref.alias.lower())
            elif ref.kind == "name":
                out.add(ref.value.split(".")[-1].lower())
        return out

    def _inner_columns(self, sub: Select) -> set:
        """Best-effort lowercase column inventory of the subquery's own
        sources (schema probe; snapshots are metadata-cached)."""
        out = set()
        for ref in list(sub.froms) + [j.ref for j in sub.joins]:
            try:
                if ref.kind == "subquery":
                    sel = (ref.value.selects[0]
                           if isinstance(ref.value, Query) else ref.value)
                    for it in sel.items:
                        if it.alias:
                            out.add(it.alias.lower())
                        elif isinstance(it.expr, Col):
                            out.add(it.expr.parts[-1].lower())
                elif ref.kind == "name" and ref.value.lower() in self.ctes:
                    out |= {c.lower()
                            for c in self.ctes[ref.value.lower()].columns}
                else:
                    snap = self._snapshot(ref)
                    if snap.schema is not None:
                        out |= {f.name.lower()
                                for f in snap.schema.fields}
            except (DeltaError, OSError):
                pass  # unknown source: treat its columns as unknown
        return out

    def _correlation(self, sub: Select):
        """Detect equality correlation: WHERE conjuncts of the form
        `outer.col = inner_col`, with the outer side either qualified
        by an outer alias (q1/q30/q81) or an unqualified name that
        belongs only to the outer scope (q32/q92's bare `i_item_sk`).
        Also factors equalities repeated across every OR branch (q41)
        and collects non-equality outer references (q94's `<>`) as
        residual conjuncts for the post-join EXISTS path. Returns a
        _CorrInfo, or None when uncorrelated; raises only when outer
        references exist with no equality to decorrelate on."""
        inner = self._inner_aliases(sub)
        outer = {a.lower() for a in getattr(self, "_outer_aliases", ())}
        inner_cols = None  # lazily probed

        def is_outer(c) -> bool:
            nonlocal inner_cols
            if not isinstance(c, Col):
                return False
            if len(c.parts) >= 2:
                return (c.parts[-2].lower() not in inner
                        and c.parts[-2].lower() in outer)
            # unqualified: outer only if the name is NOT an inner
            # column but IS resolvable in the outer scope
            if inner_cols is None:
                inner_cols = self._inner_columns(sub)
            if c.parts[0].lower() in inner_cols:
                return False
            try:
                self._resolve(c)
                return True
            except DeltaError:
                return False

        def outer_eq(conj):
            if (isinstance(conj, Cmp) and conj.op == "="
                    and isinstance(conj.left, Col)
                    and isinstance(conj.right, Col)):
                lo, ro = is_outer(conj.left), is_outer(conj.right)
                if lo != ro:
                    return ((conj.left, conj.right) if lo
                            else (conj.right, conj.left))
            return None

        corr = []       # [(outer Col, inner Col)]
        residual = []   # outer-referencing, non-equality (q94's <>)
        where_rest = []  # inner-only conjuncts (possibly rewritten)
        for conj in _split_and(sub.where):
            eq = outer_eq(conj)
            if eq:
                corr.append(eq)
                continue
            # q41's shape: OR whose EVERY branch repeats the same
            # outer-equality conjunct — factor it out and rebuild the
            # OR without it (frozen-dataclass equality makes the
            # identical-conjunct check exact)
            if isinstance(conj, Or):
                branch_splits = [_split_and(b) for b in conj.items]
                common = next(
                    (cand for cand in branch_splits[0]
                     if outer_eq(cand)
                     and all(cand in bs for bs in branch_splits)),
                    None)
                if common is not None:
                    # the rebuilt branches must be inner-only; any
                    # OTHER outer reference inside them makes this a
                    # residual conjunct, not a factorable one
                    leftover = []
                    for bs in branch_splits:
                        for c in bs:
                            if c == common:
                                continue
                            _walk_exprs(c, lambda x: leftover.append(x)
                                        if is_outer(x) else None)
                    if leftover:
                        residual.append(conj)
                        continue
                    corr.append(outer_eq(common))
                    branches = []
                    trivially_true = False
                    for bs in branch_splits:
                        rest = tuple(c for c in bs if c != common)
                        if not rest:
                            # a branch that was ONLY the equality: the
                            # whole OR holds wherever the correlation
                            # key matches — nothing left to filter
                            trivially_true = True
                            break
                        branches.append(rest[0] if len(rest) == 1
                                        else And(rest))
                    if not trivially_true:
                        where_rest.append(Or(tuple(branches)))
                    continue
            has_outer = []

            def chk(x):
                if is_outer(x):
                    has_outer.append(x)
            _walk_exprs(conj, chk)
            (residual if has_outer else where_rest).append(conj)
        if not corr:
            if residual:
                raise UnsupportedSqlError(
                    "correlated subquery has outer references but no "
                    "equality correlation to decorrelate on",
                    error_class="DELTA_UNSUPPORTED_CORRELATED_SUBQUERY")
            return None
        return _CorrInfo(corr, where_rest, residual, is_outer)

    def _decorrelated_frame(self, sub: Select, info, extra_items,
                            aggregate: bool):
        """Run `sub` with the correlation conjuncts removed (using the
        rewritten inner-only WHERE) and the inner correlation columns
        added as group keys (aggregate=True) or distinct output
        columns. Returns (df, corr_key_names)."""
        if sub.group_by or sub.having:
            raise UnsupportedSqlError(
                "correlated subquery with its own GROUP BY/HAVING is "
                "not supported",
                error_class="DELTA_UNSUPPORTED_CORRELATED_SUBQUERY")
        keep = list(info.where_rest)
        where = None
        if keep:
            where = keep[0] if len(keep) == 1 else And(tuple(keep))
        key_items = [SelectItem(i, alias=f"__ck{k}")
                     for k, (_o, i) in enumerate(info.corr)]
        inner_sel = Select(
            items=key_items + extra_items,
            froms=list(sub.froms), joins=list(sub.joins), where=where,
            group_by=[i for _o, i in info.corr] if aggregate else [],
            distinct=not aggregate,
        )
        sub_df, names = _Exec(self.engine, self.catalog,
                              self.ctes).run(inner_sel)
        sub_df = sub_df.copy()
        sub_df.columns = names
        return sub_df, [f"__ck{k}" for k in range(len(info.corr))]

    def _outer_key_frame(self, info, df):
        work = pd.DataFrame(index=pd.RangeIndex(len(df)))
        for k, (o, _i) in enumerate(info.corr):
            s = self._eval(o, df)
            work[f"__ck{k}"] = s.values if isinstance(s, pd.Series) \
                else s
        return work

    def _correlated_scalar(self, sub: Select, info, df):
        if info.residual:
            raise UnsupportedSqlError(
                "correlated scalar subquery with non-equality outer "
                "references is not supported",
                error_class="DELTA_UNSUPPORTED_CORRELATED_SUBQUERY")
        if len(sub.items) != 1 or isinstance(sub.items[0].expr, Star):
            raise SqlParseError("scalar subquery must return one column")
        val_item = SelectItem(sub.items[0].expr, alias="__cv")
        if not _has_agg(val_item.expr):
            raise UnsupportedSqlError(
                "correlated scalar subquery must aggregate (else it "
                "may return >1 row per outer row)")
        sub_df, keys = self._decorrelated_frame(sub, info, [val_item],
                                                aggregate=True)
        # missing group == subquery over ZERO rows: count()-family
        # aggregates yield 0 there, everything else NULL (the q41
        # `count(*) = 0` shape must see 0, not NULL)
        default = self._empty_agg_value(val_item.expr)
        # NULL keys never participate: `k = NULL` is UNKNOWN on both
        # sides (Python dicts would happily match None == None)
        lut = {}
        for r in sub_df[keys + ["__cv"]].itertuples(index=False):
            t = tuple(r)
            if not any(pd.isna(v) for v in t[:-1]):
                lut[t[:-1]] = t[-1]
        outer = self._outer_key_frame(info, df)
        out_vals = [None if any(pd.isna(v) for v in r)
                    else lut.get(tuple(r), default)
                    for r in outer[keys].itertuples(index=False)]
        return pd.Series(out_vals, index=df.index)

    def _empty_agg_value(self, expr):
        """Value of an aggregate expression over an empty input:
        count → 0, other aggregates → NULL, constants fold through;
        anything unresolvable defaults to NULL."""
        def sub(e):
            import dataclasses
            if isinstance(e, Func) and e.name in _AGGS:
                return Lit(0) if e.name == "count" else Lit(None)
            if isinstance(e, (BinOp, Cmp)):
                return dataclasses.replace(e, left=sub(e.left),
                                           right=sub(e.right))
            if isinstance(e, (Neg, Cast)):
                return dataclasses.replace(e, item=sub(e.item))
            return e
        try:
            empty = pd.DataFrame(index=pd.RangeIndex(0))
            v = self._eval(sub(expr), empty)
            if isinstance(v, pd.Series):
                return None
            return None if (v is not None and not isinstance(v, str)
                            and pd.isna(v)) else v
        # delta-lint: disable=except-swallow (audited: constant-folding
        # an arbitrary expression over an empty frame — any eval error
        # just means "not foldable", the real evaluator decides later)
        except Exception:
            return None

    def _correlated_semi(self, sub: Select, info, df, item=None):
        """EXISTS (semi-join) / IN membership against a correlated
        subquery; returns a kleene boolean mask over df. Residual
        non-equality outer references (q94) are applied as post-join
        filters on the EXISTS path."""
        if info.residual:
            if item is not None:
                raise UnsupportedSqlError(
                    "correlated IN with non-equality outer references "
                    "is not supported",
                    error_class="DELTA_UNSUPPORTED_CORRELATED_SUBQUERY")
            return self._correlated_exists_residual(sub, info, df)
        extra = []
        if item is not None:
            if len(sub.items) != 1 or isinstance(sub.items[0].expr,
                                                 Star):
                raise SqlParseError("IN subquery must return one column")
            extra = [SelectItem(sub.items[0].expr, alias="__cv")]
        sub_df, keys = self._decorrelated_frame(sub, info, extra,
                                                aggregate=False)
        cols = keys + (["__cv"] if item is not None else [])
        # three-valued membership: a NULL inner correlation key never
        # matches equality; a NULL inner VALUE makes non-matches in
        # that group UNKNOWN (the NOT IN footgun, per correlation group)
        match_keys = set()
        groups_seen = set()
        group_has_null = set()
        for r in sub_df[cols].itertuples(index=False):
            t = tuple(r)
            kt = t[:len(keys)]
            if any(pd.isna(v) for v in kt):
                continue
            if item is None:
                match_keys.add(kt)
                continue
            groups_seen.add(kt)
            if pd.isna(t[-1]):
                group_has_null.add(kt)
            else:
                match_keys.add(t)
        outer = self._outer_key_frame(info, df)
        if item is not None:
            s = self._eval(item, df)
            outer["__cv"] = s.values if isinstance(s, pd.Series) else s
        vals = []
        for r in outer.itertuples(index=False):
            t = tuple(r)
            kt = t[:len(keys)]
            if any(pd.isna(v) for v in kt):
                # NULL outer key: equality is UNKNOWN for every inner
                # row, so the subquery is empty — EXISTS/IN → FALSE
                vals.append(False)
            elif item is None:
                vals.append(kt in match_keys)
            elif kt not in groups_seen:
                vals.append(False)  # IN against an empty set
            elif pd.isna(t[-1]):
                vals.append(pd.NA)  # NULL item vs non-empty set
            elif t in match_keys:
                vals.append(True)
            elif kt in group_has_null:
                vals.append(pd.NA)
            else:
                vals.append(False)
        return pd.Series(vals, index=df.index, dtype="boolean")

    def _correlated_exists_residual(self, sub: Select, info, df):
        """EXISTS with equality correlation PLUS outer-referencing
        residual conjuncts: join outer keys+residual operands to the
        decorrelated inner rows on the equality keys, apply the
        residuals on the joined rows, reduce per outer row."""
        inner_cols, outer_cols = [], []
        for rc in info.residual:
            def reg(c):
                if not isinstance(c, Col):
                    return
                if info.is_outer(c):
                    if c not in outer_cols:
                        outer_cols.append(c)
                elif c not in inner_cols:
                    inner_cols.append(c)
            _walk_exprs(rc, reg)
        extra = [SelectItem(c, alias=f"__rin_{j}")
                 for j, c in enumerate(inner_cols)]
        sub_df, keys = self._decorrelated_frame(sub, info, extra,
                                                aggregate=False)
        outer = self._outer_key_frame(info, df)
        for j, c in enumerate(outer_cols):
            v = self._eval(c, df)
            outer[f"__out_{j}"] = v.values if isinstance(v, pd.Series) \
                else v
        outer["__rowid"] = np.arange(len(outer))
        merged = _merge_null_safe(outer, sub_df, "inner", keys, keys)
        # rewrite residuals over the merged frame's flat column names
        def sub_col(c):
            if info.is_outer(c):
                return Col((f"__out_{outer_cols.index(c)}",))
            return Col((f"__rin_{inner_cols.index(c)}",))
        mask = pd.Series(True, index=merged.index)
        old_resolve = self._resolve
        self._resolve = lambda col: col.parts[-1]
        try:
            for rc in info.residual:
                m = self._truth(self._eval(_rewrite_cols(rc, sub_col),
                                           merged))
                if isinstance(m, bool):
                    m = pd.Series(m, index=merged.index)
                mask &= m
        finally:
            self._resolve = old_resolve
        hit = set(merged.loc[mask, "__rowid"].tolist())
        flags = np.fromiter((i in hit for i in range(len(df))),
                            count=len(df), dtype=bool)
        return _as_kleene(pd.Series(flags, index=df.index), df.index)

    def _scalar_func(self, e: Func, df):
        return self._apply_func(e, [self._eval(a, df) for a in e.args],
                                df)

    def _window_eval(self, e: Window, df, ev):
        """Evaluate a window function over `df`; `ev` evaluates
        sub-expressions in the caller's environment (row or post-agg).
        sum/avg/min/max/count transform within partitions; rank and
        row_number additionally use the ORDER BY clause."""
        name = e.func.name
        if e.func.distinct:
            raise UnsupportedSqlError(
                f"DISTINCT inside window function {name} is not "
                "supported", error_class="DELTA_UNSUPPORTED_DISTINCT_IN_WINDOW")
        parts = [ev(p) for p in e.partition_by]
        parts = [p if isinstance(p, pd.Series)
                 else pd.Series([p] * len(df), index=df.index)
                 for p in parts]
        if name in ("sum", "avg", "min", "max", "count"):
            if e.func.star:
                s = pd.Series(1, index=df.index)
                fn = "sum"
            else:
                s = ev(e.func.args[0])
                if not isinstance(s, pd.Series):
                    s = pd.Series([s] * len(df), index=df.index)
                fn = {"avg": "mean"}.get(name, name)
            if e.order_by:
                # SQL default frame with ORDER BY: RANGE UNBOUNDED
                # PRECEDING..CURRENT ROW — a running aggregate where
                # order-key peers share the value at their last row
                if self.spine is not None:
                    r = self.spine.window_running(
                        parts, self._order_items(e, df, ev), s, fn,
                        "rows" if e.frame == "rows" else "range",
                        df.index)
                    if r is not None:
                        return r
                return self._running_window(e, df, ev, s, fn, parts)
            if not parts:
                # whole-frame window
                if fn == "count":
                    val = s.count()
                else:
                    val = getattr(s, fn)()
                return pd.Series([val] * len(df), index=df.index)
            if self.spine is not None:
                r = self.spine.partition_transform(parts, s, fn)
                if r is not None:
                    return r
            grouped = s.groupby([p.values for p in parts], dropna=False)
            # min_count=1: SUM over an all-NULL partition is NULL (SQL
            # semantics, and what the device path returns) — pandas'
            # default transform("sum") would say 0.0
            kw = {"min_count": 1} if fn == "sum" else {}
            return pd.Series(grouped.transform(fn, **kw).values,
                             index=df.index)
        if name in ("rank", "row_number", "dense_rank"):
            if not e.order_by:
                raise SqlParseError(f"{name}() requires ORDER BY",
                                    error_class="DELTA_WINDOW_REQUIRES_ORDER")
            if self.spine is not None:
                r = self.spine.window_rank(
                    parts, self._order_items(e, df, ev), name,
                    len(df), df.index)
                if r is not None:
                    return r
            work = pd.DataFrame(index=pd.RangeIndex(len(df)))
            pcols, ocols, ascs = [], [], []
            for i, p in enumerate(parts):
                work[f"__p{i}"] = p.values
                pcols.append(f"__p{i}")
            for i, (o, asc) in enumerate(e.order_by):
                s = ev(o)
                work[f"__o{i}"] = s.values if isinstance(s, pd.Series) \
                    else s
                ocols.append(f"__o{i}")
                ascs.append(asc)
            order = _sql_sort(work, ocols, ascs)
            if pcols:
                pos = order.groupby(pcols, dropna=False,
                                    sort=False).cumcount() + 1
            else:
                pos = pd.Series(np.arange(1, len(order) + 1),
                                index=order.index)
            if name == "row_number":
                ranks = pos
            elif name == "rank":
                # min position among equal order keys
                order2 = order.assign(__pos=pos)
                ranks = order2.groupby(pcols + ocols, dropna=False,
                                       sort=False)["__pos"] \
                    .transform("min")
            else:  # dense_rank: count of distinct keys before + 1
                order2 = order
                key_first = order2.groupby(
                    pcols + ocols, dropna=False,
                    sort=False).cumcount() == 0
                dr = key_first.groupby(
                    [order2[c] for c in pcols] if pcols else
                    np.zeros(len(order2), np.int8),
                    dropna=False).cumsum()
                ranks = dr.groupby(
                    [order2[c] for c in (pcols + ocols)],
                    dropna=False).transform("max")
            out = ranks.sort_index()
            return pd.Series(out.values, index=df.index)
        raise UnsupportedSqlError(f"unsupported window function {name!r}",
                                  error_class="DELTA_UNSUPPORTED_WINDOW_FUNCTION")

    @staticmethod
    def _order_items(e: Window, df, ev):
        """Evaluate a window's ORDER BY into [(Series, asc)] for the
        device path; scalar exprs broadcast."""
        items = []
        for o, asc in e.order_by:
            s = ev(o)
            if not isinstance(s, pd.Series):
                s = pd.Series([s] * len(df), index=df.index)
            items.append((s, asc))
        return items

    @staticmethod
    def _running_window(e: Window, df, ev, s, fn, parts):
        work = pd.DataFrame(index=pd.RangeIndex(len(df)))
        pcols, ocols, ascs = [], [], []
        for i, p in enumerate(parts):
            work[f"__p{i}"] = p.values
            pcols.append(f"__p{i}")
        for i, (o, asc) in enumerate(e.order_by):
            ov = ev(o)
            work[f"__o{i}"] = ov.values if isinstance(ov, pd.Series) \
                else ov
            ocols.append(f"__o{i}")
            ascs.append(asc)
        work["__v"] = s.values
        order = _sql_sort(work, ocols, ascs)
        expand = {"sum": lambda x: x.expanding().sum(),
                  "mean": lambda x: x.expanding().mean(),
                  "min": lambda x: x.expanding().min(),
                  "max": lambda x: x.expanding().max(),
                  "count": lambda x: x.expanding().count()}[fn]
        if pcols:
            cum = order.groupby(pcols, dropna=False, sort=False)[
                "__v"].transform(expand)
        else:
            cum = expand(order["__v"])
        order = order.assign(__cum=cum.values)
        if e.frame == "rows":
            # strict running frame: no peer sharing
            return pd.Series(order["__cum"].sort_index().values,
                             index=df.index)
        # RANGE frame (SQL default): peers (equal order keys) share
        # the value at the last peer row
        peers = order.groupby(pcols + ocols, dropna=False,
                              sort=False)["__cum"].transform("last")
        return pd.Series(peers.sort_index().values, index=df.index)

    def _apply_func(self, e: Func, args, df):
        name = e.name
        if e.star:
            raise SqlParseError(
                f"* argument is only allowed in count(*), not "
                f"{name}(*)")
        if name in ("substr", "substring"):
            s, start, length = args[0], int(args[1]), int(args[2]) \
                if len(args) > 2 else None
            if not isinstance(s, pd.Series):
                s = pd.Series([s] * len(df), index=df.index)
            s = s.astype("string")
            if length is None:
                return s.str.slice(start - 1)
            return s.str.slice(start - 1, start - 1 + length)
        if name == "upper":
            return args[0].str.upper()
        if name == "lower":
            return args[0].str.lower()
        if name == "length":
            return args[0].str.len()
        if name == "abs":
            return args[0].abs() if isinstance(args[0], pd.Series) \
                else abs(args[0])
        if name == "round":
            # Spark/SQL ROUND is HALF_UP; pandas/python round is
            # half-even (2.125 → 2.12 there, 2.13 in SQL)
            nd = int(args[1]) if len(args) > 1 else 0
            scale = 10 ** nd
            v = args[0]
            if isinstance(v, pd.Series):
                return np.sign(v) * np.floor(np.abs(v) * scale + 0.5) \
                    / scale
            if pd.isna(v):
                return None
            return float(np.sign(v) * np.floor(abs(v) * scale + 0.5)
                         / scale)
        if name == "coalesce":
            out = args[0]
            for nxt in args[1:]:
                if isinstance(out, pd.Series):
                    out = out.fillna(nxt) if not isinstance(nxt, pd.Series)\
                        else out.combine_first(nxt)
                elif out is None:
                    out = nxt
            return out
        if name == "concat":
            out = None
            for a in args:
                a = a.astype("string") if isinstance(a, pd.Series) \
                    else str(a)
                out = a if out is None else out + a
            return out
        if name == "year":
            return args[0].dt.year
        if name == "month":
            return args[0].dt.month
        raise UnsupportedSqlError(f"unsupported function {name!r}",
                                  error_class="DELTA_UNSUPPORTED_FUNCTION")

    @staticmethod
    def _truth(m):
        """Collapse SQL three-valued logic at a filter boundary:
        NULL → False. Predicates propagate NULL through the tree
        (Kleene, see _as_kleene); only WHERE/HAVING/CASE boundaries
        collapse."""
        if isinstance(m, pd.Series):
            if m.dtype == object or str(m.dtype) == "boolean" \
                    or m.dtype.kind == "f":
                return m.fillna(False).astype(bool)
            return m
        if m is pd.NA or m is None or (isinstance(m, float)
                                       and np.isnan(m)):
            return False
        return bool(m)

    # -- pushdown helpers ------------------------------------------------
    def _sole_alias(self, conj, resolve) -> Optional[str]:
        aliases = set()
        bad = False

        def note(e):
            nonlocal bad
            if isinstance(e, Col):
                try:
                    aliases.add(resolve(e).split(".", 1)[0])
                except DeltaError:
                    bad = True
            elif isinstance(e, (InSelect, Exists, ScalarSelect)):
                bad = True

        _walk_exprs(conj, note)
        if bad or len(aliases) != 1:
            return None
        return next(iter(aliases))

    def _to_tree(self, conj, resolve, alias):
        """Best-effort conversion to the persisted-expression tree for
        scan pushdown (file pruning). Unsupported shapes return None —
        the residual evaluation still applies the full predicate."""
        from delta_tpu.expressions import col as t_col, lit as t_lit
        from delta_tpu.expressions.tree import Expression

        def conv(e):
            if isinstance(e, Cmp):
                l, r = e.left, e.right
                flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=",
                        "=": "=", "<>": "<>"}
                if isinstance(r, Col) and isinstance(l, Lit):
                    l, r = r, l
                    op = flip[e.op]
                else:
                    op = e.op
                if not (isinstance(l, Col) and isinstance(r, Lit)):
                    return None
                if not isinstance(r.value, (int, float, str, bool)):
                    return None
                c = t_col(l.parts[-1])
                v = t_lit(r.value)
                return {"=": c == v, "<>": c != v, "<": c < v,
                        "<=": c <= v, ">": c > v, ">=": c >= v}[op]
            if isinstance(e, Between) and not e.negated:
                lo = conv(Cmp(">=", e.item, e.lo))
                hi = conv(Cmp("<=", e.item, e.hi))
                return lo & hi if lo is not None and hi is not None \
                    else None
            if isinstance(e, InList) and not e.negated:
                # emit one tree `In` (not an OR-chain of equalities):
                # skipping compiles it to a single vectorizable
                # conjunct with a range prefilter and a large-list
                # fast path (stats/skipping.py, stats/device_index.py)
                if isinstance(e.item, Col) and e.values and all(
                    isinstance(v, Lit)
                    and isinstance(v.value, (int, float, str, bool))
                    for v in e.values
                ):
                    return t_col(e.item.parts[-1]).is_in(
                        *[v.value for v in e.values])
                out = None
                for v in e.values:
                    c = conv(Cmp("=", e.item, v))
                    if c is None:
                        return None
                    out = c if out is None else (out | c)
                return out
            if isinstance(e, And):
                out = None
                for x in e.items:
                    c = conv(x)
                    if c is None:
                        return None
                    out = c if out is None else (out & c)
                return out
            if isinstance(e, Or):
                out = None
                for x in e.items:
                    c = conv(x)
                    if c is None:
                        return None
                    out = c if out is None else (out | c)
                return out
            return None

        return conv(conj)


class _CorrInfo:
    """Decorrelation analysis of a correlated subquery: equality
    correlation pairs, the inner-only WHERE remainder (with q41-style
    OR-factored equalities removed), and residual outer-referencing
    conjuncts (q94's `ws1.x <> ws2.x`) applied post-join."""

    def __init__(self, corr, where_rest, residual, is_outer):
        self.corr = corr
        self.where_rest = where_rest
        self.residual = residual
        self.is_outer = is_outer

    def __bool__(self):
        return bool(self.corr)


def _rewrite_cols(e, fn):
    """Structurally rebuild `e` with every Col node replaced by
    fn(col)."""
    import dataclasses

    if isinstance(e, Col):
        return fn(e)
    if isinstance(e, (BinOp, Cmp)):
        return dataclasses.replace(
            e, left=_rewrite_cols(e.left, fn),
            right=_rewrite_cols(e.right, fn))
    if isinstance(e, (And, Or)):
        return dataclasses.replace(
            e, items=tuple(_rewrite_cols(x, fn) for x in e.items))
    if isinstance(e, (Not, Neg, IsNull, Like, Cast)):
        return dataclasses.replace(e, item=_rewrite_cols(e.item, fn))
    if isinstance(e, Between):
        return dataclasses.replace(
            e, item=_rewrite_cols(e.item, fn),
            lo=_rewrite_cols(e.lo, fn), hi=_rewrite_cols(e.hi, fn))
    if isinstance(e, InList):
        return dataclasses.replace(
            e, item=_rewrite_cols(e.item, fn),
            values=tuple(_rewrite_cols(v, fn) for v in e.values))
    if isinstance(e, Func):
        return dataclasses.replace(
            e, args=tuple(_rewrite_cols(a, fn) for a in e.args))
    if isinstance(e, CaseWhen):
        return dataclasses.replace(
            e,
            whens=tuple((_rewrite_cols(c, fn), _rewrite_cols(v, fn))
                        for c, v in e.whens),
            else_=_rewrite_cols(e.else_, fn)
            if e.else_ is not None else None)
    if isinstance(e, Window):
        return dataclasses.replace(
            e, func=_rewrite_cols(e.func, fn),
            partition_by=tuple(_rewrite_cols(p, fn)
                               for p in e.partition_by),
            order_by=tuple((_rewrite_cols(o, fn), asc)
                           for o, asc in e.order_by))
    contains_col = []
    _walk_exprs(e, lambda x: contains_col.append(x)
                if isinstance(x, Col) else None)
    if contains_col:
        from delta_tpu.errors import UnsupportedSqlError

        raise UnsupportedSqlError(
            f"unsupported expression {type(e).__name__} in a "
            "correlated residual predicate")
    return e


def _sql_sort(frame: pd.DataFrame, cols, ascs) -> pd.DataFrame:
    """Multi-key stable sort with Spark null ordering per key: NULLS
    FIRST when ascending, LAST when descending (reverse stable passes,
    since pandas only takes one na_position per call)."""
    for i in range(len(cols) - 1, -1, -1):
        frame = frame.sort_values(
            cols[i], ascending=ascs[i], kind="mergesort",
            na_position="first" if ascs[i] else "last")
    return frame


def _case_from_values(conds, vals, default, n, index):
    """np.select over pre-evaluated CASE WHEN branches."""
    vals = [v.values if isinstance(v, pd.Series)
            else np.full(n, v, dtype=object if isinstance(v, str)
                         else None) for v in vals]
    if isinstance(default, pd.Series):
        default = default.values
    elif default is None:
        default = np.full(n, np.nan)
    else:
        default = np.full(
            n, default,
            dtype=object if isinstance(default, str) else None)
    out = np.select(conds, vals, default)
    return pd.Series(out, index=index)


def _as_kleene(x, index):
    """Normalize a predicate value to pandas nullable-boolean so &, |
    and ~ follow SQL three-valued (Kleene) logic; scalars broadcast.
    Nulls stay NULL through the tree and collapse to False only at
    filter boundaries (_truth)."""
    if isinstance(x, pd.Series):
        if str(x.dtype) == "boolean":
            return x
        return x.astype("boolean")
    if x is None or x is pd.NA or (isinstance(x, float) and np.isnan(x)):
        return pd.Series(pd.NA, index=index, dtype="boolean")
    return pd.Series(bool(x), index=index, dtype="boolean")


def _in_membership(v, vals, has_null, index):
    """SQL IN membership with three-valued semantics: NULL item → NULL;
    a NULL among the candidates means a non-match is NULL (nothing is
    provably absent from a set containing NULL) — the NOT IN footgun."""
    if isinstance(v, pd.Series):
        m = v.isin(vals).astype("boolean")
        m = m.mask(v.isna(), pd.NA)
        if has_null:
            m = m.mask(~m.fillna(False).astype(bool), pd.NA)
    elif pd.isna(v):
        m = pd.NA
    else:
        m = (v in vals) or (pd.NA if has_null else False)
    return _as_kleene(m, index)


def _with_nulls(res, *operands):
    """Comparison result → nullable boolean with NULL wherever any
    operand is NULL (numpy comparisons silently yield False for NaN ==
    and True for NaN !=, both wrong under SQL semantics)."""
    if isinstance(res, pd.Series):
        out = res.astype("boolean")
        mask = None
        for o in operands:
            if isinstance(o, pd.Series):
                n = o.isna()
                n.index = out.index
            elif pd.isna(o):
                n = pd.Series(True, index=out.index)
            else:
                continue
            mask = n if mask is None else (mask | n)
        if mask is not None and mask.any():
            out = out.mask(mask.astype(bool), pd.NA)
        return out
    for o in operands:
        if not isinstance(o, pd.Series) and pd.isna(o):
            return pd.NA
    return res


def _binop(op, l, r):
    # NULL arithmetic: a scalar NULL operand (e.g. an empty scalar
    # subquery) nulls the whole expression
    for o in (l, r):
        if not isinstance(o, pd.Series) and o is not None \
                and not isinstance(o, str) and pd.isna(o):
            return None
    if l is None or r is None:
        return None
    if op == "+":
        return l + r
    if op == "-":
        return l - r
    if op == "*":
        return l * r
    if op == "/":
        return l / r
    if op == "||":
        ls = l.astype("string") if isinstance(l, pd.Series) else str(l)
        rs = r.astype("string") if isinstance(r, pd.Series) else str(r)
        return ls + rs
    raise UnsupportedSqlError(f"unsupported operator {op!r}",
                              error_class="DELTA_UNSUPPORTED_SQL_OPERATOR")


def _coerce_datetime(l, r):
    """Make string literals comparable to datetime64 columns."""
    def is_dt(x):
        return (isinstance(x, pd.Series)
                and str(x.dtype).startswith("datetime64")) \
            or isinstance(x, (pd.Timestamp, datetime.date))

    if is_dt(l) and isinstance(r, str):
        r = pd.Timestamp(r)
    elif is_dt(r) and isinstance(l, str):
        l = pd.Timestamp(l)
    return l, r


def _cmp(op, l, r):
    l, r = _coerce_datetime(l, r)
    if op == "=":
        res = l == r
    elif op == "<>":
        res = l != r
    elif op == "<":
        res = l < r
    elif op == "<=":
        res = l <= r
    elif op == ">":
        res = l > r
    elif op == ">=":
        res = l >= r
    else:
        raise UnsupportedSqlError(f"unsupported comparison {op!r}")
    return _with_nulls(res, l, r)


def _cast(v, type_name):
    if type_name == "date":
        if isinstance(v, pd.Series):
            return pd.to_datetime(v)
        return pd.Timestamp(v)
    if type_name in ("int", "integer", "bigint", "long", "smallint"):
        if isinstance(v, pd.Series):
            return v.astype("Int64")
        return int(v)
    if type_name in ("double", "float", "real"):
        return v.astype(float) if isinstance(v, pd.Series) else float(v)
    if type_name in ("string", "varchar", "char", "text"):
        return v.astype("string") if isinstance(v, pd.Series) else str(v)
    if type_name.startswith("decimal"):
        return v.astype(float) if isinstance(v, pd.Series) else float(v)
    raise UnsupportedSqlError(f"unsupported CAST target {type_name!r}",
                              error_class="DELTA_UNSUPPORTED_CAST_TARGET")
