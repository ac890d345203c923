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

import decimal
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.json as pa_json

from delta_tpu import obs
from delta_tpu.utils.chunks import pieces
from delta_tpu.expressions.tree import (
    And,
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
_UNCOMPARED = obs.counter("scan.skip_uncompared_conjuncts")
_TABLE_BUILDS = obs.counter("scan.stats_index_table_builds")

_ARROW_ERRS = (pa.ArrowInvalid, pa.ArrowNotImplementedError,
               pa.ArrowTypeError)

# What a stat leaf is read as where the table's schema calls it one of
# these: a Delta `timestamp` is an instant (UTC microseconds whatever
# offset the writer spelled it with), a `timestamp_ntz` a wall clock.
_TIMESTAMP_LEAVES = {"timestamp": pa.timestamp("us", tz="UTC"),
                     "timestamp_ntz": pa.timestamp("us")}
# Writers truncate a timestamp stat to the millisecond (PROTOCOL.md,
# "Per-file Statistics"), so a stored max stands for any instant within
# its millisecond: it is read as stored + 1 ms, here and nowhere else
# (upstream `DataSkippingReader` does the same), so the lanes, their
# numpy twin and the Arrow ladder all see the widened value.
_TIMESTAMP_MAX_SLACK_US = 1000

# A `decimal(p,s)` stat is read from its own digits (an explicit
# `decimal128(p,s)` in the JSON reader's schema: inference reads
# `19876.54` as a double, and at p = 18 no double holds the value),
# while its unscaled value fits the index's int64 lanes. Wider ones
# parse as doubles, off the lanes, as before.
DECIMAL_LANE_PRECISION = 18
_DECIMAL_DIGITS = 38            # decimal128's


def decimal_lane_type(delta_type: Optional[str]) -> Optional[pa.DataType]:
    """`decimal128(p, s)` of a leaf the schema calls `decimal(p,s)` with
    p <= `DECIMAL_LANE_PRECISION` and 0 <= s <= p; None of any other."""
    if not delta_type or not delta_type.startswith("decimal"):
        return None
    from delta_tpu.models.schema import PrimitiveType

    p, s = PrimitiveType(delta_type).decimal_precision_scale()
    if p > DECIMAL_LANE_PRECISION or not 0 <= s <= p:
        return None
    return pa.decimal128(p, s)


def decimal_literal(value, floats: bool = False) -> Optional[decimal.Decimal]:
    """A predicate literal as the exact decimal it states: an `int`, a
    finite `decimal.Decimal`, or digits in text; with `floats` (the
    ladder, which compares at any scale) a `float` too, as the binary
    fraction it is (`700.5` is 700.5, `0.1` is 0.1000000000000000055...).
    None of a `bool` and anything else."""
    if isinstance(value, bool):
        return None
    if floats and isinstance(value, (float, np.floating)):
        value = float(value)
        return decimal.Decimal(value) if np.isfinite(value) else None
    if isinstance(value, (int, np.integer)):
        return decimal.Decimal(int(value))
    if isinstance(value, str):
        try:
            value = decimal.Decimal(value)
        except decimal.InvalidOperation:
            return None
    if isinstance(value, decimal.Decimal) and value.is_finite():
        return value
    return None


def _decimal_reader_schema(leaf_types: Dict[tuple, str],
                           wide: bool) -> Optional[pa.Schema]:
    """The part of the stats' schema that is given to the JSON reader
    and not inferred: the `minValues` / `maxValues` leaves that are
    decimal lanes, as `decimal128(p,s)`; with `wide`, with every place
    decimal128 has behind the point, so that a stat with more places
    than its column's scale still reads (and is told apart afterwards,
    `_typed_leaf`). None where the table has no such column."""
    tree: dict = {}
    for path, delta_type in leaf_types.items():
        t = decimal_lane_type(delta_type)
        if t is None:
            continue
        if wide:
            t = pa.decimal128(_DECIMAL_DIGITS,
                              _DECIMAL_DIGITS - (t.precision - t.scale))
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = t
    if not tree:
        return None

    def struct(node):
        return pa.struct([(k, struct(v) if isinstance(v, dict) else v)
                          for k, v in node.items()])

    group = struct(tree)
    return pa.schema([("minValues", group), ("maxValues", group)])


def stat_leaf_types(metadata) -> Dict[tuple, str]:
    """{leaf path as the stats JSON keys it: Delta primitive type} of
    the table's schema (a decimal by its whole name, `decimal(7,2)`);
    physical names under column mapping. Arrays and maps carry no
    stats."""
    from delta_tpu.models.schema import PrimitiveType, StructType

    mapped = metadata.configuration.get(
        "delta.columnMapping.mode", "none") != "none"

    def walk(struct, prefix, out):
        for f in struct.fields:
            path = prefix + (f.physical_name if mapped else f.name,)
            if isinstance(f.dataType, StructType):
                walk(f.dataType, path, out)
            elif isinstance(f.dataType, PrimitiveType):
                out[path] = f.dataType.name
        return out

    return walk(metadata.schema, (), {})


def _typed_leaf(leaf: pa.Array, delta_type: Optional[str], widen: bool,
                cast: bool) -> pa.Array:
    exact = decimal_lane_type(delta_type)
    if exact is not None:
        if cast and pa.types.is_decimal(leaf.type) and leaf.type != exact:
            # read with every place decimal128 has: a stat with more
            # places than the column's scale is unknown in its slot,
            # never rounded into it
            try:
                rounded = pc.round(leaf, exact.scale)
                leaf = pc.if_else(pc.equal(rounded, leaf), rounded,
                                  pa.scalar(None, leaf.type)).cast(exact)
            except _ARROW_ERRS:
                pass    # stays as parsed: off the lanes, counted
        return leaf
    target = _TIMESTAMP_LEAVES.get(delta_type)
    if target is None:
        return leaf
    if leaf.type != target:
        if not cast:
            return leaf
        try:    # ISO-8601 with `Z` or an offset; zone-less for an ntz
            leaf = leaf.cast(target)
        except _ARROW_ERRS:
            return leaf     # stays as parsed: off the lanes, counted
    if widen:
        us = pc.min_element_wise(
            leaf.cast(pa.int64()),
            pa.scalar(np.iinfo(np.int64).max - _TIMESTAMP_MAX_SLACK_US))
        leaf = pc.add(us, _TIMESTAMP_MAX_SLACK_US).cast(target)
    return leaf


def _typed_struct(arr: pa.StructArray, prefix: tuple, leaf_types, widen,
                  cast) -> pa.StructArray:
    children, fields, changed = [], [], False
    for i, f in enumerate(arr.type):
        child, path = arr.field(i), prefix + (f.name,)
        if pa.types.is_struct(f.type):
            new = _typed_struct(child, path, leaf_types, widen, cast)
        else:
            new = _typed_leaf(child, leaf_types.get(path), widen, cast)
        changed |= new is not child
        children.append(new)
        fields.append(f if new is child else pa.field(f.name, new.type))
    if not changed:
        return arr
    return pa.StructArray.from_arrays(
        children, fields=fields,
        mask=arr.is_null() if arr.null_count else None)


def _typed_stats(parsed: pa.Table, leaf_types: Dict[tuple, str],
                 cast: bool, wide_decimals: bool = False) -> pa.Table:
    """`parsed` with the `minValues` / `maxValues` leaves that the
    table's schema calls `timestamp` or `timestamp_ntz` read as such
    (JSON inference takes a time with a fraction or a zone for a
    string), and each such max widened by its writer's millisecond.
    Without `cast` only leaves already of that type are widened (rows
    parsed under a typed schema). With `wide_decimals` (the rows were
    read under `_decimal_reader_schema`'s wide form) the decimal leaves
    are brought to their column's type. A table with no such column is
    returned as it came."""
    if not (wide_decimals or any(t in _TIMESTAMP_LEAVES
                                 for t in leaf_types.values())):
        return parsed
    for group in ("minValues", "maxValues"):
        i = parsed.schema.get_field_index(group)
        if i < 0 or not pa.types.is_struct(parsed.schema.field(i).type):
            continue
        col = parsed.column(i).combine_chunks()
        typed = _typed_struct(col, (), leaf_types, group == "maxValues",
                              cast)
        if typed is not col:
            parsed = parsed.set_column(i, group, typed)
    return parsed


# Rows of `ParsedPieces` that are no longer a version's stay where they
# are until they pass this share of the rows held (1 / N, the rule a
# crossing sends a state to the full load by): then the pieces are
# combined without them, which costs what every refresh paid before the
# table was deferred, once in (rows held / N) dropped rows.
_DEAD_ROWS_SHARE = 8


@dataclass(frozen=True)
class ParsedPieces:
    """The parsed stats rows of an index brought forward from the one
    before, as they were handed on and not yet as one table: `table`,
    whose chunks are the pieces (the seed's, then one parsed tail an
    append, none of them copied), and `dead`, the numbers of its rows,
    ascending, that are no version's any more. Read, never written to:
    the index before and the seed between hold the same buffers."""

    table: pa.Table
    dead: np.ndarray

    @property
    def schema(self) -> pa.Schema:
        return self.table.schema

    def advanced(self, dropped: np.ndarray,
                 tail: pa.Table) -> Union["ParsedPieces", pa.Table]:
        """The rows of the next version: these less `dropped` (numbers
        among the rows still a version's, ascending), `tail`'s behind
        them. O(dropped + dead) look-ups and no copy of a row, until
        one of two fixed rules bounds what is carried: the small pieces
        at the end are merged as the held rows' chunks are
        (`replay/state.py::_merge_small_chunks`), and past
        `_DEAD_ROWS_SHARE` the rows are combined here and now (a
        table)."""
        from delta_tpu.replay.state import _merge_small_chunks

        dead = self.dead
        if len(dropped):
            # dead row i has dead[i] - i live rows before it, so that
            # many of the live rows come before it and the rest after
            before = np.searchsorted(dead - np.arange(len(dead)), dropped,
                                     side="right")
            at = dropped + before
            dead = np.insert(dead, np.searchsorted(dead, at), at)
        table = pa.concat_tables([self.table, tail]) if tail.num_rows \
            else self.table
        if len(dead) * _DEAD_ROWS_SHARE > table.num_rows:
            return ParsedPieces(table, dead).combined()
        return ParsedPieces(_merge_small_chunks(table)[0], dead)

    def combined(self) -> pa.Table:
        """One table of the rows that are this version's, in order."""
        table = self.table
        with obs.span("index.compact_table",
                      _verbose=table.num_rows < obs.PHASE_SPAN_ROWS,
                      rows=table.num_rows, columns=table.num_columns,
                      deferred_pieces=table.column(0).num_chunks) as ph:
            if len(self.dead):
                keep = np.ones(table.num_rows, bool)
                keep[self.dead] = False
                table = table.filter(pa.array(keep))
            table = table.combine_chunks()
            if ph.recording:
                ph.set_attr("bytes", table.nbytes)
        _TABLE_BUILDS.inc()
        return table


# The stats strings of a held state are parsed a piece of the column at
# a time: an Arrow `string` column's offsets are 32-bit a chunk, so a
# column past 2 GiB (a fact table's width: ~1.6 KB of stats a file,
# 2.4M files) can be neither combined nor joined into one buffer. A
# column under one piece is parsed in the one call it always was.
_PARSE_PIECE_BYTES = 1 << 28


def _parse_piece(arr: pa.Array, options, null_tokens: bool) -> pa.Table:
    """One `pyarrow.json.read_json` over the rows of `arr`, a line a
    row (raises `pa.ArrowInvalid` as the reader does)."""
    # substitute "{}" for null rows to keep row alignment
    filled = pc.fill_null(arr, "{}")
    # pretty-printed stats embed raw newlines, which would desync the
    # one-row-per-line framing below (parsed.num_rows != n -> ALL
    # skipping silently disabled). Raw newlines are illegal inside a
    # JSON string value (they must be escaped as \n), so every literal
    # newline in a stats row is structural whitespace — flatten it.
    filled = pc.replace_substring(filled, pattern="\r", replacement=" ")
    filled = pc.replace_substring(filled, pattern="\n", replacement=" ")
    if null_tokens:
        for tok in ('"NaN"', '"Infinity"', '"-Infinity"'):
            filled = pc.replace_substring_regex(
                filled, pattern=r":\s*" + tok, replacement=":null")
    # each row with a newline behind it: the values of the result lie
    # end to end in its data buffer, which is the file to read (no
    # Python list of every row)
    lines = pc.binary_join_element_wise(filled, "", "\n")
    offsets = np.frombuffer(lines.buffers()[1], np.int32,
                            count=len(lines) + 1, offset=4 * lines.offset)
    joined = lines.buffers()[2].slice(int(offsets[0]),
                                      int(offsets[-1] - offsets[0]))
    return pa_json.read_json(pa.BufferReader(joined), parse_options=options)


def _kept_pieces(stats_col, rows: Optional[np.ndarray]):
    """`pieces` of the column, each narrowed to its rows of the mask."""
    at = 0
    for arr in pieces(stats_col, _PARSE_PIECE_BYTES):
        if rows is not None:
            mine = rows[at:at + len(arr)]
            at += len(arr)
            if not mine.all():
                arr = arr.filter(pa.array(mine))
        if len(arr):
            yield arr


def _parse_pieces(stats_col, options, null_tokens: bool,
                  rows: Optional[np.ndarray] = None) -> Optional[pa.Table]:
    """The rows of `stats_col` (those of the mask `rows`) parsed piece
    by piece and the parsed tables one behind the other (their chunks,
    nothing copied); None where the reader refuses a piece. Where the pieces were inferred
    to different schemas (a leaf all null in one, an int column's
    first float in another), every piece is read again under the
    schema that takes them all, which is what inference over every row
    at once comes to."""
    try:
        tables = [_parse_piece(arr, options, null_tokens)
                  for arr in _kept_pieces(stats_col, rows)]
        if not tables:
            return None
        if len(tables) == 1 or all(t.schema == tables[0].schema
                                   for t in tables[1:]):
            return pa.concat_tables(tables)
        unified = pa.unify_schemas([t.schema for t in tables],
                                   promote_options="permissive")
        under = pa_json.ParseOptions(explicit_schema=unified,
                                     unexpected_field_behavior="error")
        return pa.concat_tables(
            [_parse_piece(arr, under, null_tokens)
             for arr in _kept_pieces(stats_col, rows)])
    except _ARROW_ERRS:
        return None


class StatsIndex:
    """Parsed stats for a batch of files: one Arrow table, a row a
    file. An index brought forward from the one before
    (`stats/device_index.py::append_index`) is given the rows as
    `ParsedPieces` and makes the table of them when a reader first asks
    for a leaf, once; one that no reader asks never does."""

    def __init__(self, rows: Union[pa.Table, ParsedPieces, None], n: int):
        self._lock = threading.Lock()
        self._rows = rows
        self.n = n

    @property
    def schema(self) -> Optional[pa.Schema]:
        """The parsed table's schema, None where no stats parsed;
        combines nothing."""
        rows = self._rows
        return None if rows is None else rows.schema

    @property
    def _table(self) -> Optional[pa.Table]:
        rows = self._rows
        if isinstance(rows, ParsedPieces):
            with self._lock:
                rows = self._rows
                if isinstance(rows, ParsedPieces):
                    rows = self._rows = rows.combined()
        return rows

    def carried(self) -> Optional[ParsedPieces]:
        """The rows as the next version's index takes them on: the
        pieces while no reader has asked for the table, the table as
        one piece once one has."""
        rows = self._rows
        if rows is None or isinstance(rows, ParsedPieces):
            return rows
        return ParsedPieces(rows, np.zeros(0, np.int64))

    @staticmethod
    def from_stats_column(
            stats_col: pa.ChunkedArray, schema: Optional[pa.Schema] = None,
            leaf_types: Optional[Dict[tuple, str]] = None,
            rows: Optional[np.ndarray] = None) -> "StatsIndex":
        """Parse one stats string a row, a piece of the column at a
        time (`_parse_pieces`); with `rows` (a mask over the column:
        the live rows of the rows a state holds) only those, each piece
        narrowed as it is parsed, so that no copy of every live row's
        string is made beside the column. With `schema` (the parsed schema of
        rows these will stand behind, `stats/device_index.py`) nothing
        is inferred and nothing rewritten: a value that does not read
        as the schema's type, or a key the schema lacks, gives an index
        with no table. With `leaf_types` (`stat_leaf_types` of the
        table's schema) the leaves it names are typed by it: a decimal
        lane's by the reader itself, from the stat's digits
        (`_decimal_reader_schema`), a time's afterwards
        (`_typed_stats`); a leaf it lacks, and every leaf where it is
        not given, keeps the type JSON inference gave it."""
        n = len(stats_col) if rows is None else int(np.count_nonzero(rows))
        if n == 0 or stats_col.null_count == len(stats_col):
            return StatsIndex(None, n)
        if schema is not None:
            attempts = [(pa_json.ParseOptions(
                explicit_schema=schema, unexpected_field_behavior="error"),
                False, False)]
        else:
            # the decimal leaves exactly; then with room for a stat of
            # more places than its scale; then as JSON inference reads
            # them, doubles (off the lanes, as a column past p = 18)
            attempts = []
            for wide in (False, True):
                typed = _decimal_reader_schema(leaf_types or {}, wide)
                if typed is not None:
                    attempts.append((pa_json.ParseOptions(
                        explicit_schema=typed,
                        unexpected_field_behavior="infer"), False, wide))
            # A non-finite float stat serializes as the string "NaN" /
            # "Infinity" / "-Infinity" (see collection.py); ONE such
            # file makes Arrow's JSON inference see a string/number mix
            # and refuse the column — which used to disable skipping
            # for the whole table. Nulling those tokens loses only
            # precision (a null stat means unknown -> keep), never
            # correctness: a raw `:"NaN"` byte sequence cannot occur
            # inside a JSON string value (its quote would be escaped),
            # so only whole stat values can match.
            attempts += [(None, False, False), (None, True, False)]
        for options, null_tokens, wide in attempts:
            parsed = _parse_pieces(stats_col, options, null_tokens, rows)
            if parsed is not None:
                break
        else:
            return StatsIndex(None, n)
        if parsed.num_rows != n:
            return StatsIndex(None, n)
        if leaf_types:
            parsed = _typed_stats(parsed, leaf_types, cast=schema is None,
                                  wide_decimals=wide)
        return StatsIndex(parsed, n)

    def _leaf(self, group: str, name_path: tuple) -> Optional[np.ndarray]:
        """Return (values, valid) for e.g. group='minValues', col path.
        None when the column isn't in the index, which the schema says:
        only a leaf that is there has the table made for it."""
        schema = self.schema
        if schema is None or group not in schema.names:
            return None
        t = schema.field(group).type
        if not pa.types.is_struct(t):
            return None
        for part in name_path:
            if not pa.types.is_struct(t) or t.get_field_index(part) < 0:
                return None
            t = t.field(part).type
        # the leaf of each chunk, then one array of it: combining the
        # group first copies every leaf of it for each one asked for
        arr = self._table.column(group)
        for part in name_path:
            arr = pc.struct_field(arr, part)
        return arr.combine_chunks()

    def num_records(self):
        schema = self.schema
        if schema is None or "numRecords" not in schema.names:
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


def _cmp_keep(op: str, minv, maxv, lit_arr,
              uncompared: list) -> Optional[pa.Array]:
    """Keep-condition (nullable bool Arrow array) for `col op lit` given
    min/max arrays; None = cannot decide (keep). Where the stats are
    there and Arrow cannot compare them with the literal (a zone-less
    `datetime` against a `timestamp` leaf, text against a number), the
    file is kept and `uncompared` is told.

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
    except _ARROW_ERRS:
        if any(a is not None and not pa.types.is_null(a.type)
               for a in (minv, maxv)):
            uncompared.append(op)
        return None
    return None


def _conjunct_keep(conj: Expression, index: StatsIndex,
                   uncompared: list) -> Optional[pa.Array]:
    """Nullable keep-mask for one conjunct; None/null = keep. Each
    comparison that a column's stats were there for and Arrow refused
    leaves an entry in `uncompared`."""
    if isinstance(conj, Or):
        left = _conjunct_keep(conj.left, index, uncompared)
        right = _conjunct_keep(conj.right, index, uncompared)
        if left is None or right is None:
            return None
        return pc.or_kleene(left, right)
    if isinstance(conj, And):
        # under an OR (`split_conjuncts` takes the ones above it): a
        # file is pruned by either side, and a side with no answer
        # stands for "keep", as upstream's `DataSkippingReader`
        left = _conjunct_keep(conj.left, index, uncompared)
        right = _conjunct_keep(conj.right, index, uncompared)
        if left is None or right is None:
            return right if left is None else left
        return pc.and_kleene(left, right)
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
        value = lit.value
        if any(a is not None and pa.types.is_decimal(a.type)
               for a in (minv, maxv)):
            # exactly or not at all: Arrow would compare a `float`
            # through doubles, so it is given the fraction the float is
            value = decimal_literal(value, floats=True)
            if value is None:
                uncompared.append(op)
                minv = maxv = None
        try:
            lit_arr = pa.scalar(value)
        except pa.ArrowInvalid:
            return None
        keep = _cmp_keep(op, minv, maxv, lit_arr, uncompared)
        # additionally: an all-null column can't match col op lit
        nc = index.null_count(colref.name_path)
        nr = index.num_records()
        if nc is not None and nr is not None:
            try:
                not_all_null = pc.less(nc, nr)
                keep = not_all_null if keep is None else pc.and_kleene(keep, not_all_null)
            except _ARROW_ERRS:
                uncompared.append("nullCount")
        return keep
    if isinstance(conj, IsNull):
        child = conj.child
        if isinstance(child, Column):
            nc = index.null_count(child.name_path)
            if nc is None:
                return None
            try:
                return pc.greater(nc, pa.scalar(0))
            except _ARROW_ERRS:
                uncompared.append("nullCount")
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
            except _ARROW_ERRS:
                uncompared.append("nullCount")
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
                    Comparison(">=", conj.child, Literal(lo)), index,
                    uncompared)
                k_hi = _conjunct_keep(
                    Comparison("<=", conj.child, Literal(hi)), index,
                    uncompared)
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
                k = _conjunct_keep(Comparison("=", conj.child, Literal(v)),
                                   index, uncompared)
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
                Comparison(neg[inner.op], inner.left, inner.right), index,
                uncompared)
        if isinstance(inner, IsNull):
            return _conjunct_keep(IsNotNull(inner.child), index, uncompared)
        if isinstance(inner, IsNotNull):
            return _conjunct_keep(IsNull(inner.child), index, uncompared)
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
    files: Optional[pa.Table],
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
    mask, so the result is route-independent by construction.

    `files` None is a scan over the `SnapshotState`'s own live rows
    (`scan.py`): the mask is over them, in the order held, and the
    index reads what stats strings it needs out of the rows held."""
    n = files.num_rows if files is not None else len(state.live_rows)
    keep = np.ones(n, dtype=bool)
    if n == 0 or not conjuncts:
        return keep
    rs = None
    if state is not None:
        from delta_tpu.stats.device_index import snapshot_stats_index

        rs = snapshot_stats_index(state, files, metadata)
    index = rs.arrow_index if rs is not None \
        else StatsIndex.from_stats_column(
            files.column("stats"),
            leaf_types=None if metadata is None
            else stat_leaf_types(metadata))
    if index.schema is None:
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
                from delta_tpu.resilience import device_faults

                def device_mask():
                    # fetched inside the thunk: a shed may evict the
                    # resident lanes, and the retry then re-uploads them
                    lanes = rs.device_lanes()
                    if lanes is None:
                        return None
                    return ops_skipping.skip_mask_block(*lanes, block, n)

                out = device_faults.guarded("skip", device_mask,
                                            _DEVICE_FALLBACKS)
                if out.value is not None:
                    keep &= out.value
                    _DEVICE_PLANS.inc()
                    if fallback:
                        _DEVICE_FALLBACKS.inc(len(fallback))
                else:
                    if out.fell_back is None:
                        obs.gate_fell_back("skip", "host",
                                           reason="no-resident-lanes")
                    route = "host"
            if route == "host":
                with obs.gate_observation("skip", "host"):
                    keep &= ops_skipping.host_skip_mask(
                        vals, valid, block, n)
            obs.set_attrs(skip_route=route, skip_atoms=block.n_atoms,
                          skip_fallback_conjuncts=len(fallback),
                          atoms=block.n_atoms, groups=block.n_groups,
                          decimal_atoms=block.decimal_atoms,
                          distributed=block.distributed)
    uncompared = 0
    for conj in fallback:
        refused: list = []
        mask = _conjunct_keep(conj, index, refused)
        uncompared += bool(refused)
        if mask is None:
            continue
        # null (missing stats for that file) -> keep
        filled = pc.fill_null(mask, True)
        keep &= np.asarray(filled, dtype=bool)
    if uncompared:
        _UNCOMPARED.inc(uncompared)
    obs.set_attrs(uncompared=uncompared)
    return keep
