"""Resident device stats index for scan planning.

Once per snapshot version, the parsed file-stats table
(`stats/skipping.py::StatsIndex`) is columnarized into a dense int64
lane matrix covering every *skipping-eligible* column — numeric,
timestamp, date, and bool leaves whose min/max stats parsed to a
comparable type — and cached on `SnapshotState` next to the
PR 7 resident replay state (`parallel/resident.py`):

  row 3c   : minValues  of eligible column c
  row 3c+1 : maxValues  of eligible column c
  row 3c+2 : nullCount  of eligible column c
  row -1   : numRecords

plus a validity bitplane (missing/unparseable stat -> invalid ->
"unknown" -> keep, preserving the host path's Kleene semantics). All
lanes are int64 in an order-preserving encoding (see `_enc_f64` for
the float total order; timestamps/dates become epoch microseconds), so
`ops/skipping.py` can evaluate a whole conjunct list against every
file in one type-agnostic dispatch on either backend, bit-identically.

Lifecycle mirrors `parallel/resident.py` discipline: built at most
once per `SnapshotState` under the state's dedicated
`_stats_index_lock` (NOT `_splice_lock` — building reads
`file_actions`, which takes the splice lock itself), and released on
serve-cache eviction through `release_snapshot_resident`. The device
upload is lazy (first device-routed scan) and budgeted in
`resources/transfer_budget.json` (`stats-index-lanes`): the lanes ship
ONCE per version and stay HBM-resident across scans, so the per-scan
device cost is one RTT plus the compiled atom arrays.

An index lives as long as its version, and the next version's is made
from it. `replay/state.py::advance_state` hands the index itself to the
new state across an EMPTY delta. One landed file action releases it
(host lanes dropped, device copy and ledger entry freed at once) and
leaves a `StatsIndexSeed` on the new state: references to the released
index's lanes, kinds and parsed rows, and the live mask they were
built over. Live bits of prior rows are only ever cleared and new rows
only ever land behind them, so the first filtered scan of the new
version (`snapshot_stats_index`, span `stats.index_build`, `mode`
`append`) keeps the seed's rows that are still live, parses the stats
of the rows landed since (read out of the rows the state holds, past
the seed's) under the seed's schema, and writes them behind: the cost
is that of the rows that changed, and the result is what `build_index`
over every live file gives. The lanes are what a refresh brings
forward. The parsed Arrow table, which only the fallback ladder reads,
is derived: an append hands its rows on as they came
(`stats/skipping.py::ParsedPieces`: the pieces, and which of their rows
have gone), and `StatsIndex` makes the one table of them when a reader
first asks for a leaf, under its lock, once. Where that cannot be
shown from the seed (`_APPEND_FALLBACKS`, the span's
`append_fallback`), and on a state with no seed, every live file's
stats string is parsed (`mode` `full`). The seed's arrays are never
written to: a reader may still plan on the prior version. Either way
the lanes cross to the device whole, on the first device plan
(`stats.index_upload`): nothing on the chip is patched in place.

On the host the lanes are int64 throughout (the seed, `append_index`,
the numpy twin). On the chip, which has no 64-bit integers, they are
resident in the form the kernel computes on: high halves `int32`, low
halves `uint32`, validity `bool`, each `[R, n_pad / 128, 128]` so that
a lane row is whole tiles. The int64 rows cross the link as they are
and are split there, once an upload (`_halves_fn`,
`scan.stats_index_lane_splits`), a few rows at a time so that the chip
never holds the index twice.
"""

from __future__ import annotations

import collections
import datetime
import decimal
import functools
import threading
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu import obs
from delta_tpu.obs import hbm
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
from delta_tpu.ops.skipping import AtomBlock
from delta_tpu.stats.skipping import (
    DECIMAL_LANE_PRECISION,
    ParsedPieces,
    decimal_literal,
)

_BUILDS = obs.counter("scan.stats_index_builds")
_APPENDS = obs.counter("scan.stats_index_appends")
_APPEND_FALLBACKS = obs.counter("scan.stats_index_append_fallbacks")
_REUSES = obs.counter("scan.stats_index_reuses")
# an OR over ANDs compiled to the kernel's AND of OR-groups, and one
# whose product of sides passed `IN_LIST_ATOM_LIMIT` atoms (the ladder's)
_DISTRIBUTED = obs.counter("scan.skip_disjunctions_distributed")
_TOO_WIDE = obs.counter("scan.skip_disjunctions_too_wide")
# an append that handed the parsed rows on as pieces; the table made of
# them for a reader counts in `scan.stats_index_table_builds`
# (`stats/skipping.py::ParsedPieces.combined`)
_TABLE_DEFERRED = obs.counter("scan.stats_index_table_deferred")
# the int64 lanes split into 32-bit halves on the chip: once an upload
# (a refresh, or the re-upload after an eviction), never at a launch
_LANE_SPLITS = obs.counter("scan.stats_index_lane_splits")
# leaves that carry min/max stats and got no lane, by why: an index
# that cannot read a table's schema shows here, not in a scan's bill
_UNINDEXED = {
    "string": obs.counter("scan.stats_index_unindexed_leaves.string"),
    "decimal": obs.counter("scan.stats_index_unindexed_leaves.decimal"),
    "unparsed": obs.counter("scan.stats_index_unindexed_leaves.unparsed"),
    "other": obs.counter("scan.stats_index_unindexed_leaves.other"),
}
# device bytes are accounted in the resident ledger (obs/hbm.py),
# which derives the `scan.stats_index_hbm_bytes` gauge this module
# used to maintain by hand

_OP_CODES = {"<": 0, "<=": 1, ">": 2, ">=": 3, "=": 4, "!=": 5}
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}
_NEG = {"=": "!=", "!=": "=", "<": ">=", "<=": ">", ">": "<=", ">=": "<"}
_OP_ISNULL = 6
_OP_ISNOTNULL = 7

# an int cast to float64 is exact only within +/-2^53; literals outside
# that window fall back to the Arrow route rather than compare inexactly
_F64_EXACT_INT = 1 << 53

# In-lists longer than this compile to a pure range prefilter (two
# atoms) instead of one '=' atom per value
IN_LIST_ATOM_LIMIT = 64

_ARROW_ERRS = (pa.ArrowInvalid, pa.ArrowNotImplementedError,
               pa.ArrowTypeError)

# the kinds of lane (`_lane_kind`), in the order `lane_kinds` names them
_LANE_KINDS = ("bool", "int", "float", "ts", "tstz", "decimal")

# the files of one tile row of the chip's vectors (128 lanes): the lanes
# are padded to a multiple of it (`_lanes_of`), and a lane row of the
# resident index is `[n_pad / TILE_FILES, TILE_FILES]`
TILE_FILES = 128

# lane rows that cross to the chip, and are there as int64, at a time
_UPLOAD_ROWS = 4


def _enc_f64(a: np.ndarray) -> np.ndarray:
    """Order-preserving float64 -> int64 total-order encoding (sign-
    magnitude IEEE bits flipped into two's complement); -0.0 is
    canonicalized to +0.0 first so both compare equal to 0."""
    a = np.asarray(a, np.float64) + 0.0
    u = a.view(np.int64)
    return np.where(u >= 0, u, np.int64(np.iinfo(np.int64).min) ^ ~u)


def _lane_kind(t: pa.DataType) -> Optional[str]:
    """Encoding kind for a parsed stat leaf type; None = ineligible.
    `tstz` is an instant (a Delta `timestamp`: microseconds since the
    epoch in UTC, whatever zone the type names), `ts` a wall clock (a
    `timestamp_ntz`, a `date`): both are microsecond lanes, and a
    literal of the one kind never compares with a lane of the other.
    `decimal:<s>` is a `decimal(p,s)` of p <= 18 as its unscaled value
    (`1234.56` at scale 2 is 123456), the scale part of the kind: the
    stat was read from its digits into a `decimal128(p,s)`
    (`stats/skipping.py::_decimal_reader_schema`), never through a
    double."""
    if pa.types.is_decimal128(t):
        return (f"decimal:{t.scale}"
                if t.precision <= DECIMAL_LANE_PRECISION
                and 0 <= t.scale <= t.precision else None)
    if pa.types.is_boolean(t):
        return "bool"
    if pa.types.is_integer(t):
        return "int"
    if pa.types.is_floating(t):
        return "float"
    if pa.types.is_timestamp(t):
        return "ts" if t.tz is None else "tstz"
    if pa.types.is_date(t):
        return "ts"
    return None


def _why_no_lane(t: pa.DataType, delta_type: Optional[str]) -> str:
    """The `scan.stats_index_unindexed_leaves.<why>` of a leaf with
    min/max stats and no lane."""
    if (delta_type or "").startswith("decimal"):
        return "decimal"
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        # text the schema calls a time did not read as one
        return "string" if delta_type in (None, "string") else "unparsed"
    return "other" if _lane_kind(t) is None else "unparsed"


def _resolve_kind(k_min: Optional[str], k_max: Optional[str]) -> Optional[str]:
    """Unify the min/max leaf kinds (pa_json infers each JSON field
    independently, so `min=1, max=1.5` parses as int64/double)."""
    if k_min is None or k_max is None:
        return None
    if k_min == k_max:
        return k_min
    if {k_min, k_max} == {"int", "float"}:
        return "float"
    return None


def _typed_leaves(t, prefix: Tuple[str, ...] = ()) -> Dict[tuple, pa.DataType]:
    """{path: type} of every leaf of a schema or struct type, in order."""
    out = {}
    for f in t:
        if pa.types.is_struct(f.type):
            out.update(_typed_leaves(f.type, prefix + (f.name,)))
        else:
            out[prefix + (f.name,)] = f.type
    return out


def _encode_lane(arr: pa.Array, kind: str):
    """(int64 values, validity) for one stat leaf under `kind`; invalid
    slots hold 0. None when the whole leaf can't be encoded."""
    try:
        valid = np.asarray(pc.is_valid(arr), dtype=bool)
        if kind == "bool":
            enc = np.asarray(pc.fill_null(arr.cast(pa.int64()), 0), np.int64)
        elif kind == "int":
            enc = np.asarray(pc.fill_null(arr.cast(pa.int64()), 0), np.int64)
        elif kind == "float":
            f = np.asarray(pc.fill_null(arr.cast(pa.float64()), 0.0),
                           np.float64)
            if pa.types.is_integer(arr.type):
                # int64 -> float64 is lossy past 2^53: such stats stay
                # "unknown" rather than compare inexactly
                raw = np.asarray(pc.fill_null(arr.cast(pa.int64()), 0),
                                 np.int64)
                valid &= np.abs(raw) <= _F64_EXACT_INT
            valid &= ~np.isnan(f)
            enc = _enc_f64(f)
        elif kind in ("ts", "tstz"):
            tz = arr.type.tz if pa.types.is_timestamp(arr.type) else None
            ts = arr.cast(pa.timestamp("us", tz=tz))
            enc = np.asarray(pc.fill_null(ts.cast(pa.int64()), 0), np.int64)
        elif _lane_kind(arr.type) == kind:      # decimal:<scale>
            # the unscaled value is the low word of the 128 bits (little
            # endian), whole within 18 digits
            words = np.frombuffer(arr.buffers()[1], np.int64,
                                  count=2 * len(arr), offset=16 * arr.offset)
            enc = np.where(valid, words[::2], 0)
        else:
            return None
        return enc, valid
    except _ARROW_ERRS:
        return None


def encode_literal(value, kind: str) -> Optional[int]:
    """Encode a predicate literal into the lane's int64 order; None =
    not exactly representable -> the conjunct falls back to Arrow.

    Times: a zone-aware `datetime` against a `tstz` lane (a Delta
    `timestamp`) is its UTC instant, whatever its offset; a zone-less
    `datetime`, a `date` or ISO text against a `ts` lane
    (`timestamp_ntz`, `date`) is that wall clock. Across the two there
    is no answer without a session time zone, and none is assumed: the
    literal compiles to nothing, the Arrow ladder refuses it too, the
    files are kept and `scan.skip_uncompared_conjuncts` counts it."""
    if value is None:
        return None
    if kind == "bool":
        return int(value) if isinstance(value, bool) else None
    if isinstance(value, bool):
        return None
    if kind == "int" or kind.startswith("decimal:"):
        # an `int`, a `decimal.Decimal` or digits in text, where it is
        # exact at the lane's scale (0 of an `int` lane, which takes no
        # text: `4000.5` against a `long` is the ladder's, never 4000)
        exact = None if kind == "int" and isinstance(value, str) \
            else decimal_literal(value)
        if exact is None:
            return None
        scale = 0 if kind == "int" else int(kind[len("decimal:"):])
        with decimal.localcontext() as ctx:
            ctx.prec = 80
            unscaled = exact.scaleb(scale)
            if unscaled != unscaled.to_integral_value():
                return None
            v = int(unscaled)
        return v if -(1 << 63) <= v < (1 << 63) else None
    if kind == "float":
        if isinstance(value, (int, np.integer)):
            if abs(int(value)) > _F64_EXACT_INT:
                return None
            value = float(value)
        if isinstance(value, (float, np.floating)):
            f = np.float64(value)
            if np.isnan(f):
                return None
            return int(_enc_f64(np.asarray([f]))[0])
        return None
    if kind == "tstz":
        if isinstance(value, datetime.datetime) \
                and value.utcoffset() is not None:
            s = pa.scalar(value).cast(pa.timestamp("us", tz="UTC"))
            return s.value
        return None
    if kind == "ts":
        if isinstance(value, datetime.datetime) and value.tzinfo is not None:
            return None
        if isinstance(value, (str, datetime.date, datetime.datetime)):
            try:
                s = pa.scalar(value).cast(pa.timestamp("us"))
            except _ARROW_ERRS:
                return None
            return s.value if s.is_valid else None
        return None
    return None


@functools.cache
def _halves_fn():
    """jit'd arrival of `_UPLOAD_ROWS` lane rows: the int64 values
    `[k, n_pad]` split into their 32-bit halves (the chip has no 64-bit
    integers: left int64, the compiler splits every lane of the index at
    every launch that reads it), the validity words `uint32 [k, n_pad /
    32]` unpacked by the tree's own shift-and-mask over 32-bit words (a
    `jnp.unpackbits` over the uint8 words shifts 8-bit lanes, which the
    v5e compiler takes 102 s over at a 2.6M-row index; this form, 3 s),
    and the three written as rows `r0`.. of the resident arrays, in
    place: those are donated, so the chip holds the index once and a
    piece's int64 form beside it. (Split whole, a 70-lane index takes
    its 1.5 GB of int64, 1.65 GB of results and 1.7 GB of temporaries
    at once, donated or not: a donor of another shape is not reused.)"""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from delta_tpu.ops.replay import _unpack_bits_device

    @obs.program("stats.index_upload")
    def place(high, low, valid, vals, words, r0):
        tiles = (vals.shape[0],) + high.shape[1:]
        rows = ((vals >> 32).astype(jnp.int32), vals.astype(jnp.uint32),
                _unpack_bits_device(words.reshape(-1)) != 0)
        return tuple(
            lax.dynamic_update_slice_in_dim(whole, part.reshape(tiles), r0,
                                            axis=0)
            for whole, part in zip((high, low, valid), rows))

    return jax.jit(place, donate_argnums=(0, 1, 2))


@dataclass(frozen=True)
class StatsIndexSeed:
    """What is kept of an index released by a version advance, to make
    the next one from: its lanes, kinds and parsed rows (read, never
    written to; the rows as the index carried them,
    `stats/skipping.py::ParsedPieces`: a seed makes no table), and the
    live mask over the raw rows of its version, whose set bits its `n`
    rows were."""

    vals: Optional[np.ndarray]
    valid: Optional[np.ndarray]
    cols: Dict[tuple, Tuple[int, str]]
    parsed: Optional[ParsedPieces]
    n: int
    base_live: np.ndarray
    unindexed: Dict[str, int]


class ResidentStatsIndex:
    """Per-snapshot-version stats index: the parsed stats rows (the
    host fallback ladder's, an Arrow table once it asks) plus the
    encoded int64 lanes, with a lazily uploaded device copy: the lanes'
    32-bit halves and their validity, `[R, n_pad / TILE_FILES,
    TILE_FILES]` each (`device_lanes`), which an eviction drops and the
    next plan uploads, and splits, anew."""

    def __init__(self, arrow_index, vals: Optional[np.ndarray],
                 valid: Optional[np.ndarray],
                 cols: Dict[tuple, Tuple[int, str]], n: int,
                 table_path: Optional[str] = None,
                 version: Optional[int] = None,
                 unindexed: Optional[Dict[str, int]] = None):
        self._lock = threading.Lock()
        self.arrow_index = arrow_index
        self.vals = vals          # int64 [R, n_pad] or None
        self.valid = valid        # bool  [R, n_pad] or None
        self.cols = cols          # {physical name_path: (min row, kind)}
        # {why: leaves with min/max stats and no lane} (`_why_no_lane`)
        self.unindexed = unindexed or {}
        self.n = n
        self.table_path = table_path
        self.version = version
        self.released = False
        self._dev = None
        self._hbm = hbm.noop_handle()

    @property
    def has_lanes(self) -> bool:
        return self.vals is not None and not self.released

    def seed(self, base_live: np.ndarray) -> Optional[StatsIndexSeed]:
        """The seed of the next version's index, `base_live` the live
        mask this one was built under; None once released."""
        with self._lock:
            if self.released:
                return None
            return StatsIndexSeed(self.vals, self.valid, self.cols,
                                  self.arrow_index.carried(), self.n,
                                  base_live, self.unindexed)

    def device_lanes(self):
        """(high halves, low halves, validity) device arrays, uploading
        on first use."""
        with self._lock:
            dev = self._upload_locked()
            if dev is not None:
                self._hbm.touch()
            return dev

    def _upload_locked(self):
        """The device copy: (high int32, low uint32, valid bool), each
        `[R, n_pad / TILE_FILES, TILE_FILES]`, so that a lane row is
        whole tiles of the chip's vectors. The host's int64 rows cross
        `_UPLOAD_ROWS` at a time and are split there (`_halves_fn`), the
        next piece on its way while one is placed and no further ahead:
        the chip never holds a second copy of the index."""
        if self._dev is not None or self.vals is None or self.released:
            return self._dev
        import jax
        import jax.numpy as jnp

        from delta_tpu.ops.stats import _x64

        n_lanes, n_pad = self.vals.shape
        with obs.span("index.pack_valid",
                      _verbose=self.n < obs.PHASE_SPAN_ROWS,
                      lanes=n_lanes, bytes=self.valid.nbytes):
            lane_vals = np.asarray(self.vals, np.int64)
            # bit k of word j is file 32 j + k (n_pad is a multiple of 128)
            valid_words = np.packbits(np.asarray(self.valid, bool), axis=1,
                                      bitorder="little").view("<u4")
        tiles = (n_lanes, n_pad // TILE_FILES, TILE_FILES)
        place = _halves_fn()
        with obs.span("stats.index_upload", rows=self.n, form="halves",
                      bytes=lane_vals.nbytes + valid_words.nbytes), \
            obs.device_dispatch("stats.index_upload",
                                key=(n_lanes, n_pad),
                                budget="stats-index-lanes",
                                units=n_lanes * n_pad) as dd, _x64():
            dd.h2d("lane_vals", lane_vals)
            dd.h2d("valid_words", valid_words)
            dev = tuple(jnp.zeros(tiles, t)
                        for t in (jnp.int32, jnp.uint32, jnp.bool_))
            sent = None     # the piece on its way: rows, words, first row
            for r0 in (*range(0, n_lanes, _UPLOAD_ROWS), None):
                if sent is not None:
                    # delta-lint: disable=jit-sync (audited: the piece
                    # before has been placed before the one after next
                    # is sent: two pieces' int64 on the chip at most)
                    dev[0].block_until_ready()
                    dev = place(*dev, *sent)
                if r0 is not None:
                    rows = slice(r0, r0 + _UPLOAD_ROWS)
                    # delta-lint: disable=transfer-budget (audited: the
                    # two budgeted lanes themselves, every row of both
                    # once, recorded whole above)
                    sent = jax.device_put(
                        (lane_vals[rows], valid_words[rows])) + (np.int32(r0),)
        _LANE_SPLITS.inc()
        self._dev = dev
        self._hbm = hbm.register(
            self, kind=hbm.KIND_STATS_INDEX, table_path=self.table_path,
            version=self.version, arrays=dev,
            rebuild_cost_class="cheap",  # lazy re-upload from host lanes
            evictor=self.evict_device,
        )
        return self._dev

    def evict_device(self) -> None:
        """Drop only the device copy (ledger shed under HBM pressure).
        The host lanes stay, so the next `device_lanes()` call lazily
        re-uploads — this is what makes the artifact cheap-to-rebuild
        rather than lost."""
        with self._lock:
            if self._dev is not None:
                self._dev = None
                self._hbm.release()
                self._hbm = hbm.noop_handle()

    def release(self) -> None:
        """Drop host lanes and the device copy (serve-cache eviction or
        version advancement). jax arrays are refcounted, so a scan
        concurrently holding the lanes finishes safely; the next scan
        of a still-live snapshot simply rebuilds."""
        with self._lock:
            if self._dev is not None:
                self._dev = None
                self._hbm.release()
                self._hbm = hbm.noop_handle()
            self.vals = None
            self.valid = None
            self.released = True


def _encode_column(mn: pa.Array, mx: pa.Array, nc: Optional[pa.Array],
                   kind: str):
    """The three lanes (min, max, nullCount) of one eligible column as
    (values, validity) pairs; None when min or max cannot be encoded.
    A nullCount that is missing or unreadable is unknown on every row."""
    enc_mn = _encode_lane(mn, kind)
    enc_mx = _encode_lane(mx, kind)
    if enc_mn is None or enc_mx is None:
        return None
    return enc_mn, enc_mx, _encode_count(nc, len(mn))


def _encode_count(arr: Optional[pa.Array], n: int):
    enc = _encode_lane(arr, "int") if arr is not None else None
    return enc if enc is not None else (np.zeros(n, np.int64),
                                        np.zeros(n, bool))


def _lanes_of(n_lanes: int, n: int):
    """Zeroed lane matrix and validity plane in `n`'s pad bucket."""
    from delta_tpu.ops.replay import pad_bucket

    n_pad = pad_bucket(max(n, 1), min_bucket=TILE_FILES)
    return (np.zeros((n_lanes, n_pad), np.int64),
            np.zeros((n_lanes, n_pad), bool))


def build_index(files: pa.Table, table_path: Optional[str] = None,
                version: Optional[int] = None,
                metadata=None,
                rows: Optional[np.ndarray] = None) -> ResidentStatsIndex:
    """Columnarize one snapshot version's parsed stats into lanes; with
    `rows`, of those rows of `files` alone (the rows a state holds and
    its live mask: the index of the live ones, no copy made of their
    strings). With
    the table's `metadata`, the stat leaves its schema names are typed
    by it (`stats/skipping.py::stat_leaf_types`): a `timestamp` gets a
    `tstz` lane, a `timestamp_ntz` a `ts` lane, a `decimal(p,s)` of
    p <= 18 a `decimal:<s>` lane of its unscaled value, read from the
    stat's digits; a wider decimal none (its stats parse as floats,
    which would compare inexactly)."""
    from delta_tpu.stats.skipping import StatsIndex, stat_leaf_types

    leaf_types = {} if metadata is None else stat_leaf_types(metadata)
    n = files.num_rows if rows is None else int(np.count_nonzero(rows))
    small = n < obs.PHASE_SPAN_ROWS
    with obs.span("index.parse", _verbose=small, rows=n):
        arrow_index = StatsIndex.from_stats_column(files.column("stats"),
                                                   leaf_types=leaf_types,
                                                   rows=rows)
    with obs.span("index.encode", _verbose=small, rows=arrow_index.n) as ph:
        vals, valid, cols, unindexed = _encode_all(arrow_index, leaf_types)
        ph.set_attr("lanes", 0 if vals is None else len(vals))
    return ResidentStatsIndex(arrow_index, vals, valid, cols, arrow_index.n,
                              table_path=table_path, version=version,
                              unindexed=unindexed)


def _encode_all(arrow_index, leaf_types):
    """(lane matrix, validity plane, {leaf: (min row, kind)}, {why: leaves
    left off the lanes}) of every row of a parsed index; no matrix where
    no leaf is eligible."""
    n = arrow_index.n
    table = arrow_index._table
    if table is None:
        return None, None, {}, {}

    names = table.column_names
    mins = table.schema.field("minValues").type \
        if "minValues" in names else None
    maxs = table.schema.field("maxValues").type \
        if "maxValues" in names else None
    if (mins is None or maxs is None or not pa.types.is_struct(mins)
            or not pa.types.is_struct(maxs)):
        return None, None, {}, {}

    lanes: List[Tuple[np.ndarray, np.ndarray]] = []
    cols: Dict[tuple, Tuple[int, str]] = {}
    unindexed: Dict[str, int] = {}
    for path in _typed_leaves(mins):
        mn = arrow_index.min_values(path)
        mx = arrow_index.max_values(path)
        if mn is None or mx is None or pa.types.is_null(mn.type):
            continue
        delta_type = leaf_types.get(path)
        kind = _resolve_kind(_lane_kind(mn.type), _lane_kind(mx.type))
        if (delta_type or "").startswith("decimal") \
                and not (kind or "").startswith("decimal:"):
            kind = None     # read as doubles (p > 18): would compare inexactly
        encoded = None if kind is None else _encode_column(
            mn, mx, arrow_index.null_count(path), kind)
        if encoded is None:
            why = _why_no_lane(mn.type, delta_type)
            unindexed[why] = unindexed.get(why, 0) + 1
            continue
        cols[path] = (len(lanes), kind)
        lanes.extend(encoded)
    if not cols:
        return None, None, {}, unindexed
    lanes.append(_encode_count(arrow_index.num_records(), n))

    vals, valid = _lanes_of(len(lanes), n)
    lanes.reverse()     # each lane goes as it is written: never two of all
    for r in range(len(lanes)):
        vals[r, :n], valid[r, :n] = lanes.pop()
    return vals, valid, cols, unindexed


def _cannot_append(reason: str):
    return None, {"append_fallback": reason}


def append_index(seed: StatsIndexSeed, live_mask: np.ndarray,
                 tail_stats: pa.ChunkedArray,
                 table_path: Optional[str] = None,
                 version: Optional[int] = None, metadata=None):
    """The index of the live rows under `live_mask`, in raw order, made
    from `seed`: the seed's rows that are still live, then the rows
    landed since, their stats (`tail_stats`, one string a live row past
    the seed's) parsed under the seed's schema and encoded under its
    kinds. Returns the index and the build span's attributes (`rows`
    parsed, `dropped`), or None and the reason (`append_fallback`)
    where the result cannot be shown equal to `build_index` over every
    live file: the caller then builds in full. Rows that went never
    narrow the schema: a leaf that only they carried stays, as a lane
    unknown on every row (which keeps, as no lane does)."""
    from delta_tpu.stats.skipping import StatsIndex, stat_leaf_types

    if seed.vals is None:
        return _cannot_append("seed-without-lanes")
    n_base = len(seed.base_live)
    still_live = live_mask[:n_base]
    if len(still_live) != n_base:
        return _cannot_append("row-count")
    if (still_live & ~seed.base_live).any():
        return _cannot_append("row-revived")    # bits are only cleared
    survivors = still_live[seed.base_live]
    n_kept = int(survivors.sum())
    n_tail = int(live_mask[n_base:].sum())
    n = n_kept + n_tail
    if len(survivors) != seed.n or len(tail_stats) != n_tail:
        return _cannot_append("row-count")

    small = seed.n < obs.PHASE_SPAN_ROWS
    schema = seed.parsed.schema
    with obs.span("index.parse", _verbose=small, rows=n_tail):
        if n_tail:
            tail = StatsIndex.from_stats_column(
                tail_stats, schema=schema,
                leaf_types=None if metadata is None
                else stat_leaf_types(metadata))
        else:
            tail = StatsIndex(schema.empty_table(), 0)
    if tail.schema is None:
        # a leaf the seed lacks, a leaf of another type (an int
        # column's first float), a non-finite token, or no stats on
        # any new row: an inferring parse of every row may read
        # those, under another schema than the seed's
        return _cannot_append(_why_unread(tail_stats, schema))

    dropped = seed.n - n_kept
    with obs.span("index.compact_lanes", _verbose=small,
                  lanes=len(seed.vals), rows=seed.n, dropped=dropped) as ph:
        vals, valid = _lanes_of(len(seed.vals), n)
        for r in range(len(seed.vals)):
            old_vals, old_valid = seed.vals[r, :seed.n], seed.valid[r, :seed.n]
            vals[r, :n_kept] = old_vals[survivors] if dropped else old_vals
            valid[r, :n_kept] = old_valid[survivors] if dropped else old_valid
        ph.set_attr("bytes", vals.nbytes + valid.nbytes)
    with obs.span("index.encode", _verbose=small, rows=n_tail,
                  lanes=len(seed.vals)):
        for path, (row0, kind) in seed.cols.items():
            encoded = _encode_column(tail.min_values(path),
                                     tail.max_values(path),
                                     tail.null_count(path), kind)
            if encoded is None:
                return _cannot_append("tail-encode")
            for r, (ev, eva) in enumerate(encoded, row0):
                vals[r, n_kept:n] = ev
                valid[r, n_kept:n] = eva
        ev, eva = _encode_count(tail.num_records(), n_tail)
        vals[-1, n_kept:n] = ev
        valid[-1, n_kept:n] = eva

    # the parsed rows go on as they came, the seed's pieces and the
    # tail's behind them: only the ladder reads them, and the table is
    # made when it first does (`StatsIndex._table`)
    parsed = seed.parsed.advanced(
        np.flatnonzero(~survivors) if dropped else np.zeros(0, np.int64),
        tail._table)
    if isinstance(parsed, ParsedPieces):
        _TABLE_DEFERRED.inc()
    idx = ResidentStatsIndex(StatsIndex(parsed, n), vals, valid, seed.cols,
                             n, table_path=table_path, version=version,
                             unindexed=seed.unindexed)
    return idx, {"rows": n_tail, "dropped": dropped}


def _why_unread(stats: pa.ChunkedArray, schema: pa.Schema) -> str:
    """Why rows did not read under `schema`, for the fallback's label
    (on the way to a full build, so an inferring parse is cheap)."""
    from delta_tpu.stats.skipping import StatsIndex

    if stats.null_count == len(stats):
        return "tail-without-stats"
    inferred = StatsIndex.from_stats_column(stats)._table
    if inferred is None:
        return "tail-unparsed"
    seed_leaves = _typed_leaves(schema)
    for path, t in _typed_leaves(inferred.schema).items():
        if path not in seed_leaves:
            return "new-leaf"
        if t != seed_leaves[path] and not pa.types.is_null(t):
            return "leaf-type"
    return "tail-unparsed"              # e.g. non-finite tokens, nulled


def _compile_conj(conj: Expression,
                  cols: Dict[tuple, Tuple[int, str]],
                  notes: Optional[set] = None):
    """Compile one conjunct to a list of OR-groups of atom triples
    (min_row, op_code, encoded literal), the AND of which it is; None =
    not compilable (the conjunct joins the Arrow fallback ladder).
    An AND is its sides' groups one after the other. An OR of sides
    with several groups is distributed: `(a1 AND a2) OR (b1 AND b2)` is
    the four groups `(ai OR bj)`, each pairing of a group of either
    side, while the atoms that come to stay within
    `IN_LIST_ATOM_LIMIT`; wider, it compiles to nothing. `notes` is
    told `distributed` or `too_wide`."""
    notes = set() if notes is None else notes
    if isinstance(conj, Comparison):
        sides = (conj.left, conj.right)
        if isinstance(sides[0], Column) and isinstance(sides[1], Literal):
            colref, lit, op = sides[0], sides[1], conj.op
        elif isinstance(sides[1], Column) and isinstance(sides[0], Literal):
            colref, lit, op = sides[1], sides[0], _FLIP[conj.op]
        else:
            return None
        ent = cols.get(colref.name_path)
        if ent is None or op not in _OP_CODES:
            return None
        enc = encode_literal(lit.value, ent[1])
        if enc is None:
            return None
        return [[(ent[0], _OP_CODES[op], enc)]]
    if isinstance(conj, (And, Or)):
        left = _compile_conj(conj.left, cols, notes)
        right = _compile_conj(conj.right, cols, notes)
        if left is None or right is None:
            return None
        if isinstance(conj, And):
            return left + right
        if len(left) == len(right) == 1:
            return [left[0] + right[0]]
        groups = [lg + rg for lg in left for rg in right]
        if sum(len(g) for g in groups) > IN_LIST_ATOM_LIMIT:
            notes.add("too_wide")
            return None
        notes.add("distributed")
        return groups
    if isinstance(conj, (IsNull, IsNotNull)):
        child = conj.child
        ent = cols.get(child.name_path) if isinstance(child, Column) else None
        if ent is None:
            return None
        code = _OP_ISNULL if isinstance(conj, IsNull) else _OP_ISNOTNULL
        return [[(ent[0], code, 0)]]
    if isinstance(conj, In):
        if not isinstance(conj.child, Column) or not conj.values:
            return None
        ent = cols.get(conj.child.name_path)
        if ent is None:
            return None
        encs = []
        for v in conj.values:
            e = encode_literal(v, ent[1])
            if e is None:
                return None
            encs.append(e)
        if len(encs) > IN_LIST_ATOM_LIMIT:
            # range prefilter only: col >= min(values) AND col <= max
            # (the encoding is order-preserving, so min/max over the
            # encoded ints bound the raw values)
            return [[(ent[0], _OP_CODES[">="], min(encs))],
                    [(ent[0], _OP_CODES["<="], max(encs))]]
        return [[(ent[0], _OP_CODES["="], e) for e in encs]]
    if isinstance(conj, Not):
        inner = conj.child
        if isinstance(inner, Comparison):
            return _compile_conj(
                Comparison(_NEG[inner.op], inner.left, inner.right), cols,
                notes)
        if isinstance(inner, IsNull):
            return _compile_conj(IsNotNull(inner.child), cols, notes)
        if isinstance(inner, IsNotNull):
            return _compile_conj(IsNull(inner.child), cols, notes)
        return None
    return None


def compile_conjuncts(conjuncts: List[Expression],
                      index: ResidentStatsIndex):
    """Split a conjunct list into (AtomBlock, fallback conjuncts). The
    block covers every compilable conjunct in ONE dispatch; the rest
    go through the per-conjunct Arrow ladder on both routes, so the
    final mask is route-independent by construction."""
    if not index.has_lanes:
        return None, list(conjuncts)
    rows_mn: List[int] = []
    ops: List[int] = []
    lits: List[int] = []
    grp: List[int] = []
    fallback: List[Expression] = []
    n_groups = distributed = 0
    for conj in conjuncts:
        notes: set = set()
        groups = _compile_conj(conj, index.cols, notes)
        if groups is None:
            if "too_wide" in notes:
                _TOO_WIDE.inc()
            fallback.append(conj)
            continue
        if "distributed" in notes:
            _DISTRIBUTED.inc()
            distributed += 1
        for g in groups:
            for (row0, code, enc) in g:
                rows_mn.append(row0)
                ops.append(code)
                lits.append(enc)
                grp.append(n_groups)
            n_groups += 1
    if not rows_mn:
        return None, fallback
    rmn = np.asarray(rows_mn, np.int32)
    decimal_rows = [row0 for row0, kind in index.cols.values()
                    if kind.startswith("decimal:")]
    block = AtomBlock(
        rows_mn=rmn,
        rows_mx=rmn + 1,
        rows_nc=rmn + 2,
        ops=np.asarray(ops, np.int32),
        lits=np.asarray(lits, np.int64),
        grp=np.asarray(grp, np.int32),
        n_atoms=len(rows_mn),
        n_groups=n_groups,
        decimal_atoms=int(np.isin(rmn, decimal_rows).sum()),
        distributed=distributed,
    )
    return block, fallback


def snapshot_stats_index(state, files: Optional[pa.Table] = None,
                         metadata=None):
    """The state's resident index, building it on first use, its stat
    leaves typed by the schema of the table's `metadata`. `files` None
    is a scan over the state's own live rows: what stats strings the
    build needs (a refresh: those of the rows landed since the seed)
    are read out of the rows held, and the live table is never asked
    for. A caller that holds that table may pass it. Returns None when
    `state` can't host an index or `files` isn't the state's own
    live-file table (e.g. the conflict checker's stats subsets)."""
    lock = getattr(state, "_stats_index_lock", None)
    if lock is None:
        return None
    if files is not None:
        try:
            if state.add_files_table is not files:
                return None
        except AttributeError:
            return None
    with lock:
        idx = state.stats_index
        if idx is not None and not idx.released:
            _REUSES.inc()
            return idx
        table_path = getattr(state, "table_path", None)
        version = getattr(state, "version", None)
        seed = getattr(state, "stats_index_seed", None)
        n = files.num_rows if files is not None else len(state.live_rows)
        small = n < obs.PHASE_SPAN_ROWS
        with obs.span("stats.index_build", rows=n) as sp:
            idx = None
            if seed is not None:
                state.stats_index_seed = None
                # only an advanced `SnapshotState` has a seed: the
                # tail's stats are in the rows it holds, past the seed's
                n_base = len(seed.base_live)
                with obs.span("index.read_stats", _verbose=small) as ph:
                    stats = state.file_actions.column("stats").slice(
                        n_base).filter(pa.array(state.live_mask[n_base:]))
                    if ph.recording:
                        ph.set_attrs(rows=len(stats), bytes=stats.nbytes)
                idx, attrs = append_index(seed, state.live_mask, stats,
                                          table_path, version, metadata)
                sp.set_attrs(**attrs)
            if idx is not None:
                sp.set_attr("mode", "append")
                _APPENDS.inc()
            else:
                if seed is not None:
                    _APPEND_FALLBACKS.inc()
                else:   # a state loaded in full: nothing to append to
                    sp.set_attr("reason", "no_seed")
                live = None
                if files is None:
                    # the live rows' strings are read where they lie,
                    # a piece at a time as they are parsed: at a fact
                    # table's width a copy of them is 4 GB held for the
                    # length of the build
                    with obs.span("index.read_stats",
                                  _verbose=small) as ph:
                        files = state.file_actions.select(["stats"])
                        live = state.live_mask
                        if ph.recording:
                            ph.set_attrs(
                                rows=n, bytes=files.column("stats").nbytes)
                stats = files.column("stats")
                idx = build_index(files, table_path, version, metadata,
                                  rows=live)
                sp.set_attr("mode", "full")
                _BUILDS.inc()
            for why, leaves in idx.unindexed.items():
                _UNINDEXED[why].inc(leaves)
            if sp.recording:
                kinds = collections.Counter(
                    kind.partition(":")[0] for _, kind in idx.cols.values())
                sp.set_attrs(
                    bytes=stats.nbytes,
                    lanes=0 if idx.vals is None else len(idx.vals),
                    columns=len(idx.cols),
                    lane_kinds=",".join(f"{k}:{n}"
                                        for k, n in sorted(
                                            kinds.items(),
                                            key=lambda kn: _LANE_KINDS.index(
                                                kn[0]))),
                    unindexed=sum(idx.unindexed.values()))
        state.stats_index = idx
        # built implicitly by ordinary filtered scans, so a state
        # dropped outside the explicit-release paths (one-shot reads,
        # version advance, serve eviction) must not read as a ledger
        # leak: the state's own GC releases the lanes (idempotent with
        # the explicit paths — same contract as the operand cache in
        # sqlengine/operands.py)
        weakref.finalize(state, ResidentStatsIndex.release, idx)
        return idx


def release_state_stats_index(state) -> None:
    """Release a state's resident index and the seed of one, if any
    (duck-typed: `parallel/resident.py::release_snapshot_resident`
    passes whatever it was given)."""
    idx = getattr(state, "stats_index", None)
    if idx is not None:
        idx.release()
        state.stats_index = None
    if getattr(state, "stats_index_seed", None) is not None:
        state.stats_index_seed = None   # the next index's host lanes
