"""Columnarization: log files → one canonical Arrow file-actions table.

This is the host half of state reconstruction. It turns the log segment's
JSON commits and Parquet checkpoint parts into:

- one Arrow table of *file actions* (adds + removes unified, `is_add`
  flag), each row tagged with `(version, order)` — the chronological
  coordinate the device replay sorts by; and
- the *small actions* (protocol, metaData, txn, domainMetadata,
  commitInfo) resolved host-side (they are O(commits), not O(files)).

Key performance move: all JSON commit files in a segment are concatenated
into ONE buffer and parsed by a single `pyarrow.json.read_json` call
(C++, multithreaded) — per-row version tags are derived from per-file line
counts. The reference pays this cost as a Spark JSON scan
(`Snapshot.scala:524` loadActions); the kernel as per-file Jackson parses
(`ActionsIterator.java:77`).
"""

from __future__ import annotations

import json
import threading
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.json as pa_json

from delta_tpu import obs
from delta_tpu.utils.chunks import pieces
from delta_tpu.models.actions import (
    CommitInfo,
    DomainMetadata,
    Metadata,
    Protocol,
    SetTransaction,
)

# process-wide parse-cache effectiveness counters (obs registry names
# mirror the per-instance ParsedCommitCache fields)
_OBS_CACHE_HITS = obs.counter("parse_cache.hits")
_OBS_CACHE_PARTIAL = obs.counter("parse_cache.partial_hits")
_OBS_CACHE_MISSES = obs.counter("parse_cache.misses")
_OBS_CACHE_HIT_FILES = obs.counter("parse_cache.hit_files")
_OBS_CACHE_MISS_FILES = obs.counter("parse_cache.miss_files")
_TORN_COMMITS = obs.counter("log.torn_commits")
_OBS_DECODE_PARTS = obs.counter("decode.device_parts")
_OBS_DECODE_FALLBACKS = obs.counter("decode.device_fallbacks")
# same instrument as replay/device_parse.py: absorbed device-parse
# exceptions bump the cataloged parse fallback counter here (the
# in-module bumps cover only the None-return unsupported shapes)
_OBS_PARSE_FALLBACKS = obs.counter("parse.device_fallbacks")

DV_STRUCT_TYPE = pa.struct(
    [
        pa.field("storageType", pa.string()),
        pa.field("pathOrInlineDv", pa.string()),
        pa.field("offset", pa.int32()),
        pa.field("sizeInBytes", pa.int32()),
        pa.field("cardinality", pa.int64()),
        pa.field("maxRowIndex", pa.int64()),
    ]
)

# The unified add/remove row. `dv_id` is the computed DV unique id (null =
# no DV); replay key is (path, dv_id). Checkpoint-only columns (stats,
# tags...) are nullable.
CANONICAL_FILE_ACTION_SCHEMA = pa.schema(
    [
        pa.field("path", pa.string()),
        pa.field("dv_id", pa.string()),
        pa.field("partition_values", pa.map_(pa.string(), pa.string())),
        pa.field("size", pa.int64()),
        pa.field("modification_time", pa.int64()),
        pa.field("data_change", pa.bool_()),
        pa.field("stats", pa.string()),
        pa.field("tags", pa.string()),  # JSON-encoded map; rare
        pa.field("deletion_vector", DV_STRUCT_TYPE),
        pa.field("base_row_id", pa.int64()),
        pa.field("default_row_commit_version", pa.int64()),
        pa.field("clustering_provider", pa.string()),
        pa.field("deletion_timestamp", pa.int64()),  # removes only
        pa.field("extended_file_metadata", pa.bool_()),  # removes only
        pa.field("is_add", pa.bool_()),
        pa.field("version", pa.int64()),
        pa.field("order", pa.int32()),
    ]
)


@dataclass
class ColumnarActions:
    """Output of columnarization for one log segment."""

    file_actions: pa.Table  # CANONICAL_FILE_ACTION_SCHEMA
    protocol: Optional[Protocol] = None
    metadata: Optional[Metadata] = None
    set_transactions: Dict[str, SetTransaction] = field(default_factory=dict)
    domain_metadata: Dict[str, DomainMetadata] = field(default_factory=dict)
    latest_commit_info: Optional[CommitInfo] = None
    commit_infos: Dict[int, CommitInfo] = field(default_factory=dict)
    num_commit_files: int = 0
    bytes_parsed: int = 0
    # Replay-key sidecar from the native scanner (first-appearance path
    # codes + delta encoding), row-aligned with file_actions. Only set
    # when file_actions came from one native scan (no checkpoint blocks)
    # so the alignment is exact; replay falls back to factorize otherwise.
    replay_keys: Optional[object] = None
    # Early-launched device replay (ops.replay.ReplayPending): dispatched
    # right after the native scan so the device sorts while the host
    # assembles the Arrow table. Row-aligned with file_actions under the
    # same sole-native-block condition as replay_keys.
    pending_masks: Optional[object] = None
    # Deferred stats decode (lazy-stats native scan): () -> Arrow string
    # array replacing the placeholder stats column. Set under the same
    # sole-native-block condition. NOTE: while this is set,
    # `file_actions` carries an all-null stats PLACEHOLDER — internal
    # replay consumers read only replay-safe columns, and SnapshotState
    # splices the real column before any user-facing surface; any other
    # caller must use `file_actions_complete()`.
    stats_thunk: Optional[object] = None
    # Device-resident sharded replay state (parallel/resident.py
    # ResidentShardState), established by compute_masks_device when the
    # sharded route runs; reconstruct_state moves ownership to the
    # SnapshotState so `Snapshot.update()` can append delta rows without
    # re-shipping the base state.
    resident: Optional[object] = None
    _splice_lock: object = field(default_factory=threading.Lock,
                                 repr=False, compare=False)

    def file_actions_complete(self) -> pa.Table:
        """The canonical table with the stats column materialized (the
        safe accessor for code outside the snapshot pipeline). Locked so
        concurrent first calls run the decode thunk exactly once."""
        with self._splice_lock:
            self.file_actions, self.stats_thunk = splice_stats(
                self.file_actions, self.stats_thunk)
            return self.file_actions

    @property
    def num_actions(self) -> int:
        return self.file_actions.num_rows


def splice_stats(table: pa.Table, stats_thunk):
    """Replace the deferred-stats placeholder column with the decoded
    one (shared by ColumnarActions and SnapshotState). Returns
    (table, None); no-op when no decode is pending."""
    if stats_thunk is None:
        return table, None
    idx = table.schema.get_field_index("stats")
    return (table.set_column(idx, table.schema.field(idx), stats_thunk()),
            None)


def _field_or_null(struct_arr: pa.StructArray, name: str, typ: pa.DataType) -> pa.Array:
    n = len(struct_arr)
    t = struct_arr.type
    if t.get_field_index(name) >= 0:
        arr = pc.struct_field(struct_arr, name)
        # struct-typed actual values (e.g. JSON-inferred tags maps) are
        # normalized downstream, never cast here
        if (arr.type != typ
                and not (pa.types.is_map(typ) or pa.types.is_struct(typ))
                and not pa.types.is_struct(arr.type)):
            arr = arr.cast(typ, safe=False)
        return arr
    return pa.nulls(n, typ)


def _struct_to_map(arr: pa.Array, n: int) -> pa.Array:
    """Normalize partitionValues: JSON inference yields struct<col:string>,
    checkpoints yield map<string,string>. Returns map<string,string>.
    Every struct field becomes a map entry per row (protocol: one entry
    per partition column, value may be null)."""
    map_type = pa.map_(pa.string(), pa.string())
    if pa.types.is_map(arr.type):
        if arr.type != map_type:
            arr = arr.cast(map_type, safe=False)
        return arr
    if pa.types.is_null(arr.type):
        return pa.nulls(n, map_type)
    assert pa.types.is_struct(arr.type), arr.type
    k = arr.type.num_fields
    names = [arr.type.field(i).name for i in range(k)]
    if k == 0:
        offsets = np.zeros(n + 1, dtype=np.int32)
        return pa.MapArray.from_arrays(
            pa.array(offsets, pa.int32()), pa.array([], pa.string()), pa.array([], pa.string())
        )
    valid = np.asarray(pc.is_valid(arr), dtype=bool)
    counts = np.where(valid, k, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    # keys: tile names for valid rows
    keys = np.tile(np.array(names, dtype=object), n)[np.repeat(valid, k)] if k else []
    item_cols = [pc.struct_field(arr, i) for i in range(k)]
    # interleave: row-major [row0f0, row0f1, ..., row1f0, ...]
    item_mat = np.empty((n, k), dtype=object)
    for j, col_arr in enumerate(item_cols):
        item_mat[:, j] = np.asarray(col_arr.cast(pa.string()), dtype=object)
    items = item_mat.reshape(-1)[np.repeat(valid, k)]
    return pa.MapArray.from_arrays(
        pa.array(offsets, pa.int64()).cast(pa.int32()),
        pa.array(list(keys), pa.string()),
        pa.array(list(items), pa.string()),
    )


def _map_or_json_to_string(arr: pa.Array, n: int) -> pa.Array:
    """tags → JSON string column (host-only metadata, rarely set)."""
    if pa.types.is_string(arr.type):
        return arr
    if pa.types.is_null(arr.type):
        return pa.nulls(n, pa.string())
    pylist = arr.to_pylist()
    out = [
        json.dumps(dict(v) if not isinstance(v, dict) else v, sort_keys=True)
        if v is not None
        else None
        for v in pylist
    ]
    return pa.array(out, pa.string())


def _dv_unique_id(storage, path_or_inline, offset, valid_mask, n) -> pa.Array:
    """unique id = storageType + pathOrInlineDv [+ "@" + offset]
    (DeletionVectorDescriptor.uniqueId semantics)."""
    # no DVs anywhere (the overwhelmingly common case): skip the string
    # kernels entirely — they cost ~0.2s per 3M rows
    if isinstance(valid_mask, np.ndarray):
        any_dv = bool(valid_mask.any())
    else:
        any_dv = bool(pc.any(valid_mask).as_py())
    if not any_dv:
        return pa.nulls(n, pa.string())
    base = pc.binary_join_element_wise(
        pc.fill_null(storage, ""), pc.fill_null(path_or_inline, ""), ""
    )
    with_offset = pc.binary_join_element_wise(
        base, pc.cast(offset, pa.string()), "@"
    )
    dv_id = pc.if_else(pc.is_valid(offset), with_offset, base)
    return pc.if_else(valid_mask, dv_id, pa.nulls(n, pa.string()))


def _normalize_dv(arr: pa.Array, n: int) -> tuple[pa.Array, pa.Array]:
    """Returns (dv struct column, dv_id string column)."""
    if pa.types.is_null(arr.type) or not pa.types.is_struct(arr.type):
        return pa.nulls(n, DV_STRUCT_TYPE), pa.nulls(n, pa.string())
    storage = _field_or_null(arr, "storageType", pa.string())
    path_or_inline = _field_or_null(arr, "pathOrInlineDv", pa.string())
    offset = _field_or_null(arr, "offset", pa.int32())
    size = _field_or_null(arr, "sizeInBytes", pa.int32())
    card = _field_or_null(arr, "cardinality", pa.int64())
    max_row = _field_or_null(arr, "maxRowIndex", pa.int64())
    valid_mask = pc.is_valid(arr)
    dv_struct = pa.StructArray.from_arrays(
        [storage, path_or_inline, offset, size, card, max_row],
        fields=list(DV_STRUCT_TYPE),
        mask=pc.invert(valid_mask),
    )
    return dv_struct, _dv_unique_id(storage, path_or_inline, offset, valid_mask, n)


# bytes compared at a time in `_any_percent`, so that the comparison's
# result stays in the cache: the 55 MB of 2.4M paths on the chip's host
# (PR 44) take 5.6 ms in blocks of 256 KiB and 68.6 ms compared whole
# (`match_substring` a string 98.3, `re.search` over the buffer 17.6)
_PERCENT_BLOCK = 1 << 18


def _any_percent(arr) -> bool:
    """Whether a `%` (0x25) lies in the data bytes between the first and
    the last offset of the rows given (a slice's neighbours are not
    read): one pass over bytes, no kernel a string. The bytes a null
    slot still spans are read with the rest, so they can send a column
    to the exact pass and can never make it skip one."""
    for chunk in (arr.chunks if isinstance(arr, pa.ChunkedArray) else [arr]):
        if pa.types.is_null(chunk.type) or not len(chunk):
            continue
        _, offsets, data = chunk.buffers()
        if data is None:
            continue
        width = np.dtype(np.int64 if pa.types.is_large_string(chunk.type)
                         else np.int32)
        ends = np.frombuffer(offsets, width, len(chunk) + 1,
                             chunk.offset * width.itemsize)
        span = np.frombuffer(data, np.uint8)[int(ends[0]):int(ends[-1])]
        for lo in range(0, len(span), _PERCENT_BLOCK):
            if (span[lo:lo + _PERCENT_BLOCK] == 0x25).any():
                return True
    return False


def _decode_paths(arr: pa.Array) -> pa.Array:
    """Percent-decode RFC 2396 path URIs. Fast path: untouched when no '%'
    appears (the common case for writer-generated UUID file names)."""
    if not _any_percent(arr):
        return arr
    from urllib.parse import unquote

    py = arr.to_pylist()
    return pa.array([unquote(p) if p is not None and "%" in p else p for p in py], pa.string())


# the rule `replay/state.py::_filter_rows` has, for the same column
_COMBINE_WHOLE_BYTES = 1 << 30


def _extract_file_actions(
    table: pa.Table,
    col: str,
    versions: np.ndarray,
    orders: np.ndarray,
) -> Optional[pa.Table]:
    """Extract add/remove rows from one parsed chunk into the canonical
    schema. `versions`/`orders` are per-row tags for the whole chunk."""
    if col not in table.column_names:
        return None
    struct_chunks = table.column(col)
    if struct_chunks.null_count == len(struct_chunks):
        return None
    is_add = col == "add"
    if struct_chunks.nbytes > _COMBINE_WHOLE_BYTES:
        # a checkpoint of a table at a fact table's width: its stats
        # strings pass what one chunk's offsets reach, so the rows are
        # brought to the canonical schema a chunk of the file's at a
        # time, as they lie. A narrower column takes the one call it
        # always took
        blocks, at = [], 0
        for piece in pieces(struct_chunks, _COMBINE_WHOLE_BYTES, join=False):
            rows = slice(at, at + len(piece))
            at += len(piece)
            block = _extract_structs([piece], is_add, versions[rows],
                                     orders[rows])
            if block is not None:
                blocks.append(block)
        if not blocks:
            return None
        # whoever gathers rows out of the table pays for each chunk of
        # each column (`replay/state.py::gather_rows`). While the copy
        # that takes is no more than the limit twice, the rows are
        # joined into pieces under the limit, every column at the same
        # rows; wider (a second copy of 4 GB beside the file's table is
        # what the host may not have), the wide column stays the file's
        # chunks and every column under the limit is made one chunk
        table = pa.concat_tables(blocks)
        widest = max(table.columns, key=lambda col: col.nbytes)
        if widest.nbytes > 2 * _COMBINE_WHOLE_BYTES:
            return pa.Table.from_arrays(
                [col if col.nbytes > _COMBINE_WHOLE_BYTES
                 else pa.chunked_array([col.combine_chunks()], col.type)
                 for col in table.columns], schema=table.schema)
        ends, rows, size = [], 0, 0
        for chunk in widest.chunks:
            if size and size + chunk.nbytes > _COMBINE_WHOLE_BYTES:
                ends.append(rows)
                size = 0
            rows, size = rows + len(chunk), size + chunk.nbytes
        return pa.concat_tables(
            [table.slice(lo, hi - lo).combine_chunks()
             for lo, hi in zip([0] + ends, ends + [rows])])
    return _extract_structs(struct_chunks.chunks, is_add, versions, orders)


# A run of present rows travels as a slice where the runs are at least
# this long on average, and through `filter` under it. On the chip's host
# (PR 44; 600k add structs of the cold-load cell's checkpoint, every
# second run there, ms by slices / by `filter`): runs of 16 rows 73.2 /
# 25.5, 64 22.2 / 21.5, 128 14.3 / 21.7, 1,024 7.2 / 19.3 (a slice costs
# ~3.5 us whatever it holds): the two meet between 64 and 128
_VIEW_MIN_RUN_ROWS = 128

_ROWS_VIEWED = obs.counter("canonicalize.rows_viewed")
_ROWS_FILTERED = obs.counter("canonicalize.rows_filtered")


def _present_runs(chunks: Sequence[pa.Array]) -> Tuple[np.ndarray, np.ndarray]:
    """The runs of rows that are there, as (starts, stops) over the rows
    of `chunks` laid end to end, from each chunk's validity read once. A
    run ends with its chunk, so each is a slice of one chunk."""
    starts, stops, at = [np.empty(0, np.int64)], [np.empty(0, np.int64)], 0
    for chunk in chunks:
        n = len(chunk)
        if n and chunk.null_count == 0:
            starts.append(np.array([at]))
            stops.append(np.array([at + n]))
        elif chunk.null_count < n:
            steps = np.diff(np.asarray(pc.is_valid(chunk)).view(np.int8),
                            prepend=0, append=0)
            starts.append(np.flatnonzero(steps == 1) + at)
            stops.append(np.flatnonzero(steps == -1) + at)
        at += n
    return np.concatenate(starts), np.concatenate(stops)


def _extract_structs(chunks: Sequence[pa.Array], is_add: bool,
                     versions: np.ndarray,
                     orders: np.ndarray) -> Optional[pa.Table]:
    """The add (or remove) structs of `chunks` that are there, in the
    canonical schema, one chunk a column. The selection rides on the one
    concatenation: long runs of present rows go into it as slices of
    the chunks they lie in, short ones (adds and removes interleaved)
    as each chunk's `filter`."""
    if pa.types.is_null(chunks[0].type):
        return None
    rows = sum(len(c) for c in chunks)
    with obs.span("canonicalize.filter", rows=rows) as sp:
        starts, stops = _present_runs(chunks)
        kept = int((stops - starts).sum())
        if not kept:
            return None
        # runs that meet at a chunk's end are one run of the column
        runs = len(starts) - int((starts[1:] == stops[:-1]).sum())
        view = runs == 1 or kept >= runs * _VIEW_MIN_RUN_ROWS
        sp.set_attrs(runs=runs, kept="view" if view else "filter",
                     rows_kept=kept)
        if view:
            _ROWS_VIEWED.inc(kept)
            bounds = np.cumsum([0] + [len(c) for c in chunks])
            which = np.searchsorted(bounds, starts, side="right") - 1
            parts = [chunks[i].slice(lo - bounds[i], hi - lo)
                     for i, lo, hi in zip(which.tolist(), starts.tolist(),
                                          stops.tolist())]
        else:
            _ROWS_FILTERED.inc(kept)
            parts = [c.drop_null() for c in chunks if c.null_count < len(c)]
        if runs == 1:
            # the rows' tags follow as views too
            tagged = slice(int(starts[0]), int(stops[-1]))
        else:
            steps = np.zeros(rows + 1, np.int8)
            steps[starts] = 1
            steps[stops] -= 1
            tagged = np.cumsum(steps[:-1], dtype=np.int8).view(bool)
    with obs.span("canonicalize.combine", rows=kept):
        # one chunk a column is what every later gather relies on
        # (`replay/state.py::gather_rows` pays for each chunk it touches)
        sub = parts[0] if len(parts) == 1 else pa.concat_arrays(parts)
    with obs.span("canonicalize.columns", rows=kept):
        return _canonical_block(sub, kept, is_add, versions[tagged],
                                orders[tagged])


def _canonical_block(sub: pa.StructArray, n: int, is_add: bool,
                     versions: np.ndarray, orders: np.ndarray) -> pa.Table:
    """The canonical-schema table of `n` selected add or remove structs
    (`versions`/`orders` already selected to match)."""
    raw_path = _field_or_null(sub, "path", pa.string())
    path = _decode_paths(raw_path)
    obs.set_attr("escaped", int(path is not raw_path))
    pv = _struct_to_map(_field_or_null(sub, "partitionValues", pa.map_(pa.string(), pa.string())), n)
    size = _field_or_null(sub, "size", pa.int64())
    mod_time = _field_or_null(sub, "modificationTime", pa.int64())
    data_change = _field_or_null(sub, "dataChange", pa.bool_())
    stats = _field_or_null(sub, "stats", pa.string())
    if is_add and stats.null_count == n:
        # writeStatsAsJson=false checkpoints carry stats only in the
        # stats_parsed struct — re-serialize so skipping keeps working
        stats = _stats_from_parsed(sub, n) or stats
    tags = _map_or_json_to_string(_field_or_null(sub, "tags", pa.string()), n)
    dv_struct, dv_id = _normalize_dv(
        _field_or_null(sub, "deletionVector", DV_STRUCT_TYPE), n
    )
    base_row_id = _field_or_null(sub, "baseRowId", pa.int64())
    drcv = _field_or_null(sub, "defaultRowCommitVersion", pa.int64())
    clustering = _field_or_null(sub, "clusteringProvider", pa.string())
    del_ts = _field_or_null(sub, "deletionTimestamp", pa.int64())
    ext_meta = _field_or_null(sub, "extendedFileMetadata", pa.bool_())

    return pa.table(
        {
            "path": path,
            "dv_id": dv_id,
            "partition_values": pv,
            "size": size,
            "modification_time": mod_time,
            "data_change": data_change,
            "stats": stats,
            "tags": tags,
            "deletion_vector": dv_struct,
            "base_row_id": base_row_id,
            "default_row_commit_version": drcv,
            "clustering_provider": clustering,
            "deletion_timestamp": del_ts,
            "extended_file_metadata": ext_meta,
            "is_add": pa.array(np.full(n, is_add, dtype=bool)),
            "version": pa.array(versions, pa.int64()),
            "order": pa.array(orders, pa.int32()),
        },
        schema=CANONICAL_FILE_ACTION_SCHEMA,
    )


def _stats_from_parsed(sub: pa.StructArray, n: int) -> Optional[pa.Array]:
    """Re-serialize `stats_parsed` structs to stats JSON strings (only
    taken when the checkpoint was written with writeStatsAsJson=false,
    so the struct is the sole stats form).

    Deliberately a per-row Python pass: JSON string escaping rules out a
    compositional Arrow-kernel rebuild, and this path only runs for the
    opt-in struct-only checkpoint configuration, once per snapshot load
    (the result is cached with the snapshot state)."""
    names = [f.name for f in sub.type]
    if "stats_parsed" not in names:
        return None
    sp = sub.field("stats_parsed")
    if pa.types.is_null(sp.type) or sp.null_count == len(sp):
        return None
    import json as _json

    from delta_tpu.stats.collection import _json_value

    out = []
    for r in sp.to_pylist():
        if not r:
            out.append(None)
        else:
            out.append(_json.dumps(_prune_nones(r), separators=(",", ":"),
                                   default=_json_value))
    return pa.array(out, pa.string())


def _prune_nones(d):
    if isinstance(d, dict):
        return {k: _prune_nones(v) for k, v in d.items() if v is not None}
    if isinstance(d, list):
        return [_prune_nones(v) for v in d]
    return d


def _present_rows(chunk: pa.Array) -> np.ndarray:
    """The numbers of the rows of `chunk` that are there, rising, from
    its validity bits as they lie: the bytes that hold a row are found
    first, so a column of 2.4M rows all null but one (what a projected
    read of a checkpoint hands over, in one chunk) costs its bitmap's
    300 KB and not a byte a row."""
    n, validity = len(chunk), chunk.buffers()[0]
    if validity is None:
        return np.arange(n)
    first = chunk.offset // 8
    packed = np.frombuffer(validity, np.uint8)[
        first:(chunk.offset + n + 7) // 8]
    holds = np.flatnonzero(packed)
    byte, bit = np.nonzero(
        np.unpackbits(packed[holds], bitorder="little").reshape(-1, 8))
    rows = (first + holds[byte]) * 8 + bit - chunk.offset
    return rows[(rows >= 0) & (rows < n)]


def _present_small_rows(table: pa.Table, cols: Sequence[str]):
    """(column, row number, body) of every row of the small-action
    columns `cols` that is there, column by column and in row order. A
    chunk with no such row (a checkpoint's are all but one) is passed
    over by its `null_count`; no column is ever combined."""
    for col in cols:
        if col not in table.column_names:
            continue
        column = table.column(col)
        if pa.types.is_null(column.type):
            continue
        at = 0
        for chunk in column.chunks:
            if chunk.null_count < len(chunk):
                sel = _present_rows(chunk)
                bodies = chunk.take(pa.array(sel, pa.int64())).to_pylist()
                for i, body in zip(sel.tolist(), bodies):
                    yield col, at + i, body
            at += len(chunk)


@dataclass
class _SmallActionTracker:
    """Latest-seen-wins resolution for O(commits) actions."""

    protocol: tuple = (-1, -1, None)
    metadata: tuple = (-1, -1, None)
    txns: Dict[str, tuple] = field(default_factory=dict)
    domains: Dict[str, tuple] = field(default_factory=dict)
    commit_infos: Dict[int, CommitInfo] = field(default_factory=dict)

    def scan_chunk(self, table: pa.Table, versions: np.ndarray, orders: np.ndarray):
        handlers = self._handlers()
        for col, i, row in _present_small_rows(table, tuple(handlers)):
            handlers[col](int(versions[i]), int(orders[i]), _prune_nones(row))

    def _handlers(self) -> dict:
        return {
            "protocol": self._on_protocol,
            "metaData": self._on_metadata,
            "txn": self._on_txn,
            "domainMetadata": self._on_domain,
            "commitInfo": self._on_commit_info,
        }

    def _on_protocol(self, v, o, row):
        if (v, o) > self.protocol[:2]:
            self.protocol = (v, o, Protocol.from_dict(row))

    def _on_metadata(self, v, o, row):
        if (v, o) > self.metadata[:2]:
            self.metadata = (v, o, Metadata.from_dict(row))

    def _on_txn(self, v, o, row):
        txn = SetTransaction.from_dict(row)
        cur = self.txns.get(txn.appId)
        if cur is None or (v, o) > cur[:2]:
            self.txns[txn.appId] = (v, o, txn)

    def _on_domain(self, v, o, row):
        dm = DomainMetadata.from_dict(row)
        cur = self.domains.get(dm.domain)
        if cur is None or (v, o) > cur[:2]:
            self.domains[dm.domain] = (v, o, dm)

    def _on_commit_info(self, v, o, row):
        self.commit_infos[v] = CommitInfo.from_dict(row)

    def scan_pylist(self, rows: Sequence[Tuple[int, int, dict]]):
        """Consume (version, order, {action-key: body}) rows — the
        native scanner's non-file-action lines."""
        handlers = self._handlers()
        for v, o, row in rows:
            for key, body in row.items():
                h = handlers.get(key)
                if h is not None and body is not None:
                    h(v, o, _prune_nones(body))


def _read_commits_buffer(
    engine,
    commit_infos: Sequence[Tuple[int, str, int]],
    max_workers: int = 16,
) -> Optional[tuple[bytearray, np.ndarray, np.ndarray]]:
    """Parallel-read commit files into ONE preallocated buffer.

    commit_infos: (version, path, size-from-listing). Each file gets a
    region of `size + 1` bytes, the last byte forced to "\\n" (blank
    lines between files are ignored by the parsers). Returns
    (buffer, per-file byte starts[n+1], per-file versions), or None when
    a listed size disagrees with the bytes read (caller re-reads)."""
    n = len(commit_infos)
    if any(int(s) < 0 for _, _, s in commit_infos):
        # fast listing deferred the stats: resolve sizes now (this path
        # runs only when the native one-round-trip reader is unavailable)
        from delta_tpu.utils.threads import parallel_map

        def stat(info):
            v, p, s = info
            if int(s) >= 0:
                return info
            return (v, p, engine.fs.file_status(p).size)

        try:
            commit_infos = parallel_map(stat, list(commit_infos))
        except FileNotFoundError as e:
            from delta_tpu.log.segment import CorruptLogError

            # a listed commit vanished before reading: concurrent log
            # cleanup — the same contract as a listing gap
            raise CorruptLogError(
                f"commit file vanished after listing (concurrent log "
                f"cleanup?): {e}",
                error_class="DELTA_COMMIT_FILE_VANISHED") from e
    sizes = np.array([max(0, int(s)) for _, _, s in commit_infos], dtype=np.int64)
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(sizes + 1, out=starts[1:])
    total = int(starts[-1])
    buf = bytearray(total)
    mv = memoryview(buf)
    mismatch: List[int] = []

    def fill(i: int):
        _, path, _ = commit_infos[i]
        off = starts[i]
        local = engine.fs.os_path(path)
        if local is not None:
            # local file: read straight into the shared buffer (no
            # intermediate bytes object, no second copy)
            try:
                with open(local, "rb") as f:
                    got = f.readinto(mv[off:off + sizes[i]])
                    if got != sizes[i] or f.read(1):
                        mismatch.append(i)
                        return
            except OSError:
                mismatch.append(i)
                return
        else:
            data = engine.fs.read_file(path)
            if len(data) != sizes[i]:
                mismatch.append(i)
                return
            mv[off:off + sizes[i]] = data
        mv[off + sizes[i]] = 0x0A

    from delta_tpu.utils.threads import default_io_threads, shared_pool

    workers = min(max_workers, default_io_threads())
    with obs.span("storage.read_commits", files=n, bytes=total,
                  workers=workers if n > 4 else 0):
        if n > 4:
            # obs.wrap: contextvars don't cross the pool boundary, so
            # bind this span as the workers' parent explicitly. The
            # shared pool is safe here because fill() is a leaf read —
            # it never submits pool work of its own.
            shared_pool().map(obs.wrap(fill), range(n))
        else:
            for i in range(n):
                fill(i)
    if mismatch:
        return None
    version_arr = np.array([v for v, _, _ in commit_infos], dtype=np.int64)
    return buf, starts, version_arr


def _parse_buffer_generic(
    buf, starts: np.ndarray, version_arr: np.ndarray
) -> Optional[tuple[pa.Table, np.ndarray, np.ndarray, int]]:
    """Generic path over one concatenated buffer: one Arrow read_json
    call. Row→version mapping comes from one vectorized pass: a row ends
    at every newline not preceded by a newline; per-file counts by
    searchsorted over region boundaries. None when the parsed row count
    disagrees with the line accounting (caller re-reads per file)."""
    total = int(starts[-1])
    arr = np.frombuffer(buf, np.uint8)
    nl = arr == 0x0A
    prev = np.empty_like(nl)
    prev[0] = True
    prev[1:] = nl[:-1]
    row_ends = np.nonzero(nl & ~prev)[0]
    counts = np.diff(np.searchsorted(row_ends, starts))
    versions = np.repeat(version_arr, counts)
    orders = (
        np.arange(versions.shape[0], dtype=np.int64)
        - np.repeat(np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    ).astype(np.int32)

    try:
        table = pa_json.read_json(
            pa.BufferReader(pa.py_buffer(buf)),
            read_options=pa_json.ReadOptions(block_size=1 << 24),
        )
    except pa.ArrowInvalid:
        # malformed JSON somewhere in the concatenated buffer; the
        # per-file fallback path diagnoses which commit (and whether it
        # is a torn trailing line) precisely
        return None
    if table.num_rows != versions.shape[0]:
        return None
    return table, versions, orders, total


def parse_commit_files(
    engine,
    commit_infos: Sequence[Tuple[int, str, int]],
    max_workers: int = 16,
) -> tuple[Optional[pa.Table], np.ndarray, np.ndarray, int]:
    """One buffer, one Arrow read_json call; per-file re-read fallback
    when listed sizes or line accounting disagree."""
    if not commit_infos:
        return None, np.empty(0, np.int64), np.empty(0, np.int32), 0
    read = _read_commits_buffer(engine, commit_infos, max_workers)
    out = _parse_buffer_generic(*read) if read is not None else None
    if out is None:
        from delta_tpu.utils.threads import parallel_map

        blobs = parallel_map(
            lambda vp: (vp[0], engine.fs.read_file(vp[1])),
            [(v, p) for v, p, _ in commit_infos])
        return parse_commit_batch(blobs)
    return out


def parse_commit_batch(
    commit_blobs: Sequence[Tuple[int, bytes]],
) -> tuple[Optional[pa.Table], np.ndarray, np.ndarray, int]:
    """Concatenate (version, raw bytes) commit files and parse once.

    Returns (parsed table, per-row versions, per-row orders, total bytes).
    """
    if not commit_blobs:
        return None, np.empty(0, np.int64), np.empty(0, np.int32), 0
    versions_parts: List[np.ndarray] = []
    orders_parts: List[np.ndarray] = []
    bufs: List[bytes] = []
    total = 0
    for version, blob in commit_blobs:
        total += len(blob)
        if not blob.endswith(b"\n"):
            blob = blob + b"\n"
        # vectorized line count; writers never emit blank lines, but fall
        # back to an exact scan if one shows up
        if b"\n\n" in blob or blob.startswith(b"\n"):
            nlines = sum(1 for ln in blob.split(b"\n") if ln.strip())
        else:
            nlines = int((np.frombuffer(blob, np.uint8) == 10).sum())
        bufs.append(blob)
        versions_parts.append(np.full(nlines, version, np.int64))
        orders_parts.append(np.arange(nlines, dtype=np.int32))
    data = b"".join(bufs)
    versions = np.concatenate(versions_parts) if versions_parts else np.empty(0, np.int64)
    orders = np.concatenate(orders_parts) if orders_parts else np.empty(0, np.int32)
    try:
        table = pa_json.read_json(
            pa.BufferReader(data),
            read_options=pa_json.ReadOptions(block_size=1 << 24),
        )
    except pa.ArrowInvalid as e:
        _raise_commit_parse_error(commit_blobs, str(e), cause=e)
    if table.num_rows != versions.shape[0]:
        _raise_commit_parse_error(
            commit_blobs,
            f"JSON parse row count {table.num_rows} != line count "
            f"{versions.shape[0]}",
        )
    return table, versions, orders, total


def _raise_commit_parse_error(
    commit_blobs: Sequence[Tuple[int, bytes]], detail: str, cause=None
):
    """Diagnose a commit-batch parse failure before raising.

    A crashed writer on a non-atomic store leaves the *newest* commit
    with a truncated final line; everything before it is intact. That
    shape is recoverable (drop the tip, read at version - 1), so it gets
    a dedicated `TornCommitError` carrying the torn version. Corruption
    anywhere else means the log itself is damaged and stays a plain
    `LogCorruptedError`.
    """
    from delta_tpu.errors import LogCorruptedError, TornCommitError

    tip_version, tip_blob = max(commit_blobs, key=lambda vb: vb[0])
    lines = [ln for ln in tip_blob.split(b"\n") if ln.strip()]
    torn = False
    if lines:
        try:
            json.loads(lines[-1])
        except ValueError:
            torn = all(_json_line_ok(ln) for ln in lines[:-1])
    if torn:
        _TORN_COMMITS.inc()
        raise TornCommitError(
            f"commit {tip_version} ends with a torn JSON line "
            f"(interrupted write); earlier lines are intact",
            version=tip_version,
        ) from cause
    raise LogCorruptedError(detail, version=tip_version) from cause


def _json_line_ok(line: bytes) -> bool:
    try:
        json.loads(line)
        return True
    except ValueError:
        return False


SMALL_ACTION_COLUMNS = ("protocol", "metaData", "txn", "domainMetadata")


def _extract_small_rows(
    table: pa.Table, versions: np.ndarray, orders: np.ndarray
) -> List[Tuple[int, int, dict]]:
    """Small-action rows of a parsed chunk in the native scanner's
    `others` format: (version, order, {action-key: body}). Lets a cached
    generic parse feed `_SmallActionTracker.scan_pylist` on later loads
    without re-touching the Arrow chunk."""
    return [(int(versions[i]), int(orders[i]), {col: row})
            for col, i, row in _present_small_rows(
                table, (*SMALL_ACTION_COLUMNS, "commitInfo"))]


class _OnceThunk:
    """Memoize a one-shot decode thunk (the native scan's stats thunk
    consumes its scan object on first call) so a cached parse can serve
    the decoded column to any number of later snapshots."""

    __slots__ = ("_thunk", "_value", "_lock")

    def __init__(self, thunk):
        self._thunk = thunk
        self._value = None
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            if self._thunk is not None:
                self._value = self._thunk()
                self._thunk = None
            return self._value


def _combined_stats_thunk(parts):
    """Deferred stats decode spanning several blocks: `parts` is a list
    of (block, thunk-or-None); blocks without a thunk contribute their
    already-real stats column. Returns None when nothing is deferred."""
    if all(th is None for _, th in parts):
        return None

    def thunk():
        chunks: List[pa.Array] = []
        for block, th in parts:
            col = th() if th is not None else block.column("stats")
            if isinstance(col, pa.ChunkedArray):
                chunks.extend(col.chunks)
            else:
                chunks.append(col)
        return pa.chunked_array(chunks, pa.string())

    return thunk


@dataclass
class ParsedSpan:
    """One cached parse result covering a contiguous run of commit
    files. `keys` (native replay-key sidecar) is row-aligned with
    `block` and only usable when the span is the snapshot's sole
    file-action source."""

    block: pa.Table
    others: List[Tuple[int, int, dict]]
    keys: Optional[object]
    stats_thunk: Optional[_OnceThunk]
    n_files: int
    nbytes: int


def _span_nbytes(block: pa.Table, others: list) -> int:
    try:
        b = block.get_total_buffer_size()
    except (AttributeError, NotImplementedError):
        b = block.nbytes  # older pyarrow without the buffer-level API
    return int(b) + 256 * len(others)


class ParsedCommitCache:
    """Process-wide LRU of parsed commit spans, keyed by the tuple of
    `(path, size, mtime)` of the files each span covers (commit files
    are written put-if-absent, so the triple identifies the content;
    stat-deferred listings key on `(path, -1, 0)` consistently).

    Shared between full and incremental loads: a full load caches one
    span for the whole commit run; each `update()` caches one small span
    for its tail — so a later full reload is assembled entirely from
    cached spans and re-parses nothing. Coverage is greedy from the
    front of the request; only the uncovered tail is parsed."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        from collections import OrderedDict

        self._spans: "OrderedDict[tuple, ParsedSpan]" = OrderedDict()
        self._by_first: Dict[tuple, List[tuple]] = {}
        self._bytes = 0
        self.hits = 0          # lookups fully served from cache
        self.partial_hits = 0  # a prefix was served, tail parsed
        self.misses = 0
        self.hit_files = 0
        self.miss_files = 0

    def get_covering(self, file_keys: tuple) -> List[ParsedSpan]:
        """Longest greedy prefix cover of `file_keys` by cached spans
        (possibly empty). Covered spans are LRU-refreshed."""
        out: List[ParsedSpan] = []
        n = len(file_keys)
        with self._lock:
            i = 0
            while i < n:
                best = None
                for k in self._by_first.get(file_keys[i], ()):
                    if (len(k) <= n - i
                            and (best is None or len(k) > len(best))
                            and file_keys[i:i + len(k)] == k):
                        best = k
                if best is None:
                    break
                self._spans.move_to_end(best)
                out.append(self._spans[best])
                i += len(best)
            self.hit_files += i
            self.miss_files += n - i
            _OBS_CACHE_HIT_FILES.inc(i)
            _OBS_CACHE_MISS_FILES.inc(n - i)
            if i == n:
                self.hits += 1
                _OBS_CACHE_HITS.inc()
            elif out:
                self.partial_hits += 1
                _OBS_CACHE_PARTIAL.inc()
            else:
                self.misses += 1
                _OBS_CACHE_MISSES.inc()
        return out

    def put(self, file_keys: tuple, span: ParsedSpan) -> None:
        if not file_keys or span.nbytes > self.max_bytes:
            return
        with self._lock:
            if file_keys in self._spans:
                return
            self._spans[file_keys] = span
            self._by_first.setdefault(file_keys[0], []).append(file_keys)
            self._bytes += span.nbytes
            while self._bytes > self.max_bytes and len(self._spans) > 1:
                old_key, old = self._spans.popitem(last=False)
                self._bytes -= old.nbytes
                sibs = self._by_first.get(old_key[0], [])
                if old_key in sibs:
                    sibs.remove(old_key)
                    if not sibs:
                        del self._by_first[old_key[0]]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._by_first.clear()
            self._bytes = 0

    @property
    def cached_bytes(self) -> int:
        return self._bytes


_PARSE_CACHE: Optional[ParsedCommitCache] = None
_PARSE_CACHE_LOCK = threading.Lock()
_PARSE_CACHE_DEFAULT_BYTES = 6 << 30


def parse_cache() -> Optional[ParsedCommitCache]:
    """The process-wide parsed-commit cache, or None when disabled via
    DELTA_TPU_PARSE_CACHE_BYTES=0."""
    global _PARSE_CACHE
    if _PARSE_CACHE is None:
        with _PARSE_CACHE_LOCK:
            if _PARSE_CACHE is None:
                budget = int(os.environ.get(
                    "DELTA_TPU_PARSE_CACHE_BYTES",
                    _PARSE_CACHE_DEFAULT_BYTES))
                _PARSE_CACHE = (ParsedCommitCache(budget) if budget > 0
                                else False)
    return _PARSE_CACHE or None


def clear_parse_cache() -> None:
    """Drop all cached parses AND re-read the budget env var (tests and
    the bench cold-comparator use this)."""
    global _PARSE_CACHE
    with _PARSE_CACHE_LOCK:
        _PARSE_CACHE = None


def columnarize_log_segment(
    engine,
    segment,
    table_root: Optional[str] = None,
    small_only: bool = False,
    early_replay: bool = True,
) -> ColumnarActions:
    """Read every file in the segment and produce a ColumnarActions.

    Chunk order: checkpoint parts first (tagged with the checkpoint
    version), then compacted deltas, then commits ascending — but order
    only matters through the (version, order) tags; the device sort makes
    global order irrelevant.

    `small_only`: resolve only the small actions (protocol / metaData /
    txn / domainMetadata / commitInfo) — checkpoint parquet is read with
    column projection (the add/remove columns, i.e. ~all of a large
    checkpoint's bytes, are never decoded), sidecars are skipped (file
    actions only), and no file-action blocks are built. This is the
    reference's P&M fast path (`Snapshot.scala:440`,
    `LogReplay.loadTableProtocolAndMetadata`).
    """
    with obs.span("log.columnarize", version=segment.version,
                  small_only=small_only) as osp:
        out = _columnarize_log_segment(engine, segment, table_root,
                                       small_only, early_replay)
        osp.set_attrs(bytes_parsed=out.bytes_parsed,
                      num_commit_files=out.num_commit_files,
                      num_actions=out.num_actions)
        return out


def _columnarize_log_segment(
    engine,
    segment,
    table_root: Optional[str],
    small_only: bool,
    early_replay: bool,
) -> ColumnarActions:
    tracker = _SmallActionTracker()
    blocks: List[pa.Table] = []
    bytes_parsed = 0

    # Device-resident replay handoff: when the checkpoint is the sole
    # file-action source, every part's replay-key code lane (decoded on
    # device, never materialized on host) can feed the replay kernel
    # directly. Any contributor the decoder didn't key (sidecar, Arrow
    # fallback, JSON part) or any count/dv mismatch disables it — the
    # host replay path is then authoritative.
    want_handoff = (early_replay and not small_only
                    and bool(segment.checkpoints)
                    and not segment.compacted_deltas
                    and not segment.deltas)
    handoff = {"ok": want_handoff, "parts": []}

    def _dv_all_null(block) -> bool:
        return (block is None
                or block.column("dv_id").null_count == block.num_rows)

    def _abandon_handoff(part_keys=None) -> None:
        # a dead handoff abandons every accumulated device code lane;
        # deregister them so the resident ledger never counts lanes no
        # launch will ever consume
        from delta_tpu.ops.page_decode import release_part_keys

        dead = list(handoff["parts"])
        if part_keys is not None:
            dead.append(part_keys)
        handoff["parts"] = []
        release_part_keys(dead)

    def _track_handoff(part_keys, add_block, rem_block) -> None:
        if not handoff["ok"]:
            if part_keys is not None:
                _abandon_handoff(part_keys)
            return
        n_add = add_block.num_rows if add_block is not None else 0
        n_rem = rem_block.num_rows if rem_block is not None else 0
        if part_keys is None:
            # keyless contributors break row alignment unless they
            # contribute no file-action rows at all
            handoff["ok"] = not (n_add or n_rem)
            if not handoff["ok"]:
                _abandon_handoff()
            return
        # the device key lane must agree row-for-row with the Arrow
        # blocks: same present counts, no null paths inside present
        # structs, and no deletion vectors (the key lane is path-only)
        if (part_keys.n_bad or part_keys.n_add != n_add
                or part_keys.n_rem != n_rem
                or not _dv_all_null(add_block)
                or not _dv_all_null(rem_block)):
            handoff["ok"] = False
            _abandon_handoff(part_keys)
        else:
            handoff["parts"].append(part_keys)

    def _consume_checkpoint_table(tbl: pa.Table, part_keys=None):
        nonlocal blocks
        n = tbl.num_rows
        with obs.span("checkpoint.canonicalize", rows=n) as sp:
            if sp.recording:
                sp.set_attr("bytes", tbl.nbytes)
            versions = np.full(n, cp_version, np.int64)
            # checkpoint rows precede all commit rows at the same
            # version; order is irrelevant within a checkpoint (keys
            # are unique)
            orders = np.arange(n, dtype=np.int32)
            with obs.span("canonicalize.small_actions", rows=n):
                tracker.scan_chunk(tbl, versions, orders)
            if small_only:
                return  # sidecars carry only file actions — nothing to do
            part_blocks = {}
            for col in ("add", "remove"):
                block = _extract_file_actions(tbl, col, versions, orders)
                part_blocks[col] = block
                if block is not None:
                    blocks.append(block)
            _track_handoff(part_keys, part_blocks["add"],
                           part_blocks["remove"])
        # V2 checkpoints: resolve sidecar pointers to _sidecars/ parquet
        if "sidecar" in tbl.column_names:
            sc = tbl.column("sidecar").combine_chunks()
            if not pa.types.is_null(sc.type):
                paths = pc.struct_field(sc, "path").to_pylist()
                sidecar_paths = [
                    p if "/" in p else f"{segment.log_path}/_sidecars/{p}"
                    for p in paths
                    if p is not None
                ]
                subs = engine.parquet.read_parquet_files(sidecar_paths)
                for _ in sidecar_paths:
                    _consume_checkpoint_table(
                        _read_part(lambda: next(subs)))

    def _read_part(read, nbytes=None) -> pa.Table:
        """One part's table: `read()` is the fetch and the Arrow decode
        of exactly one file."""
        with obs.span("checkpoint.read_part", bytes=nbytes) as sp:
            tbl = read()
            sp.set_attr("rows", tbl.num_rows)
            return tbl

    def _read_json_part(fstat) -> pa.Table:
        # V2 top-level checkpoint in JSON form
        return _read_part(lambda: pa_json.read_json(pa.BufferReader(
            engine.fs.read_file(fstat.path))), fstat.size)

    def _read_checkpoint_part(path: str):
        """The part's table. The small-action read asks for the small
        columns and, by `present_only`, for no more than the rows that
        hold one: the host handler then reads the part's footer, the row
        groups whose statistics admit a small action and, of each, the
        batches up to the last one the statistics count (a part without
        statistics is read whole, as before). The rows come numbered as
        handed back; within a checkpoint a row's position decides
        nothing. The hint's one limit (a small action with every leaf
        null) is `ParquetHandler.read_parquet_files`'s to state."""
        if not small_only:
            yield from engine.parquet.read_parquet_files([path])
            return
        try:
            yield from engine.parquet.read_parquet_files(
                [path], columns=list(SMALL_ACTION_COLUMNS),
                present_only=True)
        except (pa.ArrowException, KeyError, ValueError):
            # part lacks some small column (e.g. a multipart tail part
            # written by another engine): fall back to a full read
            yield from engine.parquet.read_parquet_files([path])

    # --- checkpoint parts (columnar already) ---
    cp_version = segment.checkpoint_version

    def _consume_parts_device(parts):
        """Device page-decode route: prefetched part BYTES feed the
        one-lane plan builder (one dispatch per part); an unsupported
        shape decodes the SAME bytes through Arrow — never re-fetched."""
        nonlocal bytes_parsed
        import pyarrow.parquet as pq

        from delta_tpu.log.page_decode import read_checkpoint_part_device
        from delta_tpu.replay.pipeline import prefetch_file_bytes
        from delta_tpu.resilience import device_faults

        byte_iter = prefetch_file_bytes(
            engine, [f.path for f in parts
                     if not f.path.endswith(".json")])
        for fstat in parts:
            try:
                if fstat.path.endswith(".json"):
                    _consume_checkpoint_table(_read_json_part(fstat))
                else:
                    data = next(byte_iter)
                    # a permanent error (a missing part file is one)
                    # leaves guarded() for the handler below
                    out = device_faults.guarded(
                        "decode",
                        lambda data=data: read_checkpoint_part_device(
                            data, want_keys=want_handoff),
                        _OBS_DECODE_FALLBACKS)
                    if out.value is not None:
                        _OBS_DECODE_PARTS.inc()
                        _consume_checkpoint_table(*out.value)
                    else:
                        if out.fell_back is None:
                            _OBS_DECODE_FALLBACKS.inc()
                            obs.gate_fell_back("decode", "host",
                                               reason="unsupported-shape")
                        with obs.gate_observation("decode", "host"):
                            tbl = _read_part(
                                lambda data=data: pq.read_table(
                                    pa.BufferReader(data)), fstat.size)
                        _consume_checkpoint_table(tbl)
            except FileNotFoundError:
                from delta_tpu.errors import LogCorruptedError

                raise LogCorruptedError(
                    f"couldn't find all part files of the checkpoint at "
                    f"version {cp_version}: {fstat.path} is missing",
                    error_class="DELTA_MISSING_PART_FILES")
            bytes_parsed += fstat.size

    def _consume_checkpoint_parts():
        nonlocal bytes_parsed
        parts = list(segment.checkpoints)
        # One routing decision per checkpoint read (the dispatch funnel
        # accumulates every part's cost onto it): raw part bytes over
        # the link vs the host Arrow decode rate.
        if not small_only and any(not f.path.endswith(".json")
                                  for f in parts):
            from delta_tpu.parallel import gate as _gate

            nbytes = sum(max(0, int(f.size)) for f in parts
                         if not f.path.endswith(".json"))
            if _gate.decode_route(
                    nbytes, getattr(engine, "use_device_decode",
                                    False)) == "device":
                _consume_parts_device(parts)
                return
        # Multipart/V2 parquet checkpoints: ONE batched handler call so
        # its byte-prefetch overlaps part i's decode with part i+1's
        # read. Consumption order is unchanged; the small_only
        # projection fallback keeps the per-part loop below.
        if (len(parts) > 1 and not small_only
                and all(not f.path.endswith(".json") for f in parts)):
            tables = engine.parquet.read_parquet_files(
                [f.path for f in parts])
            for fstat in parts:
                try:
                    # sidecar reads nest inside the consume call; a
                    # vanished sidecar maps like a vanished part
                    _consume_checkpoint_table(
                        _read_part(lambda: next(tables), fstat.size))
                except FileNotFoundError:
                    from delta_tpu.errors import LogCorruptedError

                    raise LogCorruptedError(
                        f"couldn't find all part files of the checkpoint "
                        f"at version {cp_version}: {fstat.path} is missing",
                        error_class="DELTA_MISSING_PART_FILES")
                bytes_parsed += fstat.size
            return
        for fstat in parts:
            try:
                if fstat.path.endswith(".json"):
                    _consume_checkpoint_table(_read_json_part(fstat))
                else:
                    _consume_checkpoint_table(_read_part(
                        lambda fstat=fstat: next(
                            _read_checkpoint_part(fstat.path)),
                        fstat.size))
            except FileNotFoundError:
                # selected as a complete checkpoint at LIST time, gone at
                # read time (`DeltaErrors.missingPartFilesException`)
                from delta_tpu.errors import LogCorruptedError

                raise LogCorruptedError(
                    f"couldn't find all part files of the checkpoint at "
                    f"version {cp_version}: {fstat.path} is missing",
                    error_class="DELTA_MISSING_PART_FILES")
            bytes_parsed += fstat.size

    native_keys = None
    native_pending = None
    native_stats_thunk = None

    if segment.checkpoints:
        try:
            with obs.span("log.read_checkpoint", version=cp_version,
                          parts=len(segment.checkpoints)):
                _consume_checkpoint_parts()
        except BaseException:
            # a torn/corrupt checkpoint aborts the load mid-accumulation
            # (the caller falls back to an older segment) — the decoded
            # code lanes must leave the resident ledger with it
            _abandon_handoff()
            raise
        if handoff["ok"] and handoff["parts"]:
            # checkpoint-only load with every part keyed on device:
            # launch the replay straight from the device-resident code
            # lanes — the device sorts while the host assembles Arrow
            from delta_tpu.ops.page_decode import (
                launch_checkpoint_handoff,
            )

            native_pending = launch_checkpoint_handoff(
                handoff["parts"], engine)

    # --- compacted deltas + commits: parallel read, one JSON parse ---
    from delta_tpu.utils import filenames as fn

    commit_infos: List[Tuple[int, str, int]] = []
    commit_stats: List[object] = []  # FileStatus aligned with commit_infos
    for fstat in segment.compacted_deltas:
        _, hi = fn.compacted_delta_versions(fstat.path)
        commit_infos.append((hi, fstat.path, fstat.size))
        commit_stats.append(fstat)
    for fstat in segment.deltas:
        commit_infos.append((fn.delta_version(fstat.path), fstat.path, fstat.size))
        commit_stats.append(fstat)

    checkpoint_blocks = list(blocks)
    if commit_infos:
        cache = parse_cache()
        file_keys = tuple(
            (f.path, f.size, f.modification_time) for f in commit_stats)
        span_parts: List[ParsedSpan] = (
            cache.get_covering(file_keys) if cache is not None else [])
        n_covered = sum(s.n_files for s in span_parts)
        remaining = commit_infos[n_covered:]
        fresh_pending = None
        if remaining:
            version_arr = np.array([v for v, _, _ in remaining],
                                   dtype=np.int64)
            from delta_tpu import native as _native

            total_listed = sum(max(0, int(s)) for _, _, s in remaining)
            if any(int(s) < 0 for _, _, s in remaining):
                # stat-deferred listing: estimate with a typical commit size
                total_listed = max(total_listed, 8192 * len(remaining))
            allow_compile = total_listed >= _native.MIN_BYTES_FOR_COLD_BUILD
            parsed_native = generic = read = None
            native_rejected = False

            # Early device dispatch: when the native block will be the sole
            # block (no checkpoint rows, no cached spans) on a
            # single-device engine, kick the replay kernel off as soon as
            # the scan's key lanes exist — the device sorts while the host
            # assembles the Arrow table.
            launch = None
            sole_fresh = not blocks and not span_parts
            if early_replay and sole_fresh and not small_only:
                def launch(scan, row_versions, row_orders):
                    from delta_tpu.ops.replay import replay_select_launch
                    from delta_tpu.parallel import gate
                    from delta_tpu.resilience import device_faults

                    # An early launch may only claim the replay when the
                    # plain single-chip kernel is what the gate picks
                    # (the host, sharded and blockwise kernels dispatch
                    # in compute_masks_device, which asks again).
                    if gate.replay_kernel(scan.n_rows, engine) != "single":
                        return None
                    if row_versions.max(initial=0) >= 2**31:
                        return None
                    # An overlap optimization: a transient failure
                    # here just forfeits the head start (None), and
                    # compute_masks_device makes the guarded attempt
                    # later, so no counter, no record, no host twin yet.
                    return device_faults.try_device(
                        "replay", lambda: replay_select_launch(
                            [scan.path_code,
                             np.zeros(scan.n_rows, np.uint32)],
                            row_versions.astype(np.int32), row_orders,
                            scan.is_add.astype(bool),
                            fa_hint=(scan.path_new, scan.refs,
                                     scan.n_uniq),
                        )).value
            # Pipelined load: when the tail is big enough to window,
            # overlap storage reads with parsing (and with the device
            # replay dispatch) instead of the phase-serial flow below.
            fresh = None
            if not small_only:
                from delta_tpu.replay import pipeline as _pipeline

                if _pipeline.enabled() and _pipeline.profitable(
                        engine, remaining,
                        _native.available(allow_compile)):
                    windows = _pipeline.plan_windows(
                        _pipeline.resolve_sizes(engine, remaining))
                    if len(windows) >= 2:
                        fresh, fresh_pending, pipe_nbytes = (
                            _pipeline.parse_commits_pipelined(
                                engine, windows,
                                allow_native=_native.available(
                                    allow_compile),
                                lazy_stats=True,
                                launch=launch,
                                allow_device=getattr(
                                    engine, "use_device_parse", False)))
                        bytes_parsed += pipe_nbytes
            if fresh is None:
                # Device JSON parse: gated by the engine's accelerator
                # opt-in + link economics (or DELTA_TPU_DEVICE_PARSE).
                # On fallback the buffer it read is REUSED by the host
                # branches below — never fetched twice.
                from delta_tpu.parallel import gate as _gate

                if _gate.parse_route(
                        total_listed,
                        getattr(engine, "use_device_parse",
                                False)) == "device":
                    from delta_tpu.replay import device_parse as _dp
                    from delta_tpu.resilience import device_faults

                    # the buffer (if read) is reused by the host branches
                    # below, priced against the "device" prediction
                    read = _read_commits_buffer(engine, remaining)
                    if read is None:
                        obs.gate_fell_back("parse", "host",
                                           reason="read-failed")
                    else:
                        buf, starts, version_arr = read
                        out = device_faults.guarded(
                            "parse",
                            lambda: _dp.parse_commits_device(
                                buf, starts, version_arr,
                                small_only=small_only,
                                lazy_stats=not small_only),
                            _OBS_PARSE_FALLBACKS)
                        parsed_native = out.value
                        if parsed_native is not None:
                            bytes_parsed += int(starts[-1])
                        elif out.fell_back is None:
                            obs.gate_fell_back(
                                "parse", "host",
                                reason="device-parse-unavailable")
            if (fresh is None and parsed_native is None and read is None
                    and _native.available(allow_compile)):
                # local files: one native read+scan round-trip (no per-file
                # interpreter I/O, no buffer copy into Python)
                local = [engine.fs.os_path(p) for _, p, _ in remaining]
                if all(p is not None for p in local):
                    from delta_tpu.replay.native_parse import (
                        parse_commit_paths_native,
                    )

                    out = parse_commit_paths_native(
                        local, version_arr, small_only=small_only,
                        launch=launch,
                        # stats decode defers only when a deferred column
                        # can later be assembled: the combined stats thunk
                        # spans blocks, so any non-small parse may defer
                        lazy_stats=not small_only)
                    if out is not None:
                        block, others, keys, pending, sthunk, total = out
                        parsed_native = (block, others, keys, pending, sthunk)
                        bytes_parsed += total
                    else:
                        # the scanner saw (and rejected) this exact content —
                        # don't scan the same bytes natively a second time
                        native_rejected = True
            if fresh is None and parsed_native is None:
                # one parallel read into one buffer; the native C++ scanner
                # and the generic Arrow parser are alternative consumers of
                # the SAME bytes — a native-side rejection never re-fetches
                # (and a device-route fallback above already supplied them)
                if read is None:
                    read = _read_commits_buffer(engine, remaining)
                if read is not None:
                    buf, starts, version_arr = read
                    if not native_rejected and _native.available(allow_compile):
                        from delta_tpu.replay.native_parse import (
                            parse_commits_native,
                        )

                        parsed_native = parse_commits_native(
                            buf, starts, version_arr, small_only=small_only,
                            launch=launch)
                        if parsed_native is not None:
                            bytes_parsed += int(starts[-1])
                    if parsed_native is None:
                        generic = _parse_buffer_generic(buf, starts, version_arr)
            if parsed_native is not None:
                block, others, keys, pending, sthunk = parsed_native
                fresh_pending = pending
                fresh = ParsedSpan(
                    block=block, others=others, keys=keys,
                    stats_thunk=_OnceThunk(sthunk) if sthunk is not None
                    else None,
                    n_files=len(remaining),
                    nbytes=_span_nbytes(block, others))
            elif fresh is None:
                if generic is None:  # size mismatch or accounting failure
                    from delta_tpu.utils.threads import parallel_map

                    blobs = parallel_map(
                        lambda vp: (vp[0], engine.fs.read_file(vp[1])),
                        [(v, p) for v, p, _ in remaining])
                    generic = parse_commit_batch(blobs)
                tbl, versions, orders, nbytes = generic
                bytes_parsed += nbytes
                gen_blocks: List[pa.Table] = []
                small_rows: List[Tuple[int, int, dict]] = []
                if tbl is not None:
                    if small_only:
                        tracker.scan_chunk(tbl, versions, orders)
                    else:
                        small_rows = _extract_small_rows(tbl, versions,
                                                         orders)
                        for col in ("add", "remove"):
                            b = _extract_file_actions(tbl, col, versions,
                                                      orders)
                            if b is not None:
                                gen_blocks.append(b)
                fresh = None
                if not small_only:
                    gb = (pa.concat_tables(gen_blocks) if gen_blocks
                          else CANONICAL_FILE_ACTION_SCHEMA.empty_table())
                    fresh = ParsedSpan(
                        block=gb, others=small_rows, keys=None,
                        stats_thunk=None, n_files=len(remaining),
                        nbytes=_span_nbytes(gb, small_rows))
            if fresh is not None:
                span_parts.append(fresh)
                # never cache a small_only parse — its span has no file
                # actions and would poison later full loads
                if cache is not None and not small_only:
                    cache.put(file_keys[n_covered:], fresh)
        for part in span_parts:
            tracker.scan_pylist(part.others)
            if not small_only and part.block.num_rows:
                blocks.append(part.block)
        if not small_only:
            if not checkpoint_blocks and len(span_parts) == 1:
                # sole file-action source: the span's replay-key sidecar
                # (and any in-flight device dispatch) are row-aligned
                # with the final table
                native_keys = span_parts[0].keys
                native_pending = fresh_pending
            native_stats_thunk = _combined_stats_thunk(
                [(b, None) for b in checkpoint_blocks]
                + [(p.block, p.stats_thunk) for p in span_parts
                   if p.block.num_rows])

    if blocks:
        file_actions = pa.concat_tables(blocks)
    else:
        file_actions = CANONICAL_FILE_ACTION_SCHEMA.empty_table()

    latest_ci = None
    if tracker.commit_infos:
        latest_ci = tracker.commit_infos[max(tracker.commit_infos)]

    return ColumnarActions(
        file_actions=file_actions,
        protocol=tracker.protocol[2],
        metadata=tracker.metadata[2],
        set_transactions={k: t[2] for k, t in tracker.txns.items()},
        domain_metadata={k: t[2] for k, t in tracker.domains.items()},
        latest_commit_info=latest_ci,
        commit_infos=tracker.commit_infos,
        num_commit_files=len(commit_infos),
        pending_masks=native_pending,
        stats_thunk=native_stats_thunk,
        bytes_parsed=bytes_parsed,
        replay_keys=native_keys,
    )


def columnarize_commit_blobs(
    commit_blobs: Sequence[Tuple[int, bytes]],
) -> ColumnarActions:
    """In-memory commits → ColumnarActions, no filesystem access. The
    post-commit fast path feeds the bytes a transaction just wrote
    straight into snapshot advancement — the commit it authored is never
    re-listed or re-read (`SnapshotManagement.updateAfterCommit`)."""
    tracker = _SmallActionTracker()
    tbl, versions, orders, nbytes = parse_commit_batch(commit_blobs)
    blocks: List[pa.Table] = []
    if tbl is not None:
        tracker.scan_chunk(tbl, versions, orders)
        for col in ("add", "remove"):
            b = _extract_file_actions(tbl, col, versions, orders)
            if b is not None:
                blocks.append(b)
    fa = (pa.concat_tables(blocks) if blocks
          else CANONICAL_FILE_ACTION_SCHEMA.empty_table())
    latest_ci = None
    if tracker.commit_infos:
        latest_ci = tracker.commit_infos[max(tracker.commit_infos)]
    return ColumnarActions(
        file_actions=fa,
        protocol=tracker.protocol[2],
        metadata=tracker.metadata[2],
        set_transactions={k: t[2] for k, t in tracker.txns.items()},
        domain_metadata={k: t[2] for k, t in tracker.domains.items()},
        latest_commit_info=latest_ci,
        commit_infos=tracker.commit_infos,
        num_commit_files=len(commit_blobs),
        bytes_parsed=nbytes,
    )
