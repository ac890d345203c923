"""Checkpoint writing (classic single-file, multi-part, V2+sidecars).

Reference: spark `Checkpoints.scala:616` writeCheckpoint, kernel
`CreateCheckpointIterator` → `ParquetHandler.writeParquetFileAtomically`.

A checkpoint materializes the reconciled state at a version as Parquet in
the SingleAction layout: struct columns `protocol`, `metaData`, `txn`,
`domainMetadata`, `add`, `remove` — one non-null per row. Contents:
- 1 protocol + 1 metaData row,
- one `txn` row per appId, one `domainMetadata` row per domain
  (including removal tombstones),
- every live `add` (dataChange=false),
- every `remove` tombstone younger than the retention window
  (`delta.deletedFileRetentionDuration`), dataChange=false.

The add/remove struct columns are assembled directly from the snapshot's
canonical columnar state — no per-row object hop. Finishes by pointing
`_last_checkpoint` at the new checkpoint.

Multi-artifact checkpoints (multipart parts, V2 sidecars) go through
`delta_tpu.write.ckpt_pipeline`: per-artifact serialize and upload are
split so encode(part i+1) overlaps upload(part i) on remote stores,
and any failure settles the in-flight tail, deletes every artifact
this attempt created, bumps `checkpoint.aborted_writes`, and re-raises
WITHOUT advancing `_last_checkpoint` — a torn multipart write can
never become the active checkpoint.

Incremental checkpoints: each file-action part is content-fingerprinted
(`_part_fp`) and the fingerprints ride the `_last_checkpoint` hint as
`partManifest`. The next write reuses fingerprint-matched parts —
byte-copied under the new filename for multipart (old parts are
cleanup-eligible once shadowed), re-referenced in place for V2
sidecars (log cleanup never deletes `_sidecars/`). Append-only
workloads rewrite only the tail part.
"""

from __future__ import annotations

import hashlib
import json
import time
import uuid
from typing import Callable, Dict, List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.config import (
    CHECKPOINT_POLICY,
    TOMBSTONE_RETENTION,
    get_table_config,
    settings,
)
from delta_tpu import obs
from delta_tpu.errors import ChecksumMismatchError, InvalidArgumentError
from delta_tpu.log import parquet_stitch
from delta_tpu.log.last_checkpoint import LastCheckpointInfo, write_last_checkpoint
from delta_tpu.models.actions import Sidecar
from delta_tpu.replay.columnar import DV_STRUCT_TYPE
from delta_tpu.utils import filenames
from delta_tpu.write import ckpt_pipeline

_BYTES_WRITTEN = obs.counter("checkpoint.bytes_written")
_PARTS_WRITTEN = obs.counter("checkpoint.parts_written")
_PARTS_REUSED = obs.counter("checkpoint.parts_reused")
_ABORTED_WRITES = obs.counter("checkpoint.aborted_writes")
_ENCODES_DEALT = obs.counter("checkpoint.encodes_dealt")
_ENCODES_SERIAL = obs.counter("checkpoint.encodes_serial")

PV_MAP = pa.map_(pa.string(), pa.string())

ADD_STRUCT = pa.struct(
    [
        pa.field("path", pa.string()),
        pa.field("partitionValues", PV_MAP),
        pa.field("size", pa.int64()),
        pa.field("modificationTime", pa.int64()),
        pa.field("dataChange", pa.bool_()),
        pa.field("stats", pa.string()),
        pa.field("deletionVector", DV_STRUCT_TYPE),
        pa.field("baseRowId", pa.int64()),
        pa.field("defaultRowCommitVersion", pa.int64()),
        pa.field("clusteringProvider", pa.string()),
    ]
)

REMOVE_STRUCT = pa.struct(
    [
        pa.field("path", pa.string()),
        pa.field("deletionTimestamp", pa.int64()),
        pa.field("dataChange", pa.bool_()),
        pa.field("extendedFileMetadata", pa.bool_()),
        pa.field("partitionValues", PV_MAP),
        pa.field("size", pa.int64()),
        pa.field("deletionVector", DV_STRUCT_TYPE),
        pa.field("baseRowId", pa.int64()),
        pa.field("defaultRowCommitVersion", pa.int64()),
    ]
)

PROTOCOL_STRUCT = pa.struct(
    [
        pa.field("minReaderVersion", pa.int32()),
        pa.field("minWriterVersion", pa.int32()),
        pa.field("readerFeatures", pa.list_(pa.string())),
        pa.field("writerFeatures", pa.list_(pa.string())),
    ]
)

METADATA_STRUCT = pa.struct(
    [
        pa.field("id", pa.string()),
        pa.field("name", pa.string()),
        pa.field("description", pa.string()),
        pa.field(
            "format",
            pa.struct(
                [pa.field("provider", pa.string()), pa.field("options", PV_MAP)]
            ),
        ),
        pa.field("schemaString", pa.string()),
        pa.field("partitionColumns", pa.list_(pa.string())),
        pa.field("configuration", PV_MAP),
        pa.field("createdTime", pa.int64()),
    ]
)

TXN_STRUCT = pa.struct(
    [
        pa.field("appId", pa.string()),
        pa.field("version", pa.int64()),
        pa.field("lastUpdated", pa.int64()),
    ]
)

DOMAIN_STRUCT = pa.struct(
    [
        pa.field("domain", pa.string()),
        pa.field("configuration", pa.string()),
        pa.field("removed", pa.bool_()),
    ]
)


def _stats_parsed_schema(schema, configuration,
                         partition_columns) -> Optional[pa.Schema]:
    """Explicit arrow schema for stats_parsed, typed per the TABLE
    schema (external struct-form readers expect e.g. timestamp mins as
    timestamps, not inferred strings): numRecords int64, minValues /
    maxValues as nested structs of the indexed leaves' arrow types,
    nullCount as int64 per leaf."""
    from delta_tpu.models.schema import PrimitiveType, StructType, to_arrow_type
    from delta_tpu.stats.collection import stats_columns

    if schema is None:
        return None

    def resolve(path):
        node = schema
        for name in path[:-1]:
            if not isinstance(node, StructType) or name not in node:
                return None
            node = node[name].dataType
        if not isinstance(node, StructType) or path[-1] not in node:
            return None
        return node[path[-1]].dataType

    minmax_tree: dict = {}
    null_tree: dict = {}

    def insert(tree, path, typ):
        for p in path[:-1]:
            tree = tree.setdefault(p, {})
        tree[path[-1]] = typ

    for path in stats_columns(schema, configuration, partition_columns):
        dt = resolve(path)
        if not isinstance(dt, PrimitiveType):
            continue
        try:
            arrow_t = to_arrow_type(dt)
        except (ValueError, InvalidArgumentError):
            continue  # unmappable type: no stats column for it
        insert(null_tree, path, pa.int64())
        if dt.name != "binary":
            insert(minmax_tree, path, arrow_t)

    def to_struct(tree) -> pa.DataType:
        return pa.struct([
            pa.field(k, to_struct(v) if isinstance(v, dict) else v)
            for k, v in tree.items()
        ])

    fields = [pa.field("numRecords", pa.int64())]
    if minmax_tree:
        fields.append(pa.field("minValues", to_struct(minmax_tree)))
        fields.append(pa.field("maxValues", to_struct(minmax_tree)))
    if null_tree:
        fields.append(pa.field("nullCount", to_struct(null_tree)))
    # DV-capable writers mark whether min/max reflect the post-delete
    # rows; without this field in the explicit schema a struct-only
    # checkpoint round-trip would silently drop it
    fields.append(pa.field("tightBounds", pa.bool_()))
    return pa.schema(fields)


def _stats_ndjson_buffer(stats_col: pa.Array) -> Optional[pa.Buffer]:
    """The stats strings as one newline-delimited buffer, built with
    Arrow kernels (no per-row Python objects — this runs at
    checkpoint-write scale)."""
    import pyarrow.compute as _pc

    filled = _pc.fill_null(stats_col, "{}")
    # append "\n" per row: the LAST argument is the separator, so join
    # (value, "") with separator "\n" — value + "\n" + ""
    with_nl = _pc.binary_join_element_wise(filled, pa.scalar(""),
                                           pa.scalar("\n"))
    arr = (with_nl.combine_chunks()
           if isinstance(with_nl, pa.ChunkedArray) else with_nl)
    if arr.offset != 0:
        arr = pa.concat_arrays([arr])  # re-materialize at offset 0
    offsets_buf = arr.buffers()[1]
    width = 8 if pa.types.is_large_string(arr.type) else 4
    dtype = np.int64 if width == 8 else np.int32
    offsets = np.frombuffer(offsets_buf, dtype=dtype, count=len(arr) + 1)
    total = int(offsets[-1])
    return arr.buffers()[2].slice(0, total)


def _parse_stats_structs(
    stats_col: pa.Array, explicit_schema: Optional[pa.Schema] = None
) -> Optional[pa.Array]:
    """Parse per-file stats JSON strings into a struct array, typed by
    `explicit_schema` when given (falling back to inference if the
    explicit parse fails — e.g. 'NaN' strings in double stats). Null
    stats become empty objects (all-null fields). None when nothing
    parses."""
    import pyarrow.json as pa_json

    if stats_col.null_count == len(stats_col):
        return None
    buf = _stats_ndjson_buffer(stats_col)
    if buf is None:
        return None
    parsed = None
    if explicit_schema is not None:
        try:
            parsed = pa_json.read_json(
                pa.BufferReader(buf),
                parse_options=pa_json.ParseOptions(
                    explicit_schema=explicit_schema,
                    unexpected_field_behavior="ignore"))
        except (pa.ArrowException, ValueError, OSError):
            parsed = None  # schema mismatch: retry with inference below
    if parsed is None:
        try:
            parsed = pa_json.read_json(pa.BufferReader(buf))
        except (pa.ArrowException, ValueError, OSError):
            return None  # malformed stats: skip the struct form entirely
    if parsed.num_rows != len(stats_col):
        return None
    return parsed.to_struct_array().combine_chunks()


# What a checkpoint reads of a live row, of the canonical schema's
# seventeen columns: `_file_struct_from_canonical` unpacks an add row
# from exactly these, in this order (`dataChange` is written as a
# column of `false`), `_checkpoint_aggregates` reads four of them, and
# `_write_checkpoint` asks the state for no others.
ADD_COLUMNS = (
    "path", "partition_values", "size", "modification_time", "stats",
    "deletion_vector", "base_row_id", "default_row_commit_version",
    "clustering_provider",
)


def _file_struct_from_canonical(
    tbl: pa.Table,
    is_add: bool,
    stats_as_json: bool = True,
    stats_as_struct: bool = False,
    stats_schema: Optional[pa.Schema] = None,
) -> pa.Array:
    """Canonical columnar rows → add/remove StructArray. Stats shaping
    per `delta.checkpoint.writeStatsAsJson` / `writeStatsAsStruct`
    (`Checkpoints.scala` buildCheckpoint)."""
    n = tbl.num_rows
    false_col = pa.array(np.zeros(n, dtype=bool))

    def col(name):
        # `combine_chunks` copies even a column of one chunk, which is
        # how `live_columns` hands a large state's over
        column = tbl.column(name)
        return (column.chunk(0) if column.num_chunks == 1
                else column.combine_chunks())

    if is_add:
        (path, partition_values, size, modification_time, stats,
         deletion_vector, base_row_id, default_row_commit_version,
         clustering_provider) = map(col, ADD_COLUMNS)
        fields = list(ADD_STRUCT)
        children = [
            path,
            partition_values,
            size,
            modification_time,
            false_col,  # dataChange normalized to false in checkpoints
            stats if stats_as_json else pa.nulls(n, pa.string()),
            deletion_vector,
            base_row_id,
            default_row_commit_version,
            clustering_provider,
        ]
        if stats_as_struct:
            parsed = _parse_stats_structs(stats, stats_schema)
            if parsed is not None:
                children.append(parsed)
                fields = fields + [pa.field("stats_parsed", parsed.type)]
        return pa.StructArray.from_arrays(children, fields=fields)
    children = [
        col("path"),
        col("deletion_timestamp"),
        false_col,
        col("extended_file_metadata"),
        col("partition_values"),
        col("size"),
        col("deletion_vector"),
        col("base_row_id"),
        col("default_row_commit_version"),
    ]
    return pa.StructArray.from_arrays(children, fields=list(REMOVE_STRUCT))


def _single_action_table(
    n: int,
    protocol_rows: Optional[pa.Array] = None,
    metadata_rows: Optional[pa.Array] = None,
    txn_rows: Optional[pa.Array] = None,
    domain_rows: Optional[pa.Array] = None,
    add_rows: Optional[pa.Array] = None,
    remove_rows: Optional[pa.Array] = None,
) -> pa.Table:
    """Assemble a SingleAction table: each input occupies its own row
    range; all other columns null there."""
    with obs.span("checkpoint.table", rows=n):
        blocks = [
            ("protocol", PROTOCOL_STRUCT, protocol_rows),
            ("metaData", METADATA_STRUCT, metadata_rows),
            ("txn", TXN_STRUCT, txn_rows),
            ("domainMetadata", DOMAIN_STRUCT, domain_rows),
            ("add", ADD_STRUCT, add_rows),
            ("remove", REMOVE_STRUCT, remove_rows),
        ]
        sizes = [len(b[2]) if b[2] is not None else 0 for b in blocks]
        total = sum(sizes)
        assert total == n, (total, n)
        # chunked columns, not concat_arrays: the null spans and the payload
        # arrays become chunks as-is, so a million-file checkpoint table is
        # assembled without copying a single struct row
        cols = {}
        offset = 0
        offsets = []
        for (name, typ, arr), sz in zip(blocks, sizes):
            offsets.append(offset)
            offset += sz
        for i, (name, typ, arr) in enumerate(blocks):
            sz = sizes[i]
            # honor the payload's actual type when present — the add struct
            # may carry an extra stats_parsed field beyond the static schema
            if arr is not None and sz:
                typ = arr.type
            before, after = offsets[i], n - offsets[i] - sz
            chunks = []
            if before:
                chunks.append(pa.nulls(before, typ))
            if arr is not None and sz:
                chunks.append(arr)
            if after:
                chunks.append(pa.nulls(after, typ))
            cols[name] = (pa.chunked_array(chunks, type=typ) if chunks
                          else pa.chunked_array([], type=typ))
        return pa.table(cols)


def _small_action_arrays(state, txn_min_last_updated: Optional[int] = None) -> tuple:
    proto = state.protocol
    protocol_rows = pa.array(
        [
            {
                "minReaderVersion": proto.minReaderVersion,
                "minWriterVersion": proto.minWriterVersion,
                "readerFeatures": (
                    sorted(proto.readerFeatures) if proto.readerFeatures is not None else None
                ),
                "writerFeatures": (
                    sorted(proto.writerFeatures) if proto.writerFeatures is not None else None
                ),
            }
        ],
        PROTOCOL_STRUCT,
    )
    meta = state.metadata
    metadata_rows = pa.array(
        [
            {
                "id": meta.id,
                "name": meta.name,
                "description": meta.description,
                "format": {"provider": meta.format.provider, "options": list(meta.format.options.items())},
                "schemaString": meta.schemaString,
                "partitionColumns": list(meta.partitionColumns),
                "configuration": list(meta.configuration.items()),
                "createdTime": meta.createdTime,
            }
        ],
        METADATA_STRUCT,
    )
    txns = list(state.set_transactions.values())
    if txn_min_last_updated is not None:
        # expire idle SetTransaction entries from the checkpoint
        # (`InMemoryLogReplay.scala:84-91`: lastUpdated.exists(_ > min) —
        # entries without a timestamp are dropped once retention is on)
        txns = [t for t in txns
                if t.lastUpdated is not None
                and t.lastUpdated >= txn_min_last_updated]
    txn_rows = (
        pa.array(
            [
                {"appId": t.appId, "version": t.version, "lastUpdated": t.lastUpdated}
                for t in txns
            ],
            TXN_STRUCT,
        )
        if txns
        else None
    )
    domain_rows = (
        pa.array(
            [
                {"domain": d.domain, "configuration": d.configuration, "removed": d.removed}
                for d in state.domain_metadata.values()
            ],
            DOMAIN_STRUCT,
        )
        if state.domain_metadata
        else None
    )
    return protocol_rows, metadata_rows, txn_rows, domain_rows


def _retained_tombstones(state, now_ms: int, retention_ms: int) -> pa.Table:
    tombs = state.tombstones_table
    if tombs.num_rows == 0:
        return tombs
    min_retain = now_ms - retention_ms
    del_ts = pc.fill_null(tombs.column("deletion_timestamp"), 0)
    keep = pc.greater_equal(del_ts, pa.scalar(min_retain, pa.int64()))
    return tombs.filter(keep)


def _partition_codes(state, adds: pa.Table) -> tuple:
    """Dictionary-code each add row's partition-value tuple.
    Unpartitioned tables (the common case) take the zero-work
    single-code path; partitioned tables code the tuples on host — the
    expensive per-part distinct-count then reduces with the other
    lanes in the one batched dispatch."""
    n = adds.num_rows
    if not list(state.metadata.partitionColumns or []):
        return np.zeros(n, np.int64), 1
    codebook: dict = {}
    codes = np.empty(n, np.int64)
    for i, kv in enumerate(adds.column("partition_values").to_pylist()):
        key = tuple(kv) if kv is not None else ()
        codes[i] = codebook.setdefault(key, len(codebook))
    return codes, max(len(codebook), 1)


def _checkpoint_aggregates(engine, state, adds: pa.Table, plan) -> None:
    """Stats summary for the checkpoint being written: per-part
    min/max/sum/null-count over the add lanes (file size, modification
    time, DV cardinality) plus distinct partition values. On an engine
    with an accelerator backend (`device_stats_enabled`) the whole
    stage is ONE batched device dispatch returning one dense D2H block
    (`ops/stats.py`, budgeted in transfer_budget.json), colocated with
    the resident replay state's device when one exists; otherwise the
    bit-identical host twin runs. The block feeds the
    `checkpoint.aggregate` span — it is deliberately NOT part of the
    reuse fingerprint, so stat-mode flips can never change checkpoint
    bytes."""
    from delta_tpu.ops import stats as ckstats

    n = adds.num_rows
    n_parts = len(plan)
    with obs.span("checkpoint.aggregate", rows=n, parts=n_parts) as sp:

        def lane(col) -> tuple:
            arr = (col.combine_chunks()
                   if isinstance(col, pa.ChunkedArray) else col)
            vals = pc.fill_null(arr, 0).to_numpy(
                zero_copy_only=False).astype(np.int64, copy=False)
            ok = pc.is_valid(arr).to_numpy(zero_copy_only=False)
            return vals, ok

        size_v, size_ok = lane(adds.column("size"))
        mt_v, mt_ok = lane(adds.column("modification_time"))
        dv_v, dv_ok = lane(pc.struct_field(
            adds.column("deletion_vector").combine_chunks(), "cardinality"))
        codes, n_codes = _partition_codes(state, adds)
        lanes = [size_v, mt_v, dv_v, codes]
        valids = [size_ok, mt_ok, dv_ok, np.ones(n, bool)]
        part_of = np.zeros(n, np.int32)
        for i, (a0, a1, _r0, _r1) in enumerate(plan):
            part_of[a0:a1] = i
        mode, device_error = "host", None
        if ckstats.device_stats_enabled(engine):
            resident = getattr(state, "resident", None)
            hint = resident.device_hint() if resident is not None else None
            try:
                block = ckstats.checkpoint_stats_block(
                    lanes, valids, part_of, n_parts, n_codes, device=hint)
                mode = "device"
            # delta-lint: disable=except-swallow (audited: the aggregate
            # block is telemetry riding the checkpoint write — a device
            # dispatch failure must degrade to the bit-identical host
            # twin, never abort the checkpoint)
            except Exception as e:
                device_error = type(e).__name__
                block = ckstats.host_stats_block(
                    lanes, valids, part_of, n_parts, n_codes)
        else:
            block = ckstats.host_stats_block(
                lanes, valids, part_of, n_parts, n_codes)
        n_l = len(lanes)
        sp.set_attrs(
            stats_mode=mode,
            logical_bytes=int(block[2 * n_l].sum()),
            dv_cardinality=int(block[2 * n_l + 2].sum()),
            distinct_partition_values=int(block[4 * n_l].max(initial=0)),
            # the whole block over the parts, a value a lane in the
            # order of `lanes`: what a reader of the span can hold
            # against the table (an empty lane reads the identities)
            lanes="size,modification_time,dv_cardinality,partition_code",
            lane_min=block[0:n_l].min(axis=1).tolist(),
            lane_max=block[n_l:2 * n_l].max(axis=1).tolist(),
            lane_sum=block[2 * n_l:3 * n_l].sum(axis=1).tolist(),
            lane_nulls=block[3 * n_l:4 * n_l].sum(axis=1).tolist(),
        )
        if device_error is not None:
            sp.set_attr("device_error", device_error)


def write_checkpoint(engine, snapshot, policy: Optional[str] = None,
                     prev_info: Optional[LastCheckpointInfo] = None,
                     ) -> LastCheckpointInfo:
    """Write a checkpoint for `snapshot` and update `_last_checkpoint`.

    `prev_info` is the previous `_last_checkpoint` hint; when it carries
    a `partManifest` from an identically-configured writer, unchanged
    parts/sidecars are reused instead of re-serialized."""
    with obs.span("checkpoint.write", log_path=snapshot._table.log_path,
                  version=snapshot.version) as sp:
        info, route, parts = _write_checkpoint(engine, snapshot, policy,
                                               prev_info)
        # `parts`: the files of file actions: the one classic file,
        # multipart's chunks (its part 1, the small actions, beside
        # them), V2's sidecars
        sp.set_attrs(actions=info.size, num_add_files=info.numOfAddFiles,
                     size_bytes=info.sizeInBytes, route=route, parts=parts)
        return info


def _write_checkpoint(engine, snapshot, policy: Optional[str],
                      prev_info: Optional[LastCheckpointInfo] = None,
                      ) -> tuple:
    """-> (the hint written, the route taken, its files of file
    actions)."""
    with obs.span("checkpoint.assemble") as sp:
        state = snapshot.state
        meta_conf = state.metadata.configuration
        if policy is None:
            policy = get_table_config(meta_conf, CHECKPOINT_POLICY)
        now_ms = int(time.time() * 1000)
        retention = get_table_config(meta_conf, TOMBSTONE_RETENTION)
        from delta_tpu.config import (
            CHECKPOINT_WRITE_STATS_AS_JSON,
            CHECKPOINT_WRITE_STATS_AS_STRUCT,
            SET_TXN_RETENTION,
        )

        stats_as_json = get_table_config(
            meta_conf, CHECKPOINT_WRITE_STATS_AS_JSON)
        stats_as_struct = get_table_config(
            meta_conf, CHECKPOINT_WRITE_STATS_AS_STRUCT)
        txn_retention = get_table_config(meta_conf, SET_TXN_RETENTION)
        txn_min = ((now_ms - txn_retention) if txn_retention is not None
                   else None)

        # the live rows of the columns read, and no live table: a
        # large state's are filtered on the scan pool, which this
        # thread waits for here as it does in `checkpoint.serialize`
        adds = state.live_columns(list(ADD_COLUMNS))
        tombs = _retained_tombstones(state, now_ms, retention)
        stats_schema = (_stats_parsed_schema(
            state.metadata.schema, meta_conf,
            list(state.metadata.partitionColumns or []))
            if stats_as_struct else None)
        add_struct = _file_struct_from_canonical(
            adds, is_add=True,
            stats_as_json=stats_as_json, stats_as_struct=stats_as_struct,
            stats_schema=stats_schema)
        remove_struct = _file_struct_from_canonical(tombs, is_add=False)
        protocol_rows, metadata_rows, txn_rows, domain_rows = (
            _small_action_arrays(state, txn_min_last_updated=txn_min))
        sp.set_attrs(adds=len(add_struct), removes=len(remove_struct),
                     stats_as_struct=bool(stats_as_struct))

    if settings.verify_checkpoint_row_count and len(add_struct) != state.num_files:
        raise ChecksumMismatchError(
            error_class="DELTA_CHECKPOINT_SNAPSHOT_MISMATCH",
            message=f"checkpoint add rows {len(add_struct)} != snapshot numFiles "
            f"{state.num_files}"
        )

    log_path = snapshot._table.log_path
    version = snapshot.version
    part_size = settings.checkpoint_part_size
    n_files = len(add_struct) + len(remove_struct)

    if policy == "v2":
        route = "v2"
        plan = _chunk_plan(len(add_struct), len(remove_struct),
                           part_size or max(n_files, 1))
    elif part_size is not None and n_files > part_size:
        route = "multipart"
        plan = _chunk_plan(len(add_struct), len(remove_struct), part_size)
    else:
        route = "classic"
        plan = [(0, len(add_struct), 0, len(remove_struct))]

    _checkpoint_aggregates(engine, state, adds, plan)
    writer_fp = _writer_fp(policy, part_size, stats_as_json,
                           stats_as_struct, state.metadata.schemaString)
    prev_parts = (_prev_part_index(prev_info, writer_fp)
                  if route != "classic" else {})

    try:
        if route == "v2":
            info = _write_v2_checkpoint(
                engine, log_path, version, add_struct, remove_struct,
                protocol_rows, metadata_rows, txn_rows, domain_rows,
                plan, writer_fp, prev_parts,
            )
        elif route == "multipart":
            info = _write_multipart_checkpoint(
                engine, log_path, version, add_struct, remove_struct,
                protocol_rows, metadata_rows, txn_rows, domain_rows,
                plan, writer_fp, prev_parts,
            )
        else:
            n = (
                len(protocol_rows) + len(metadata_rows)
                + (len(txn_rows) if txn_rows is not None else 0)
                + (len(domain_rows) if domain_rows is not None else 0)
                + len(add_struct) + len(remove_struct)
            )
            table = _single_action_table(
                n, protocol_rows, metadata_rows, txn_rows, domain_rows,
                add_struct, remove_struct,
            )
            path = filenames.checkpoint_file_singular(log_path, version)
            # same funnel as multipart/V2: put-if-absent with the
            # torn-collision wholeness check, CheckpointWriteError on
            # failure, and bytes/parts accounting
            results = ckpt_pipeline.run_write_tasks(
                engine,
                [ckpt_pipeline.WriteTask(
                    path, lambda: _encode_parquet(table),
                    overwrite=False, label="classic")],
                pipelined=False)
            _count_written(results)
            info = LastCheckpointInfo(
                version=version,
                size=n,
                numOfAddFiles=len(add_struct),
            )
    except ckpt_pipeline.CheckpointWriteError as e:
        # torn checkpoint: delete everything this attempt materialized
        # and leave `_last_checkpoint` pointing at the previous (still
        # complete) checkpoint — readers never see a partial part set
        _ABORTED_WRITES.inc()
        _cleanup_orphans(engine, e.touched_paths)
        raise
    with obs.span("checkpoint.hint", version=version):
        if route == "classic":
            info.sizeInBytes = _file_size(engine, path)
        write_last_checkpoint(engine.json, log_path, info)
    return info, route, len(plan)


def _file_size(engine, path: str) -> Optional[int]:
    try:
        return engine.fs.file_status(path).size
    except OSError:
        return None


def _chunk_plan(n_add: int, n_rem: int, part_size: int) -> List[tuple]:
    """FIXED `part_size`-row chunks over the concatenated [adds;
    removes] file-action row space → [(a0, a1, r0, r1)] per part.

    Fixed chunks (not an even split) are what makes incremental reuse
    work: append-only commits add rows at the END of the canonical
    state, so every full earlier chunk covers the same rows as last
    time and its fingerprint — and therefore its bytes — are unchanged.
    An even split would shift every boundary on each append and
    invalidate all parts."""
    total = n_add + n_rem
    out = []
    lo = 0
    while lo < total:
        hi = min(lo + part_size, total)
        out.append((min(lo, n_add), min(hi, n_add),
                    max(lo, n_add) - n_add, max(hi, n_add) - n_add))
        lo = hi
    return out or [(0, 0, 0, 0)]


def _writer_fp(policy, part_size, stats_as_json, stats_as_struct,
               schema_string) -> str:
    """Fingerprint of everything that shapes part bytes besides the rows
    themselves. A part is only reusable when the writer that produced it
    had an identical config — chunk boundaries (part_size), stats
    shaping, the table schema (drives stats_parsed typing), and the
    layout revision of this module."""
    blob = json.dumps(
        {
            "layout": 1,
            "policy": policy,
            "partSize": part_size,
            "statsAsJson": bool(stats_as_json),
            "statsAsStruct": bool(stats_as_struct),
            "schema": hashlib.sha1(
                (schema_string or "").encode()).hexdigest(),
        },
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def _part_fp(writer_fp: str, adds_i: pa.Array, rems_i: pa.Array) -> str:
    """Content fingerprint of one part's file-action rows: sha1 over the
    Arrow IPC bytes of the slices, re-materialized at offset 0 first
    (`pa.concat_arrays`) — a plain slice's IPC stream leaks its parent's
    buffer truncation and absolute offset, so only the rebased form is
    byte-stable across snapshots. Equal fingerprints ⇒ identical rows ⇒
    the previous checkpoint's part bytes are valid for this part."""
    h = hashlib.sha1(writer_fp.encode())
    for name, arr in (("add", adds_i), ("remove", rems_i)):
        batch = pa.record_batch({name: pa.concat_arrays([arr])})
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, batch.schema) as w:
            w.write_batch(batch)
        h.update(sink.getvalue())
    return h.hexdigest()[:20]


def _prev_part_index(prev_info: Optional[LastCheckpointInfo],
                     writer_fp: str) -> Dict[str, dict]:
    """fp → manifest entry for the previous checkpoint's file-action
    parts; empty unless the manifest was written by an identically
    configured writer (unknown/absent manifests degrade to a full
    write, never to wrong reuse)."""
    if prev_info is None:
        return {}
    pm = getattr(prev_info, "partManifest", None)
    if not isinstance(pm, dict) or pm.get("writerFp") != writer_fp:
        return {}
    out: Dict[str, dict] = {}
    for e in pm.get("parts") or []:
        if isinstance(e, dict) and e.get("fp") and e.get("name"):
            out[e["fp"]] = e
    return out


def _encode_parquet(table: pa.Table) -> bytes:
    """`table` as `pq.write_table(table, sink, compression="snappy")`
    writes it, byte for byte: a large table's pieces are encoded on the
    scan pool and stitched under one footer (`log/parquet_stitch.py`),
    a small one in that one call. Part fingerprints and incremental
    reuse rest on the bytes being a function of the rows alone. The
    active span (`checkpoint.serialize`) learns how it was made."""
    data, how = parquet_stitch.encode(table)
    obs.set_attrs(**how)
    (_ENCODES_DEALT if how["dealt"] else _ENCODES_SERIAL).inc()
    return data


def _file_part_build(engine, log_path: str, prev_entry: Optional[dict],
                     adds_i: pa.Array, rems_i: pa.Array,
                     ) -> Callable[[], bytes]:
    """Build closure for one file-action part. With a fingerprint-matched
    previous part the bytes are COPIED from the old object: multipart
    part names embed version and part count, and log cleanup may delete
    old parts once shadowed, so reuse must re-materialize under the new
    checkpoint's filename rather than re-reference. A vacuumed or
    unreadable old part degrades to a fresh encode."""

    def fresh() -> bytes:
        return _encode_parquet(_single_action_table(
            len(adds_i) + len(rems_i), None, None, None, None,
            adds_i, rems_i))

    if prev_entry is None:
        return fresh
    prev_path = f"{log_path}/{prev_entry['name']}"

    def build() -> bytes:
        try:
            data = engine.fs.read_file(prev_path)
        except OSError:
            return fresh()
        _PARTS_REUSED.inc()
        return data

    return build


def _count_written(results) -> None:
    for r in results:
        if r.created:
            _PARTS_WRITTEN.inc()
            _BYTES_WRITTEN.inc(r.nbytes)


def _cleanup_orphans(engine, paths) -> None:
    """Best-effort delete of an aborted checkpoint attempt's artifacts.
    The write failure is re-raised by the caller either way; a path
    that refuses to delete merely leaves an orphan part behind, which
    readers ignore (an incomplete part set is never selected)."""
    for p in paths:
        try:
            engine.fs.delete(p)
        # delta-lint: disable=except-swallow (audited: cleanup after an
        # aborted checkpoint is best-effort — the original failure
        # propagates regardless, and a surviving orphan is inert)
        except Exception:
            pass


def _write_multipart_checkpoint(
    engine, log_path, version, add_struct, remove_struct,
    protocol_rows, metadata_rows, txn_rows, domain_rows,
    plan, writer_fp, prev_parts,
):
    """Legacy multi-part. Part 1 holds the small actions ONLY (they
    churn every checkpoint — protocol/metaData/txn/domainMetadata must
    never dirty a reusable file-action chunk); parts 2..N are fixed
    `part_size`-row file-action chunks per `plan`. Layout mirrors
    `Checkpoints.scala:669-699` (hash split by row — contiguous ranges
    are equally valid: parts are unordered). Parts flow through the
    serialize→upload pipeline (`write/ckpt_pipeline.py`) when its gate
    engages."""
    num_parts = 1 + len(plan)
    paths = filenames.checkpoint_file_with_parts(log_path, version, num_parts)
    n_small = (
        len(protocol_rows) + len(metadata_rows)
        + (len(txn_rows) if txn_rows is not None else 0)
        + (len(domain_rows) if domain_rows is not None else 0)
    )

    def small_build() -> bytes:
        return _encode_parquet(_single_action_table(
            n_small, protocol_rows, metadata_rows, txn_rows, domain_rows,
            None, None))

    tasks = [ckpt_pipeline.WriteTask(paths[0], small_build,
                                     overwrite=False, label="small-actions")]
    part_rows = [n_small]
    part_fps: List[Optional[str]] = [None]
    prev_parts = dict(prev_parts)
    for i, (a0, a1, r0, r1) in enumerate(plan):
        adds_i = add_struct.slice(a0, a1 - a0)
        rems_i = remove_struct.slice(r0, r1 - r0)
        fp = _part_fp(writer_fp, adds_i, rems_i)
        # pop, not get: one old part must not satisfy two new chunks
        prev = prev_parts.pop(fp, None)
        tasks.append(ckpt_pipeline.WriteTask(
            paths[i + 1],
            _file_part_build(engine, log_path, prev, adds_i, rems_i),
            overwrite=False,
            label=f"part-{i + 2}" + (":reuse" if prev else "")))
        part_rows.append(len(adds_i) + len(rems_i))
        part_fps.append(fp)

    pipelined = ckpt_pipeline.profitable(engine, log_path, len(tasks))
    results = ckpt_pipeline.run_write_tasks(engine, tasks, pipelined)
    _count_written(results)

    manifest: Optional[dict] = {"writerFp": writer_fp, "parts": []}
    total_bytes = 0
    for path, fp, n, r in zip(paths, part_fps, part_rows, results):
        if r.status is None:
            # another writer materialized this part: its bytes may not
            # match our fingerprints or sizes — publish no manifest
            manifest = None
            break
        total_bytes += r.status.size or 0
        if fp is not None and manifest is not None:
            manifest["parts"].append({
                "name": filenames.file_name(path), "fp": fp, "rows": n,
                "bytes": r.status.size,
                "mtime": r.status.modification_time,
            })
    return LastCheckpointInfo(
        version=version, size=sum(part_rows), parts=num_parts,
        sizeInBytes=total_bytes if manifest is not None else None,
        numOfAddFiles=len(add_struct),
        partManifest=manifest,
    )


def _sidecar_usable(engine, log_path: str, prev_entry: dict) -> bool:
    """Plan-time existence check before re-referencing a previous
    checkpoint's sidecar, so one lost to manual deletion degrades to a
    rewrite instead of a dangling pointer in the new checkpoint."""
    path = f"{filenames.sidecar_dir(log_path)}/{prev_entry['name']}"
    try:
        return bool(engine.fs.exists(path))
    except OSError:
        return False


def _write_v2_checkpoint(
    engine, log_path, version, add_struct, remove_struct,
    protocol_rows, metadata_rows, txn_rows, domain_rows,
    plan, writer_fp, prev_parts,
):
    """V2 (PROTOCOL.md:196-269): file actions go to `_sidecars/<uuid>.parquet`;
    the top-level UUID checkpoint holds checkpointMetadata + sidecar
    pointers + the small actions. File actions split across fixed
    `checkpoint_part_size`-row sidecars per `plan` (the reference
    writes one sidecar per state partition), run through the
    serialize→upload pipeline when its gate engages.

    Reuse here is a RE-REFERENCE, not a copy: sidecars are uuid-named,
    so log cleanup never deletes them (their names parse to no
    version) and a fingerprint-matched previous sidecar can simply be
    pointed at again — zero serialize, zero upload."""
    n_files = len(add_struct) + len(remove_struct)
    num_parts = len(plan)
    prev_parts = dict(prev_parts)
    tasks: List[ckpt_pipeline.WriteTask] = []
    # per part: ("reuse", Sidecar) | ("task", task index, sidecar name)
    slots: List[tuple] = []
    part_fps: List[str] = []
    part_rows: List[int] = []
    for i, (a0, a1, r0, r1) in enumerate(plan):
        adds_i = add_struct.slice(a0, a1 - a0)
        rems_i = remove_struct.slice(r0, r1 - r0)
        fp = _part_fp(writer_fp, adds_i, rems_i)
        part_fps.append(fp)
        part_rows.append(len(adds_i) + len(rems_i))
        prev = prev_parts.pop(fp, None)
        if prev is not None and _sidecar_usable(engine, log_path, prev):
            _PARTS_REUSED.inc()
            slots.append(("reuse", Sidecar(
                path=prev["name"], sizeInBytes=prev.get("bytes"),
                modificationTime=prev.get("mtime"))))
            continue

        def fresh(adds_i=adds_i, rems_i=rems_i) -> bytes:
            return _encode_parquet(_single_action_table(
                len(adds_i) + len(rems_i), None, None, None, None,
                adds_i, rems_i))

        name = f"{uuid.uuid4()}.parquet"
        tasks.append(ckpt_pipeline.WriteTask(
            f"{filenames.sidecar_dir(log_path)}/{name}", fresh,
            overwrite=True,  # uuid-named: never contended
            label=f"sidecar-{i + 1}"))
        slots.append(("task", len(tasks) - 1, name))

    pipelined = ckpt_pipeline.profitable(engine, log_path, len(tasks))
    results = ckpt_pipeline.run_write_tasks(engine, tasks, pipelined)
    _count_written(results)

    sidecars: List[Sidecar] = []
    manifest_parts: List[dict] = []
    for slot, fp, n in zip(slots, part_fps, part_rows):
        if slot[0] == "reuse":
            sc = slot[1]
        else:
            status = results[slot[1]].status
            sc = Sidecar(path=slot[2], sizeInBytes=status.size,
                         modificationTime=status.modification_time)
        sidecars.append(sc)
        manifest_parts.append({
            "name": sc.path, "fp": fp, "rows": n,
            "bytes": sc.sizeInBytes, "mtime": sc.modificationTime,
        })

    top_schema_cols = {}
    n_top = (
        1 + num_parts  # checkpointMetadata + sidecar pointers
        + len(protocol_rows) + len(metadata_rows)
        + (len(txn_rows) if txn_rows is not None else 0)
        + (len(domain_rows) if domain_rows is not None else 0)
    )
    CP_META_STRUCT = pa.struct([pa.field("version", pa.int64())])
    SIDECAR_STRUCT = pa.struct(
        [
            pa.field("path", pa.string()),
            pa.field("sizeInBytes", pa.int64()),
            pa.field("modificationTime", pa.int64()),
        ]
    )

    def block(arr, typ, start, sz):
        parts = []
        if start:
            parts.append(pa.nulls(start, typ))
        if arr is not None and sz:
            parts.append(arr)
        rest = n_top - start - sz
        if rest:
            parts.append(pa.nulls(rest, typ))
        return pa.concat_arrays(parts)

    offset = 0
    cp_arr = pa.array([{"version": version}], CP_META_STRUCT)
    top_schema_cols["checkpointMetadata"] = block(cp_arr, CP_META_STRUCT, offset, 1)
    offset += 1
    sc_arr = pa.array(
        [{
            "path": sc.path,
            "sizeInBytes": sc.sizeInBytes,
            "modificationTime": sc.modificationTime,
        } for sc in sidecars],
        SIDECAR_STRUCT,
    )
    top_schema_cols["sidecar"] = block(sc_arr, SIDECAR_STRUCT, offset, num_parts)
    offset += num_parts
    top_schema_cols["protocol"] = block(protocol_rows, PROTOCOL_STRUCT, offset, len(protocol_rows))
    offset += len(protocol_rows)
    top_schema_cols["metaData"] = block(metadata_rows, METADATA_STRUCT, offset, len(metadata_rows))
    offset += len(metadata_rows)
    if txn_rows is not None:
        top_schema_cols["txn"] = block(txn_rows, TXN_STRUCT, offset, len(txn_rows))
        offset += len(txn_rows)
    if domain_rows is not None:
        top_schema_cols["domainMetadata"] = block(domain_rows, DOMAIN_STRUCT, offset, len(domain_rows))
        offset += len(domain_rows)

    top_table = pa.table(top_schema_cols)
    top_path = filenames.top_level_v2_checkpoint_file(log_path, version, "parquet")
    try:
        engine.parquet.write_parquet_file_atomically(top_path, top_table)
    except BaseException as e:
        # only OUR fresh sidecars are orphans — re-referenced ones
        # belong to the previous (still active) checkpoint
        touched = [r.task.path for r in results if r.created] + [top_path]
        raise ckpt_pipeline.CheckpointWriteError(e, touched) from e
    total_bytes = sum(sc.sizeInBytes or 0 for sc in sidecars)
    total_bytes += _file_size(engine, top_path) or 0
    return LastCheckpointInfo(
        version=version,
        size=n_top + n_files,
        sizeInBytes=total_bytes or None,
        numOfAddFiles=len(add_struct),
        tag=filenames.file_name(top_path),
        partManifest={"writerFp": writer_fp, "parts": manifest_parts},
    )
