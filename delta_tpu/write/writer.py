"""Transactional data-file writing.

The `TransactionalWrite.writeFiles` analogue (`files/
TransactionalWrite.scala:230`): an Arrow table goes in; Parquet data files
plus fully-populated `AddFile` actions (partition values, size, mtime,
stats JSON) come out, ready to stage on a transaction. Partitioned tables
are split by partition values into Hive-style directories; large inputs
split into multiple files per `delta.targetFileSize` (approximated by row
count from the input's in-memory footprint).

Invariant / constraint enforcement (NOT NULL, CHECK) runs before any file
is written (`constraints/Invariants.scala` role).
"""

from __future__ import annotations

import time
import uuid
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.errors import InvariantViolationError, SchemaMismatchError
from delta_tpu.models.actions import AddFile
from delta_tpu.models.schema import StructType, from_arrow_schema, to_arrow_schema
from delta_tpu.stats.collection import collect_stats
from delta_tpu.stats.partition import partition_path, serialize_partition_value


def _check_invariants(table: pa.Table, schema: StructType, constraints=None) -> None:
    for f in schema.fields:
        if not f.nullable and f.name in table.column_names:
            nulls = table.column(f.name).null_count
            if nulls:
                raise InvariantViolationError(
                    error_class="DELTA_NOT_NULL_CONSTRAINT_VIOLATED",
                    message=f"NOT NULL constraint violated for column {f.name}: "
                    f"{nulls} null row(s)"
                )
    if constraints:
        from delta_tpu.expressions.eval import evaluate_predicate_host

        for name, expr in constraints.items():
            ok = evaluate_predicate_host(expr, table)
            bad = int((~ok).sum())
            if bad:
                raise InvariantViolationError(
                    error_class="DELTA_VIOLATE_CONSTRAINT_WITH_VALUES",
                    message=f"CHECK constraint {name} violated by {bad} row(s)"
                )


def _validate_schema(table: pa.Table, schema: StructType) -> None:
    table_fields = set(table.column_names)
    schema_fields = set(schema.field_names())
    missing = schema_fields - table_fields
    extra = table_fields - schema_fields
    if extra:
        reserved = {"_change_type", "_commit_version", "_commit_timestamp"}
        if reserved & extra:
            raise SchemaMismatchError(
                f"columns {sorted(reserved & extra)} are reserved for the "
                "change data feed and cannot be written",
                error_class="RESERVED_CDC_COLUMNS_ON_WRITE",
            )
        raise SchemaMismatchError(
            f"columns {sorted(extra)} not in table schema {sorted(schema_fields)}",
            error_class="DELTA_COLUMN_NOT_FOUND_IN_SCHEMA",
        )
    if missing:
        nonnull_missing = [
            m for m in missing if m in schema and not schema[m].nullable
        ]
        if nonnull_missing:
            raise SchemaMismatchError(
                error_class="DELTA_MISSING_NOT_NULL_COLUMN_VALUE",
                message=f"missing non-nullable columns: {sorted(nonnull_missing)}"
            )


def write_data_files(
    engine,
    table_path: str,
    data: pa.Table,
    schema: StructType,
    partition_columns: Sequence[str],
    configuration: Dict[str, str],
    data_change: bool = True,
    constraints=None,
    target_rows_per_file: Optional[int] = None,
    base_row_id_start: Optional[int] = None,
) -> List[AddFile]:
    """Write `data` under `table_path`, returning AddFile actions.

    Inputs use LOGICAL column names; under column mapping the Parquet
    files, stats JSON, and partitionValues keys all use physical names
    (protocol requirement)."""
    from delta_tpu.columnmapping import logical_to_physical_names, mapping_mode

    _validate_schema(data, schema)
    if constraints is None:
        from delta_tpu.constraints import table_constraints

        constraints = table_constraints(configuration)
    _check_invariants(data, schema, constraints)
    now_ms = int(time.time() * 1000)
    adds: List[AddFile] = []
    partition_columns = list(partition_columns)

    from delta_tpu.config import (
        RANDOM_PREFIX_LENGTH,
        RANDOMIZE_FILE_PREFIXES,
        get_table_config,
    )

    randomize_prefixes = get_table_config(configuration, RANDOMIZE_FILE_PREFIXES)
    prefix_len = max(1, get_table_config(configuration, RANDOM_PREFIX_LENGTH))

    mapped = mapping_mode(configuration) != "none"
    l2p = logical_to_physical_names(schema) if mapped else {}

    def phys(name: str) -> str:
        return l2p.get(name, name)

    if partition_columns:
        groups = _partition_groups(data, partition_columns)
    else:
        groups = [({}, data)]

    phys_schema = schema
    if mapped:
        from delta_tpu.columnmapping import physical_schema

        phys_schema = physical_schema(schema)

    next_base_row_id = base_row_id_start
    for pv, part_data in groups:
        file_data = part_data.drop_columns(
            [c for c in partition_columns if c in part_data.column_names]
        )
        if mapped:
            file_data = file_data.rename_columns(
                [phys(c) for c in file_data.column_names]
            )
        phys_pv = {phys(k): v for k, v in pv.items()}
        phys_part_cols = [phys(c) for c in partition_columns]
        for chunk in _split_rows(file_data, target_rows_per_file):
            if chunk.num_rows == 0:
                continue
            fname = f"part-{uuid.uuid4()}.parquet"
            if randomize_prefixes:
                # random bucket INSTEAD of partition directories
                # (reference DelayedCommitProtocol): flattens the
                # object-store key space; partition values live in the
                # AddFile metadata, not the path
                rel_path = f"{uuid.uuid4().hex[:prefix_len]}/{fname}"
            else:
                rel_path = f"{partition_path(phys_pv, phys_part_cols)}{fname}"
            abs_path = f"{table_path}/{rel_path}"
            status = engine.parquet.write_parquet_file(abs_path, chunk)
            stats = collect_stats(
                chunk, phys_schema, configuration, phys_part_cols
            )
            add = AddFile(
                path=rel_path,
                partitionValues=dict(phys_pv),
                size=status.size,
                modificationTime=status.modification_time or now_ms,
                dataChange=data_change,
                stats=stats,
            )
            if next_base_row_id is not None:
                add.baseRowId = next_base_row_id
                next_base_row_id += chunk.num_rows
            adds.append(add)
    return adds


def _partition_groups(data: pa.Table, partition_columns: List[str]):
    """Split rows by partition-column values, groups in the order their
    first row appears. The values are the Arrow column's own (an
    `integer` is 2450816 and a null is null, whatever pandas would make
    of a nullable column), and the rows are grouped by one stable sort
    of their group codes, not one pass a partition."""
    codes = None
    columns = []    # per column: (its distinct values, a row's index in them)
    for c in partition_columns:
        if c not in data.column_names:
            raise SchemaMismatchError(
                f"partition column {c} missing from data",
                error_class="DELTA_MISSING_PARTITION_COLUMN")
        col = data.column(c)
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        encoded = pc.dictionary_encode(col.combine_chunks(),
                                       null_encoding="encode")
        if isinstance(encoded, pa.ChunkedArray):
            encoded = encoded.unify_dictionaries().combine_chunks()
        values = encoded.dictionary.to_pylist()
        idx = encoded.indices.to_numpy(zero_copy_only=False).astype(
            np.int64, copy=False)
        columns.append((values, idx))
        if codes is None:
            codes = idx
        else:
            # dense after every column, so the product never overflows
            _, codes = np.unique(codes * len(values) + idx,
                                 return_inverse=True)
    if data.num_rows == 0:
        return []
    order = np.argsort(codes, kind="stable")
    ordered = codes[order]
    starts = np.flatnonzero(np.concatenate(
        [[True], ordered[1:] != ordered[:-1]]))
    stops = np.append(starts[1:], len(order))
    by_group = data.take(pa.array(order, pa.int64()))
    firsts = order[starts]      # a stable sort keeps a group's first row first
    out = []
    for g in np.argsort(firsts, kind="stable"):
        pv = {c: serialize_partition_value(values[idx[firsts[g]]])
              for c, (values, idx) in zip(partition_columns, columns)}
        out.append((pv, by_group.slice(int(starts[g]),
                                       int(stops[g] - starts[g]))))
    return out


def _split_rows(data: pa.Table, target_rows: Optional[int]):
    if target_rows is None or data.num_rows <= target_rows:
        return [data]
    out = []
    for start in range(0, data.num_rows, target_rows):
        out.append(data.slice(start, target_rows))
    return out
