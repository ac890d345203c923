"""Scan execution: materialize the scanned rows as one Arrow table.

The `DeltaParquetFileFormat` role (`DeltaParquetFileFormat.scala:189`):
per surviving file — read the Parquet data, drop rows deleted by the
file's deletion vector, splice in partition-column values from
`partitionValues`, apply residual filters, project requested columns.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu import obs
from delta_tpu.models.schema import to_arrow_type


def _absolute_path(table_path: str, file_path: str) -> str:
    if "://" in file_path or file_path.startswith("/"):
        return file_path
    return f"{table_path}/{file_path}"


def _dv_row_mask(engine, table_path: str, dv_row: dict, num_rows: int) -> Optional[np.ndarray]:
    """Boolean keep-mask from a deletion vector descriptor row (None = keep
    all)."""
    if dv_row is None or dv_row.get("storageType") is None:
        return None
    from delta_tpu.dv.descriptor import load_deletion_vector_mask

    deleted = load_deletion_vector_mask(engine, table_path, dv_row, num_rows)
    return ~deleted


def _alignment(physical: pa.Schema, schema, partition_columns, p2l,
               needed=None):
    """How a file of Arrow schema `physical` is brought to the logical
    schema (`_align`): None where it is there already, else (the
    columns' logical names, the positions kept, the casts as (position,
    name, type), the columns to add as nulls). Dropped columns
    disappear, columns added after the file was written read as null
    (restricted to `needed` when projecting), and files written before a
    type-widening change cast up."""
    names = [p2l.get(c, c) for c in physical.names]
    if schema is None:
        return (None if names == physical.names
                else (names, list(range(len(names))), (), ()))
    known = {f.name: f for f in schema.fields if f.name not in partition_columns}
    keep = [i for i, c in enumerate(names) if c in known]
    casts = []
    for at, i in enumerate(keep):
        target_t = to_arrow_type(known[names[i]].dataType)
        if physical.field(i).type != target_t:
            casts.append((at, names[i], target_t))
    have = {names[i] for i in keep}
    nulls = [(f.name, to_arrow_type(f.dataType)) for f in schema.fields
             if f.name in known and f.name not in have
             and (needed is None or f.name in needed)]
    if (names == physical.names and len(keep) == len(names)
            and not casts and not nulls):
        return None
    return names, keep, casts, nulls


def _align(tbl: pa.Table, alignment) -> pa.Table:
    """One file's rows under its schema's `_alignment`."""
    if alignment is None:
        return tbl
    names, keep, casts, nulls = alignment
    tbl = tbl.rename_columns(names)
    if len(keep) != len(names):
        tbl = tbl.select(keep)
    for at, name, target_t in casts:
        try:
            tbl = tbl.set_column(
                at, pa.field(name, target_t), tbl.column(at).cast(target_t))
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError):
            pass  # non-widening mismatch: surface as-is
    for name, t in nulls:
        tbl = tbl.append_column(name, pa.nulls(tbl.num_rows, t))
    return tbl


def _append_partition_columns(tbl: pa.Table, partition_values: pa.ChunkedArray,
                              counts, metadata, needed=None) -> pa.Table:
    """Splice partition-column values (serialized strings in
    `partitionValues`, keyed by physical name under column mapping) back
    into the row set as typed columns: `partition_values` holds a map a
    file and `counts` the rows `tbl` has of each, in order. One value a
    file is typed, then each repeated over its file's rows in one pass
    (a run-end decode)."""
    from delta_tpu.stats.partition import partition_values_to_columns

    wanted = [c for c in metadata.partitionColumns
              if needed is None or c in needed]
    if not wanted:
        return tbl
    counts = np.asarray(counts, dtype=np.int64)
    held = counts > 0        # a run has a row at the least
    per_file = partition_values_to_columns(partition_values, metadata)
    if not held.all():
        per_file = per_file.filter(pa.array(held))
    ends = pa.array(np.cumsum(counts[held]))
    # built whole: a projection of partition columns alone reads tables
    # of no column, whose concatenation forgets its rows
    return pa.Table.from_arrays(
        tbl.columns + [
            pc.run_end_decode(pa.RunEndEncodedArray.from_arrays(
                ends, per_file.column(c).combine_chunks()))
            for c in wanted],
        schema=pa.schema(list(tbl.schema) + [per_file.field(c) for c in wanted],
                         metadata=tbl.schema.metadata))


def read_add_file_logical(engine, table_path: str, snapshot, add,
                          apply_dv: bool = True) -> pa.Table:
    """Read one AddFile as a logical-schema Arrow table: physical→logical
    column renames, schema alignment (missing columns as null, widened
    types cast up), deletion-vector rows dropped, partition columns
    appended. The shared read half of every file-rewrite command
    (OPTIMIZE / REORG PURGE / copy-on-write DML) — the reference does the
    same via `DeltaParquetFileFormat` (`DeltaParquetFileFormat.scala:189`).
    """
    from delta_tpu.columnmapping import mapping_mode, physical_to_logical_names

    schema = snapshot.schema
    meta = snapshot.metadata
    partition_columns = snapshot.partition_columns
    mapped = mapping_mode(meta.configuration) != "none" and schema is not None
    p2l = physical_to_logical_names(schema) if mapped else {}

    try:
        tbl = next(iter(engine.parquet.read_parquet_files(
            [_absolute_path(table_path, add.path)])))
    except FileNotFoundError as e:
        from delta_tpu.errors import FileNotFoundInLogError

        raise FileNotFoundInLogError(
            f"data file referenced by the log is missing: {add.path} "
            "(removed by VACUUM, or the log is ahead of storage)") from e
    tbl = _align(tbl, _alignment(tbl.schema, schema, partition_columns, p2l))
    if apply_dv and add.deletionVector is not None:
        mask = _dv_row_mask(engine, table_path, add.deletionVector.to_dict(),
                            tbl.num_rows)
        if mask is not None:
            tbl = tbl.filter(pa.array(mask))
    partition_values = pa.array(
        [list((add.partitionValues or {}).items())],
        pa.map_(pa.string(), pa.string()))
    return _append_partition_columns(tbl, partition_values, [tbl.num_rows],
                                     meta)


def _deletion_vectors(files: pa.Table) -> dict:
    """row of the plan -> its deletion vector's descriptor, for the rows
    that have one."""
    column = files.column("deletion_vector")
    if not pa.types.is_struct(column.type) or column.null_count == len(column):
        return {}
    has = pc.is_valid(pc.struct_field(column, "storageType"))
    rows = np.flatnonzero(has.to_numpy(zero_copy_only=False))
    return dict(zip(rows.tolist(), column.take(rows).to_pylist()))


def read_scan(scan) -> pa.Table:
    """The plan's files as one table, in the plan's order. The files are
    handed to the engine's Parquet handler as one batch (which may read
    them on several threads); what is the same for every file is done
    once a scan: the alignment is decided once a physical schema, a
    deletion vector is looked for only where the plan has one, and the
    partition columns are built after the concatenation."""
    from delta_tpu.columnmapping import (
        logical_to_physical_names,
        mapping_mode,
        physical_to_logical_names,
    )

    snapshot = scan.snapshot
    engine = snapshot._engine
    table_path = snapshot.table_path
    schema = snapshot.schema
    meta = snapshot.metadata
    partition_columns = snapshot.partition_columns
    files = scan.add_files_table()

    mapped = mapping_mode(meta.configuration) != "none" and schema is not None
    l2p = logical_to_physical_names(schema) if mapped else {}
    p2l = physical_to_logical_names(schema) if mapped else {}

    requested = scan.columns
    # Columns the residual filter references must be read even when not
    # projected (SELECT name ... WHERE id = 2); projection happens last.
    needed = requested
    if requested is not None and scan.filter is not None:
        refs = [r[0] for r in scan.filter.references()]
        needed = requested + [c for c in dict.fromkeys(refs) if c not in requested]
    # Always named where there is a schema, so that the handler reads the
    # batch as a projection: of the columns asked for, those a file has
    # (one that predates a column reads without it). With no projection
    # every column of the schema is asked for, under its physical and
    # its logical name: a file written before the table was mapped
    # carries the logical ones, and read whole it was taken as it came.
    data_columns = None
    if needed is not None:
        data_columns = [
            l2p.get(c, c) for c in needed if c not in partition_columns
        ]
    elif schema is not None:
        names = [f.name for f in schema.fields
                 if f.name not in partition_columns]
        data_columns = list(dict.fromkeys(
            [l2p.get(c, c) for c in names] + names))

    paths = [_absolute_path(table_path, p)
             for p in files.column("path").to_pylist()]
    sizes = files.column("size").fill_null(0).to_numpy()
    dvs = _deletion_vectors(files)
    # (a physical schema met, its alignment): few, and `equals` is
    # cheap where hashing a schema is not
    alignments: List[tuple] = []
    batches: List[pa.Table] = []
    with obs.span("scan.read", files=len(paths), bytes=int(sizes.sum())):
        tables = engine.parquet.read_parquet_files(
            paths, columns=data_columns, sizes=sizes)
        for row, tbl in enumerate(tables):
            physical = tbl.schema
            for met, alignment in alignments:
                if physical.equals(met, check_metadata=False):
                    break
            else:
                alignment = _alignment(
                    physical, schema, partition_columns, p2l, needed)
                alignments.append((physical, alignment))
            tbl = _align(tbl, alignment)
            if row in dvs:
                mask = _dv_row_mask(engine, table_path, dvs[row], tbl.num_rows)
                if mask is not None:
                    tbl = tbl.filter(pa.array(mask))
            batches.append(tbl)

    if not batches:
        cols = requested or (
            [f.name for f in schema.fields] if schema is not None else []
        )
        empty = {}
        for c in cols:
            t = to_arrow_type(schema[c].dataType) if schema and c in schema else pa.string()
            empty[c] = pa.array([], t)
        return pa.table(empty)

    # what a scan does to its batches once they are read: one table, the
    # partition columns, the residual filter, the projection
    with obs.span("scan.assemble", batches=len(batches),
                  filtered=scan.filter is not None) as sp:
        result = _append_partition_columns(
            pa.concat_tables(batches, promote_options="permissive"),
            files.column("partition_values"), [t.num_rows for t in batches],
            meta, needed)
        sp.set_attr("rows_in", result.num_rows)
        if scan.filter is not None:
            from delta_tpu.expressions.eval import evaluate_predicate_host

            try:
                keep = evaluate_predicate_host(scan.filter, result)
                result = result.filter(pa.array(keep))
            except KeyError:
                pass  # filter references columns not projected
        if requested is not None:
            result = result.select(
                [c for c in requested if c in result.column_names])
        sp.set_attr("rows", result.num_rows)
    return result
