"""OPTIMIZE: bin-packing compaction and Z-order / Hilbert clustering.

Reference `commands/OptimizeTableCommand.scala:251-427` (OptimizeExecutor:
candidate selection → `groupFilesIntoBins` → per-bin rewrite →
SnapshotIsolation commit with dataChange=false) and
`skipping/MultiDimClustering.scala:41-69` (curve-key range clustering).

TPU mapping: the clustering permutation (rank → curve key → sort) runs
entirely on device (`ops/zorder.py`); bin packing is a host heuristic
(`BinPackingUtils.binPackBySize` semantics).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from delta_tpu import obs
from delta_tpu.errors import DeltaError, MissingTransactionLogError, OptimizeArgumentError
from delta_tpu.expressions.tree import Expression
from delta_tpu.models.actions import AddFile
from delta_tpu.txn.isolation import IsolationLevel
from delta_tpu.txn.transaction import Operation
from delta_tpu.write.writer import write_data_files

DEFAULT_MIN_FILE_SIZE = 256 * 1024 * 1024   # files below this are compacted
DEFAULT_MAX_FILE_SIZE = 256 * 1024 * 1024   # bin capacity

_BINS = obs.counter("optimize.bins")
_ROWS_CLUSTERED = obs.counter("optimize.rows_clustered")
_FILES_REMOVED = obs.counter("optimize.files_removed")
_FILES_ADDED = obs.counter("optimize.files_added")


@dataclass
class OptimizeMetrics:
    num_files_added: int = 0
    num_files_removed: int = 0
    bytes_added: int = 0
    bytes_removed: int = 0
    num_bins: int = 0
    num_batches: int = 1
    partitions_optimized: int = 0
    version: Optional[int] = None

    def to_dict(self) -> Dict:
        return dict(self.__dict__)


def bin_pack_by_size(
    files: Sequence[AddFile], max_bin_size: int
) -> List[List[AddFile]]:
    """First-fit-decreasing-ish packing (reference
    `BinPackingUtils.binPackBySize:317`: sort ascending, accumulate until
    the bin would overflow)."""
    bins: List[List[AddFile]] = []
    cur: List[AddFile] = []
    cur_size = 0
    for f in sorted(files, key=lambda f: f.size):
        if cur and cur_size + f.size > max_bin_size:
            bins.append(cur)
            cur, cur_size = [], 0
        cur.append(f)
        cur_size += f.size
    if cur:
        bins.append(cur)
    return bins


class OptimizeBuilder:
    """`table.optimize().where(...).execute_compaction()` /
    `.execute_zorder_by("c1", "c2")` (mirrors `DeltaOptimizeBuilder`)."""

    def __init__(self, table):
        self._table = table
        self._filter: Optional[Expression] = None

    def where(self, predicate: Expression) -> "OptimizeBuilder":
        self._filter = predicate
        return self

    def execute_compaction(
        self,
        min_file_size: int = DEFAULT_MIN_FILE_SIZE,
        max_file_size: int = DEFAULT_MAX_FILE_SIZE,
    ) -> OptimizeMetrics:
        return _run_optimize(
            self._table, self._filter, zorder_by=None,
            min_file_size=min_file_size, max_file_size=max_file_size,
        )

    def execute_zorder_by(
        self, *columns: str, curve: str = "zorder",
        max_file_size: int = DEFAULT_MAX_FILE_SIZE,
    ) -> OptimizeMetrics:
        if not columns:
            raise OptimizeArgumentError("ZORDER BY requires at least one column",
                                        error_class="DELTA_ZORDER_REQUIRES_COLUMN")
        return _run_optimize(
            self._table, self._filter, zorder_by=list(columns), curve=curve,
            min_file_size=None, max_file_size=max_file_size,
        )

    def execute_full(
        self, max_file_size: int = DEFAULT_MAX_FILE_SIZE,
    ) -> OptimizeMetrics:
        """OPTIMIZE ... FULL: re-cluster EVERY file of a clustered
        table, including files already in stable ZCubes
        (`OptimizeTableCommand.scala` isFull; only valid on clustered
        tables — `DeltaErrors.optimizeFullNotSupportedException`)."""
        from delta_tpu.clustering import clustering_columns

        snap = self._table.latest_snapshot()
        if not clustering_columns(snap):
            raise OptimizeArgumentError(
                "OPTIMIZE FULL is only supported for clustered tables "
                "with non-empty clustering columns",
                error_class="DELTA_OPTIMIZE_FULL_NOT_SUPPORTED")
        return _run_optimize(
            self._table, self._filter, zorder_by=None,
            min_file_size=None, max_file_size=max_file_size, full=True,
        )


def _run_optimize(
    table,
    filter: Optional[Expression],
    zorder_by: Optional[List[str]],
    max_file_size: int,
    min_file_size: Optional[int],
    curve: str = "zorder",
    full: bool = False,
) -> OptimizeMetrics:
    with obs.span("command.optimize", table=table.path,
                  zorder=bool(zorder_by)) as sp:
        metrics = _run_optimize_inner(
            table, filter, zorder_by, max_file_size, min_file_size, curve,
            full)
        sp.set_attrs(files_removed=metrics.num_files_removed,
                     files_added=metrics.num_files_added)
        return metrics


def _run_optimize_inner(
    table,
    filter: Optional[Expression],
    zorder_by: Optional[List[str]],
    max_file_size: int,
    min_file_size: Optional[int],
    curve: str = "zorder",
    full: bool = False,
) -> OptimizeMetrics:
    from delta_tpu.clustering import (
        clustering_columns,
        file_in_stable_zcube,
        new_zcube_tags,
    )

    txn = table.create_transaction_builder(Operation.OPTIMIZE).build()
    txn._isolation = IsolationLevel.SNAPSHOT_ISOLATION
    snapshot = txn.read_snapshot
    if snapshot is None:
        raise MissingTransactionLogError(f"no table at {table.path}")
    meta = snapshot.metadata
    schema = meta.schema

    # clustered table: compaction becomes clustering by the domain's
    # columns (`OptimizeExecutor` isClusteredTable semantics)
    cluster_cols = clustering_columns(snapshot)
    zcube_tags = None
    if zorder_by is None and cluster_cols:
        zorder_by = cluster_cols
        min_file_size = None
        zcube_tags = new_zcube_tags(cluster_cols, curve)
        if filter is not None:
            # `DeltaErrors.clusteringWithPartitionPredicatesException`:
            # clustered tables cluster the whole table, never a slice
            raise OptimizeArgumentError(
                "predicates are not supported when optimizing a "
                "clustered table",
                error_class="DELTA_CLUSTERING_WITH_PARTITION_PREDICATE")
    elif zorder_by and cluster_cols:
        raise OptimizeArgumentError(
            "clustered tables use OPTIMIZE (no ZORDER BY); clustering "
            f"columns are {cluster_cols}",
            error_class="DELTA_CLUSTERING_WITH_ZORDER_BY")

    if zorder_by:
        from delta_tpu.stats.collection import stats_columns

        indexed = {".".join(p) for p in stats_columns(
            schema, meta.configuration, meta.partitionColumns)} \
            if schema is not None else None
        for c in zorder_by:
            if c in meta.partitionColumns:
                raise OptimizeArgumentError(f"cannot Z-order by partition column {c}",
                                        error_class="DELTA_ZORDERING_ON_PARTITION_COLUMN")
            if schema is not None and c not in schema:
                raise OptimizeArgumentError(f"Z-order column {c} not in schema",
                                        error_class="DELTA_ZORDERING_COLUMN_DOES_NOT_EXIST")
            if indexed is not None and c not in indexed:
                # `DeltaErrors.zOrderingOnColumnWithNoStatsException`:
                # clustering by an unindexed column cannot help skipping
                raise OptimizeArgumentError(
                    f"Z-ordering on {c} will be ineffective: no "
                    "file statistics are collected for it (see "
                    "delta.dataSkippingStatsColumns / "
                    "delta.dataSkippingNumIndexedCols)",
                    error_class="DELTA_ZORDERING_ON_COLUMN_WITHOUT_STATS")

    with obs.span("optimize.plan") as sp:
        candidates = txn.scan_files(filter=filter)
        if full:
            zcube_tags = zcube_tags or (
                new_zcube_tags(cluster_cols, curve) if cluster_cols else None)
            # OPTIMIZE FULL ignores ZCube stability: everything re-clusters
        elif zcube_tags is not None:
            # skip files already in a stable cube over the same columns
            cube_sizes: Dict[str, int] = {}
            from delta_tpu.clustering import ZCUBE_ID_TAG

            for f in candidates:
                cid = (f.tags or {}).get(ZCUBE_ID_TAG)
                if cid:
                    cube_sizes[cid] = cube_sizes.get(cid, 0) + f.size
            candidates = [
                f for f in candidates
                if not file_in_stable_zcube(f, zorder_by, cube_sizes)
            ]

        # group per partition (bins never span partitions)
        by_partition: Dict[tuple, List[AddFile]] = {}
        for f in candidates:
            key = tuple(sorted((f.partitionValues or {}).items()))
            by_partition.setdefault(key, []).append(f)

        plan: List[List[List[AddFile]]] = []    # a partition's bins
        for _pkey, files in sorted(by_partition.items()):
            if zorder_by is None:
                small = [f for f in files if f.size < min_file_size]
                plan.append([b for b in bin_pack_by_size(small, max_file_size)
                             if len(b) > 1])
            else:
                # multi-dim clustering rewrites every candidate file
                plan.append([files] if files else [])
        sp.set_attrs(candidates=len(candidates),
                     bins=sum(len(bins) for bins in plan))
    metrics = OptimizeMetrics()

    # Explicit Z-order stamps ZCube tags on its output too, so scan
    # planning (and the bench's skip-rate assert) can see which files
    # were curve-clustered. Kept separate from `zcube_tags`: explicit
    # zorder must not inherit the clustered path's stable-cube
    # candidate filtering, clusteringProvider, or operationParameters
    # clusterBy semantics.
    explicit_tags = (new_zcube_tags(zorder_by, curve)
                     if zorder_by and zcube_tags is None else None)

    now_ms = int(time.time() * 1000)
    new_adds: List[AddFile] = []
    removed: List[AddFile] = []
    for bins in plan:
        for bin_files in bins:
            adds = _rewrite_bin(
                table, snapshot, bin_files, zorder_by, curve, max_file_size
            )
            if zcube_tags is not None:
                adds = [
                    dataclasses.replace(
                        a, tags={**(a.tags or {}), **zcube_tags},
                        clusteringProvider="liquid",
                    )
                    for a in adds
                ]
            elif explicit_tags is not None:
                adds = [
                    dataclasses.replace(
                        a, tags={**(a.tags or {}), **explicit_tags})
                    for a in adds
                ]
            new_adds.extend(adds)
            removed.extend(bin_files)
            metrics.num_bins += 1
        if bins:
            metrics.partitions_optimized += 1

    if not removed:
        return metrics  # nothing to do; no commit

    with obs.span("optimize.commit", adds=len(new_adds),
                  removes=len(removed)):
        for f in removed:
            txn.remove_file(
                f.remove(deletion_timestamp=now_ms, data_change=False))
        txn.add_files(new_adds)
        txn.set_operation_parameters(
            {
                "predicate": repr(filter) if filter is not None else "[]",
                "zOrderBy": (list(zorder_by)
                             if zorder_by and zcube_tags is None else []),
                "clusterBy": list(zorder_by) if zcube_tags is not None else [],
                "auto": False,
            }
        )
        metrics.num_files_added = len(new_adds)
        metrics.num_files_removed = len(removed)
        metrics.bytes_added = sum(a.size for a in new_adds)
        metrics.bytes_removed = sum(r.size for r in removed)
        txn.set_operation_metrics(
            {
                "numAddedFiles": metrics.num_files_added,
                "numRemovedFiles": metrics.num_files_removed,
                "numAddedBytes": metrics.bytes_added,
                "numRemovedBytes": metrics.bytes_removed,
            }
        )
        result = txn.commit()
    metrics.version = result.version
    _BINS.inc(metrics.num_bins)
    _FILES_REMOVED.inc(metrics.num_files_removed)
    _FILES_ADDED.inc(metrics.num_files_added)
    return metrics


def _rewrite_bin(
    table, snapshot, bin_files: List[AddFile],
    zorder_by: Optional[List[str]], curve: str, max_file_size: int,
) -> List[AddFile]:
    """Read the bin's rows (deletion vectors applied, physical→logical
    names mapped), optionally reorder along the curve, and write back as
    (approximately) bin-size files. Rewritten files drop their DVs —
    OPTIMIZE purges soft-deleted rows like the reference's
    `OptimizeExecutor`.

    Under clustering the bin's files are read in ascending order of
    `path`, each file's rows in file order: the curve's ranks break ties
    by position, so the output's row order is a function of the table's
    state and not of the order in which the replay happens to hand the
    files over. A bin of plain compaction is read in the packing's order
    (by size, equal sizes as handed over): its rows are only laid end
    to end."""
    from delta_tpu.read.reader import read_add_file_logical

    engine = table.engine
    meta = snapshot.metadata
    schema = meta.schema
    if zorder_by:
        bin_files = sorted(bin_files, key=lambda f: f.path)
    with obs.span("optimize.read", files=len(bin_files),
                  bytes=sum(f.size for f in bin_files)) as sp:
        data = pa.concat_tables(
            [read_add_file_logical(engine, table.path, snapshot, f)
             for f in bin_files],
            promote_options="permissive",
        )
        sp.set_attr("rows", data.num_rows)

    if zorder_by and data.num_rows:
        import pyarrow.compute as pc

        from delta_tpu.ops.zorder import curve_keys, curve_perm

        with obs.span("optimize.keys", columns=len(zorder_by),
                      rows=data.num_rows) as sp:
            cols = []
            for c in zorder_by:
                arr = data.column(c).combine_chunks()
                if arr.null_count:
                    fill = "" if pa.types.is_string(arr.type) else 0
                    arr = pc.fill_null(arr, fill)
                a = np.asarray(arr)
                if a.dtype == object:
                    a = a.astype(str)
                cols.append(a)
            stacked = curve_keys(cols)
            sp.set_attr("n_pad", stacked.shape[1])
        # the dispatch and its blocking read
        with obs.span("optimize.curve", curve=curve, n_pad=stacked.shape[1]):
            perm = curve_perm(stacked, data.num_rows, curve)
        with obs.span("optimize.gather", rows=data.num_rows,
                      columns=data.num_columns, bytes=data.nbytes):
            data = data.take(pa.array(perm, pa.int64()))
        _ROWS_CLUSTERED.inc(data.num_rows)

    total_bytes = sum(f.size for f in bin_files)
    n_out = max(1, -(-total_bytes // max_file_size))
    rows_per_file = max(1, -(-data.num_rows // n_out))

    part_cols = meta.partitionColumns
    with obs.span("optimize.write", rows=data.num_rows) as sp:
        adds = write_data_files(
            engine=engine,
            table_path=table.path,
            data=data,
            schema=schema,
            partition_columns=part_cols,
            configuration=meta.configuration,
            data_change=False,
            target_rows_per_file=rows_per_file if n_out > 1 else None,
        )
        sp.set_attrs(files=len(adds), bytes=sum(a.size for a in adds))
    return adds
