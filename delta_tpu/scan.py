"""Scan: pruned file listing (and data read) over a snapshot.

Mirrors kernel `ScanBuilder`/`Scan`/`ScanImpl.java:438`: a scan applies,
in order,
1. partition pruning — the filter conjuncts that touch only partition
   columns, evaluated against each file's `partitionValues`;
2. data skipping — remaining conjuncts translated into min/max-stats
   predicates over the stats index (delta_tpu.stats.skipping), evaluated
   on device for the TpuEngine;
3. (on read) deletion-vector row filtering and column mapping.

`add_files_table()` returns the surviving files columnar; `to_arrow()`
reads the actual data rows.
"""
# delta-lint: file-disable=shared-state-race — audited:
# ScanBuilder is a per-operation builder: created and consumed by the
# thread running the scan; instances are never shared across threads
# (matching the reference's ScanBuilder contract).

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu import obs
from delta_tpu.expressions.tree import Expression, split_conjuncts
from delta_tpu.models.actions import AddFile

_FROM_HELD_ROWS = obs.counter("scan.plans_from_held_rows")


class ScanBuilder:
    def __init__(self, snapshot):
        self._snapshot = snapshot
        self._filter: Optional[Expression] = None
        self._columns: Optional[List[str]] = None

    def with_filter(self, expr: Expression) -> "ScanBuilder":
        self._filter = expr if self._filter is None else (self._filter & expr)
        return self

    def with_columns(self, columns: Sequence[str]) -> "ScanBuilder":
        self._columns = list(columns)
        return self

    def build(self) -> "Scan":
        return Scan(self._snapshot, self._filter, self._columns)


class Scan:
    def __init__(self, snapshot, filter: Optional[Expression], columns: Optional[List[str]]):
        self._snapshot = snapshot
        self.filter = filter
        self.columns = columns
        self._result_cache: Optional[pa.Table] = None
        self.partition_pruned = 0
        self.skipped_by_stats = 0

    @property
    def snapshot(self):
        return self._snapshot

    def _partition_batch(self, partition_values: pa.ChunkedArray) -> pa.Table:
        """Reconstruct typed partition-column values from the
        partitionValues string map (protocol Partition Value Serialization)."""
        from delta_tpu.stats.partition import partition_values_to_columns

        return partition_values_to_columns(partition_values,
                                           self._snapshot.metadata)

    def add_files_table(self) -> pa.Table:
        """Surviving AddFiles (canonical columnar schema) after pruning."""
        if self._result_cache is not None:
            return self._result_cache
        with obs.span("scan.plan", table=self._snapshot.table_path,
                      version=self._snapshot.version) as sp:
            result = self._plan(sp)
            sp.set_attrs(surviving=result.num_rows,
                         partition_pruned=self.partition_pruned,
                         skipped_by_stats=self.skipped_by_stats)
            return result

    def _plan(self, sp) -> pa.Table:
        """With no filter, the state's live table. With one, the plan
        is made over the state's live rows, row `i` the `i`-th live row
        held, as the resident stats index has them, and the survivors
        are read straight out of the rows held: the live table is never
        built for it."""
        state = self._snapshot.state
        n = 0 if self.filter is None else len(state.live_rows)
        if n == 0:
            files = state.add_files_table
            sp.set_attr("total_files", files.num_rows)
            self._result_cache = files
            return files
        sp.set_attr("total_files", n)

        partition_cols = set(self._snapshot.partition_columns)
        conjuncts = split_conjuncts(self.filter)
        part_conjuncts = [
            c for c in conjuncts
            if c.references() and all(r[0] in partition_cols for r in c.references())
        ]
        # identity, not `in`: Expression.__eq__ BUILDS a (truthy)
        # Comparison node, so `c not in part_conjuncts` was False for
        # every conjunct whenever any partition conjunct existed —
        # silently disabling stats skipping on partition-filtered scans
        part_ids = {id(c) for c in part_conjuncts}
        data_conjuncts = [c for c in conjuncts if id(c) not in part_ids]

        keep = None     # every live row, until a conjunct says otherwise
        if part_conjuncts:
            batch = self._partition_batch(state.live_columns(
                ["partition_values"]).column(0))
            from delta_tpu.expressions.eval import evaluate_predicate_host

            keep = np.ones(n, dtype=bool)
            for c in part_conjuncts:
                keep &= evaluate_predicate_host(c, batch)
            self.partition_pruned = n - int(np.count_nonzero(keep))

        if data_conjuncts:
            from delta_tpu.stats.skipping import skipping_mask

            # `skipping_mask` names its route, atoms and fallback
            # conjuncts on this span
            with obs.span("plan.skip", rows=n,
                          conjuncts=len(data_conjuncts)):
                stats_keep = skipping_mask(
                    None,
                    data_conjuncts,
                    self._snapshot.metadata,
                    engine=self._snapshot._engine,
                    state=state,
                )
            keep = stats_keep if keep is None else keep & stats_keep

        with obs.span("plan.filter", rows=n) as fsp:
            result = state.live_subset(keep)
            fsp.set_attr("surviving", result.num_rows)
        # counted from what survived: a pass over a mask of every live
        # row is a tenth of a plan at 2.4M files
        self.skipped_by_stats = (n - self.partition_pruned
                                 - result.num_rows)
        _FROM_HELD_ROWS.inc()
        self._result_cache = result
        self._report_metrics(n, result.num_rows)
        return result

    def _report_metrics(self, total: int, surviving: int) -> None:
        eng = self._snapshot._engine
        if getattr(eng, "metrics_reporters", None):
            eng.report_metrics(
                {
                    "type": "ScanReport",
                    "tablePath": self._snapshot.table_path,
                    "tableVersion": self._snapshot.version,
                    "totalFiles": total,
                    "survivingFiles": surviving,
                    "partitionPruned": self.partition_pruned,
                    "skippedByStats": self.skipped_by_stats,
                    "filter": repr(self.filter) if self.filter else None,
                }
            )

    def files(self) -> List[AddFile]:
        from delta_tpu.replay.state import _row_to_add

        return [_row_to_add(r) for r in self.add_files_table().to_pylist()]

    def file_paths(self) -> List[str]:
        return self.add_files_table().column("path").to_pylist()

    def to_arrow(self) -> pa.Table:
        """Read the scanned data into one Arrow table (applies DV row
        filtering, partition-column injection, and residual filters)."""
        from delta_tpu.read.reader import read_scan

        return read_scan(self)
