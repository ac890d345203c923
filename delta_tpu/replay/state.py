"""State reconstruction driver: columnar actions → SnapshotState.

Pipeline (TPU path):
1. Columnarize the log segment (columnar.py) → canonical Arrow table.
2. Dictionary-encode the replay key `(path, dv_id)` into int32 codes
   (exact, vectorized factorization — the host-side equivalent of the
   reference's path canonicalization + hashing at `Snapshot.scala:477-483`).
3. Run the device sort + segmented last-wins reduce (ops.replay) to get
   the live/tombstone masks.
4. Filter the Arrow table by the masks; aggregate numFiles/sizeInBytes.

HostEngine path replaces step 3 with the sequential dict replay — the
faithful re-implementation of `InMemoryLogReplay` used as parity oracle
and baseline.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np
import pandas as pd
import pyarrow as pa

from delta_tpu import obs
from delta_tpu.errors import LogCorruptedError, UnsupportedTableFeatureError
from delta_tpu.models.actions import (
    AddFile,
    CommitInfo,
    DeletionVectorDescriptor,
    DomainMetadata,
    Metadata,
    Protocol,
    RemoveFile,
    SetTransaction,
)
from delta_tpu.replay.columnar import ColumnarActions, columnarize_log_segment
from delta_tpu.replay.path_codes import first_appearance_codes

# same registry instrument as parallel/resident.py: the cataloged
# fallback counter for the replay route (route-contract lint)
_ROUTE_FALLBACKS = obs.counter("replay.resident_fallbacks")
_LIVE_TABLE_BUILDS = obs.counter("state.live_table_builds")
_LIVE_COLUMNS_DEALT = obs.counter("state.live_columns_dealt")
_LIVE_COLUMNS_SERIAL = obs.counter("state.live_columns_serial")


@dataclass
class SnapshotState:
    version: int
    protocol: Protocol
    metadata: Metadata
    set_transactions: Dict[str, SetTransaction]
    domain_metadata: Dict[str, DomainMetadata]
    file_actions_raw: pa.Table        # canonical schema, all actions; the
                                      # stats column may be a deferred
                                      # placeholder until first
                                      # `file_actions` access
    live_mask: np.ndarray             # bool over file_actions rows
    tombstone_mask: np.ndarray
    latest_commit_info: Optional[CommitInfo] = None
    commit_infos: Dict[int, CommitInfo] = field(default_factory=dict)
    timestamp_ms: int = 0
    # deferred stats decode from the lazy-stats native scan (columnar
    # stats_thunk); spliced exactly once below
    stats_thunk: Optional[object] = None
    # Device-resident sharded replay state (parallel/resident.py):
    # exactly one SnapshotState owns it at a time — `advance_state`
    # moves it to the advanced state (the append kernel donates the
    # device buffer, so the prior owner's reference would be stale)
    resident: Optional[object] = field(default=None, repr=False,
                                       compare=False)
    # Resident scan-planning stats index (stats/device_index.py):
    # built at most once per state under `_stats_index_lock` — a
    # dedicated lock because the build reads `file_actions`, which
    # takes `_splice_lock` itself. `advance_state` carries it forward
    # on empty deltas and releases it otherwise; serve-cache eviction
    # releases it through `release_snapshot_resident`.
    stats_index: Optional[object] = field(default=None, repr=False,
                                          compare=False)
    # What `advance_state` kept of a prior version's released index
    # (`stats/device_index.py::StatsIndexSeed`), under the same lock:
    # the first filtered scan of this state makes its index from it
    # and drops it; until then later advances pass it on.
    stats_index_seed: Optional[object] = field(default=None, repr=False,
                                               compare=False)
    # Resident SQL operand cache (sqlengine/operands.py): per-column
    # device lanes for join/group keys, built lazily per state under
    # `_operand_cache_lock`. `advance_state` carries it forward on
    # empty deltas and releases it otherwise; serve-cache eviction
    # releases it through `release_snapshot_resident`.
    operand_cache: Optional[object] = field(default=None, repr=False,
                                            compare=False)
    # Table root this state was reconstructed from — threaded into the
    # HBM resident ledger so lazily built device artifacts (stats-index
    # lanes, replay key lanes grown on advance) attribute to the right
    # table even when built outside a `hbm.table_scope` block.
    table_path: Optional[str] = None

    _add_table_cache: Optional[pa.Table] = None
    _live_rows_cache: Optional[np.ndarray] = None
    _tombstone_table_cache: Optional[pa.Table] = None
    _splice_lock: object = field(default_factory=threading.Lock,
                                 repr=False, compare=False)
    _stats_index_lock: object = field(default_factory=threading.Lock,
                                      repr=False, compare=False)
    _operand_cache_lock: object = field(default_factory=threading.Lock,
                                        repr=False, compare=False)

    @property
    def file_actions(self) -> pa.Table:
        """The complete canonical table. Splices the deferred stats
        column in on first access — stats are ~60% of commit bytes and
        pure metadata loads (num_files/size_in_bytes/replay) never pay
        for decoding them. Locked: two threads' first accesses must not
        both run the thunk."""
        from delta_tpu.replay.columnar import splice_stats

        with self._splice_lock:
            if self.stats_thunk is not None:
                with obs.span("state.splice_stats",
                              rows=self.file_actions_raw.num_rows) as sp:
                    self.file_actions_raw, self.stats_thunk = splice_stats(
                        self.file_actions_raw, self.stats_thunk)
                    if sp.recording:
                        sp.set_attr("bytes", self.file_actions_raw.column(
                            "stats").nbytes)
            return self.file_actions_raw

    @property
    def add_files_table(self) -> pa.Table:
        """Live files as an Arrow table (canonical schema): a copy of
        every column of every live row, for the callers that want them
        all. A scan with a filter plans over `live_rows` and copies
        only what it keeps (`live_subset`)."""
        if self._add_table_cache is None:
            with obs.span("state.add_files_table",
                          rows=len(self.live_mask)) as sp:
                table = self.file_actions  # state.splice_stats, if deferred
                with obs.span("state.filter_live", rows=table.num_rows,
                              **{"as": "table"}):
                    live = _filter_rows(table, self.live_mask)
                if sp.recording:
                    sp.set_attrs(live_rows=live.num_rows, bytes=live.nbytes)
                _LIVE_TABLE_BUILDS.inc()
                self._add_table_cache = live
        return self._add_table_cache

    @property
    def live_rows(self) -> np.ndarray:
        """Row numbers of the live files in `file_actions`, ascending:
        row `i` of `add_files_table`, and of the resident stats index,
        is row `live_rows[i]` of the rows held. The same narrowing as
        `add_files_table`'s, by index and not by copy: made once a
        state, under the same span."""
        if self._live_rows_cache is None:
            with obs.span("state.filter_live", rows=len(self.live_mask),
                          **{"as": "rows"}):
                self._live_rows_cache = np.flatnonzero(self.live_mask)
        return self._live_rows_cache

    def live_columns(self, names) -> pa.Table:
        """Columns `names` of `add_files_table`, filtered out of the
        rows held without building the rest: same rows, same order,
        same types. Under the line `_deal_small` draws it is the one
        filter on the calling thread; over it the columns are dealt
        over `scan_pool()` (`_dealt_live_columns`) and each comes back
        as one contiguous array. Not to be called from a task of that
        pool: the caller waits here for it."""
        table = self.file_actions.select(names)
        with obs.span("state.filter_live", rows=table.num_rows,
                      columns=table.num_columns, **{"as": "columns"}) as sp:
            if _deal_small(table.num_rows, table.num_columns):
                live = _filter_rows(table, self.live_mask)
                how = {"tasks": 1, "threads": 1, "serial_reason": "small"}
                _LIVE_COLUMNS_SERIAL.inc()
            else:
                live, how = _dealt_live_columns(table, self.live_mask)
                _LIVE_COLUMNS_DEALT.inc()
            sp.set_attrs(live_rows=live.num_rows, **how)
        return live

    def live_subset(self, keep: np.ndarray) -> pa.Table:
        """`add_files_table.filter(keep)` read straight out of the rows
        held, `keep` a mask over the live rows: same schema, same rows,
        same order. Few survivors are gathered by their row numbers;
        past a fixed share of the rows held a boolean filter of them
        is the faster way to the same table."""
        table = self.file_actions
        rows = self.live_rows[np.flatnonzero(keep)]
        if len(rows) * _GATHER_SHARE <= table.num_rows:
            return gather_rows(table, rows)
        mask = np.zeros(table.num_rows, dtype=bool)
        mask[rows] = True
        return _filter_rows(table, mask)

    @property
    def tombstones_table(self) -> pa.Table:
        if self._tombstone_table_cache is None:
            self._tombstone_table_cache = self.file_actions.filter(
                pa.array(self.tombstone_mask)
            )
        return self._tombstone_table_cache

    @property
    def num_files(self) -> int:
        return int(self.live_mask.sum())

    @property
    def size_in_bytes(self) -> int:
        # raw access on purpose: aggregates never touch stats, so they
        # must not trigger the deferred decode
        with obs.span("state.size_in_bytes", rows=len(self.live_mask)):
            sizes = np.asarray(
                self.file_actions_raw.column("size").fill_null(0),
                dtype=np.int64
            )
            return int(sizes[self.live_mask].sum())

    def visible_domain_metadata(self) -> Dict[str, DomainMetadata]:
        return {k: v for k, v in self.domain_metadata.items() if not v.removed}

    def add_files(self) -> list[AddFile]:
        """Materialize live files as AddFile objects (small results only —
        columnar consumers should use add_files_table)."""
        return [_row_to_add(r) for r in self.add_files_table.to_pylist()]

    def tombstones(self) -> list[RemoveFile]:
        return [_row_to_remove(r) for r in self.tombstones_table.to_pylist()]


def _row_dv(r) -> Optional[DeletionVectorDescriptor]:
    dv = r.get("deletion_vector")
    if dv is None or dv.get("storageType") is None:
        return None
    return DeletionVectorDescriptor(
        storageType=dv["storageType"],
        pathOrInlineDv=dv["pathOrInlineDv"],
        sizeInBytes=dv.get("sizeInBytes") or 0,
        cardinality=dv.get("cardinality") or 0,
        offset=dv.get("offset"),
        maxRowIndex=dv.get("maxRowIndex"),
    )


def _pv_dict(r) -> dict:
    pv = r.get("partition_values")
    if pv is None:
        return {}
    if isinstance(pv, list):  # arrow map -> list of (k, v)
        return {k: v for k, v in pv}
    return dict(pv)


def _row_to_add(r: dict) -> AddFile:
    import json as _json

    return AddFile(
        path=r["path"],
        partitionValues=_pv_dict(r),
        size=r.get("size") or 0,
        modificationTime=r.get("modification_time") or 0,
        dataChange=bool(r.get("data_change", True)),
        stats=r.get("stats"),
        tags=_json.loads(r["tags"]) if r.get("tags") else None,
        deletionVector=_row_dv(r),
        baseRowId=r.get("base_row_id"),
        defaultRowCommitVersion=r.get("default_row_commit_version"),
        clusteringProvider=r.get("clustering_provider"),
    )


def _row_to_remove(r: dict) -> RemoveFile:
    import json as _json

    return RemoveFile(
        path=r["path"],
        deletionTimestamp=r.get("deletion_timestamp"),
        dataChange=bool(r.get("data_change", True)),
        extendedFileMetadata=r.get("extended_file_metadata"),
        partitionValues=_pv_dict(r) or None,
        size=r.get("size"),
        stats=r.get("stats"),
        tags=_json.loads(r["tags"]) if r.get("tags") else None,
        deletionVector=_row_dv(r),
        baseRowId=r.get("base_row_id"),
        defaultRowCommitVersion=r.get("default_row_commit_version"),
    )


def build_replay_keys(file_actions: pa.Table) -> tuple[np.ndarray, np.ndarray]:
    """Dictionary-encode (path, dv_id) into two uint32 code arrays.

    Path codes are `pd.factorize(paths, sort=False)`'s, first appearance
    first, exact (Arrow's tables compare the bytes), coded over many small
    tables at once from `path_codes.DEAL_MIN_ROWS` rows
    (`replay/path_codes.py`); null dv_id maps to code 0, real ids to
    1+code."""
    n = file_actions.num_rows
    with obs.span("keys.combine", rows=n):
        paths = file_actions.column("path").combine_chunks()
    with obs.span("keys.factorize", rows=n) as sp:
        path_codes, engaged = first_appearance_codes(paths)
        if sp.recording:
            sp.set_attrs(**engaged)
    return path_codes, _dv_codes_only(file_actions)


def _dv_codes_only(file_actions: pa.Table) -> np.ndarray:
    """dv_id lane codes (0 = no DV) without touching the path column."""
    dv = file_actions.column("dv_id").combine_chunks()
    if dv.null_count == len(dv):
        return np.zeros(len(dv), dtype=np.uint32)
    codes, _ = pd.factorize(dv.to_pandas(), sort=False, use_na_sentinel=True)
    return (codes + 1).astype(np.uint32)


def _guarded_replay(columnar: ColumnarActions,
                    run_device) -> tuple[np.ndarray, np.ndarray]:
    """One device replay under the route contract; after an absorbed
    failure, the host twin under the calibration join."""
    from delta_tpu.resilience import device_faults

    out = device_faults.guarded("replay", run_device, _ROUTE_FALLBACKS)
    if out.fell_back is None:
        return out.value
    with obs.gate_observation("replay", "host"):
        return compute_masks_host(columnar)


def compute_masks_device(
    columnar: ColumnarActions, engine=None
) -> tuple[np.ndarray, np.ndarray]:
    from delta_tpu.ops.replay import replay_select
    from delta_tpu.parallel import gate

    fa = columnar.file_actions
    n = fa.num_rows
    if n == 0:
        z = np.zeros(0, bool)
        return z, z
    pending = columnar.pending_masks
    if pending is not None:
        # device replay was dispatched during columnarization (overlapped
        # with the Arrow assembly) — just collect the masks; a failed
        # overlapped dispatch degrades to the host twin like any other
        return _guarded_replay(columnar, pending.finish)
    keys = columnar.replay_keys
    fa_hint = None
    with obs.span("replay.keys", rows=n) as sp:
        if keys is not None and len(keys.path_code) == n:
            # the native scanner already dictionary-coded the paths in
            # first-appearance order and emitted the delta encoding —
            # skip the factorize pass entirely
            path_codes = keys.path_code
            dv_codes = _dv_codes_only(fa)
            fa_hint = (keys.path_new, keys.refs, keys.n_uniq)
        else:
            path_codes, dv_codes = build_replay_keys(fa)
        version = np.asarray(fa.column("version"), dtype=np.int64)
        # versions fit int32 in practice (2^31 commits); assert to be safe
        assert version.max(initial=0) < 2**31, "version overflow"
        order = np.asarray(fa.column("order"), dtype=np.int32)
        is_add = np.asarray(fa.column("is_add"), dtype=bool)
        if sp.recording:
            sp.set_attrs(factorized=fa_hint is None,
                         bytes=fa.column("path").nbytes)

    kernel = gate.replay_kernel(n, engine)
    if kernel == "host":
        # RTT-dominated tiny segment: dispatching to the device costs
        # more than the host-vectorized replay (gate.py's link model)
        with obs.gate_observation("replay", "host"):
            return compute_masks_host(columnar)

    def _run_device() -> tuple[np.ndarray, np.ndarray]:
        if kernel == "sharded-blockwise":
            # sharded AND >HBM: each shard streams its substream in
            # bounded blocks with a persistent bitset — the
            # `Snapshot.scala:481-511` multi-host configuration
            from delta_tpu.parallel.sharded_blockwise import (
                replay_select_sharded_blockwise,
            )

            live, tomb, _ = replay_select_sharded_blockwise(
                [path_codes, dv_codes], version.astype(np.int32),
                order, is_add, engine.mesh)
            return live, tomb
        if kernel == "sharded":
            from delta_tpu.parallel import resident as _resident
            from delta_tpu.parallel.sharded_replay import (
                sharded_replay_select,
            )

            sink = [] if _resident.enabled() else None
            live, tomb, _, _ = sharded_replay_select(
                path_codes, dv_codes, version.astype(np.int32), order,
                is_add, mesh=engine.mesh, fa_hint=fa_hint,
                resident_sink=sink,
            )
            if sink:
                # keep the per-shard state on device so Snapshot.update()
                # ships only delta rows (ownership moves to SnapshotState
                # in reconstruct_state)
                columnar.resident = _resident.establish_resident(
                    sink[0], fa, path_codes)
            return live, tomb
        if kernel == "single-blockwise":
            # >HBM scale path (SURVEY §5.7): stream fixed-size blocks
            # through the device with a persistent key bitset instead of
            # one giant sort
            from delta_tpu.ops.replay_blockwise import (
                replay_select_blockwise,
            )

            return replay_select_blockwise(
                [path_codes, dv_codes], version.astype(np.int32), order,
                is_add)
        return replay_select(
            [path_codes, dv_codes], version.astype(np.int32), order, is_add,
            fa_hint=fa_hint,
        )

    return _guarded_replay(columnar, _run_device)


def compute_masks_host(columnar: ColumnarActions) -> tuple[np.ndarray, np.ndarray]:
    """Sequential reference replay (`InMemoryLogReplay` semantics)."""
    fa = columnar.file_actions
    n = fa.num_rows
    live = np.zeros(n, dtype=bool)
    tomb = np.zeros(n, dtype=bool)
    if n == 0:
        return live, tomb
    with obs.span("replay.host", rows=n):
        paths = fa.column("path").to_pylist()
        dvs = fa.column("dv_id").to_pylist()
        version = np.asarray(fa.column("version"), dtype=np.int64)
        order = np.asarray(fa.column("order"), dtype=np.int32)
        is_add = np.asarray(fa.column("is_add"), dtype=bool)
        rows = sorted(range(n), key=lambda i: (version[i], order[i]))
        winner: dict = {}
        for i in rows:
            winner[(paths[i], dvs[i])] = i
        for i in winner.values():
            if is_add[i]:
                live[i] = True
            else:
                tomb[i] = True
    return live, tomb


SUPPORTED_READER_FEATURES = frozenset(
    {
        "deletionVectors",
        "columnMapping",
        "timestampNtz",
        "typeWidening",
        "typeWidening-preview",
        "v2Checkpoint",
        "vacuumProtocolCheck",
        "variantType",
        "variantType-preview",
        "inCommitTimestamp",
        "domainMetadata",
        "rowTracking",
        "clustering",
        "appendOnly",
        "invariants",
        "checkConstraints",
        "changeDataFeed",
        "generatedColumns",
        "identityColumns",
        "allowColumnDefaults",
        "icebergCompatV1",
        "icebergCompatV2",
        "liquid",
    }
)
MAX_READER_VERSION = 3


def check_read_supported(protocol: Protocol) -> None:
    """Protocol gate (PROTOCOL.md:844-876): reader version <= 3 and, at
    (3,7), every readerFeature must be implemented here."""
    if protocol.minReaderVersion > MAX_READER_VERSION:
        raise UnsupportedTableFeatureError(
            {f"readerVersion={protocol.minReaderVersion}"}, read=True
        )
    unsupported = protocol.reader_feature_set() - SUPPORTED_READER_FEATURES
    if unsupported:
        raise UnsupportedTableFeatureError(unsupported, read=True)


@dataclass
class SmallState:
    """Protocol/metadata/txn/domain/commitInfo resolution WITHOUT the
    file-level replay — checkpoint parquet is read with column
    projection so none of the add/remove bytes are decoded. The
    reference's P&M fast path (`Snapshot.scala:440`); serves
    metadata-only operations (schema reads, config lookups, blind-append
    transaction setup) on large tables in milliseconds."""

    version: int
    protocol: Protocol
    metadata: Metadata
    set_transactions: Dict[str, SetTransaction]
    domain_metadata: Dict[str, DomainMetadata]
    latest_commit_info: Optional[CommitInfo] = None
    commit_infos: Dict[int, CommitInfo] = field(default_factory=dict)
    timestamp_ms: int = 0


def reconstruct_small_state(engine, segment,
                            check_protocol: bool = True) -> SmallState:
    """Small-action-only reconstruction (see SmallState)."""
    columnar = columnarize_log_segment(engine, segment, small_only=True)
    if columnar.protocol is None or columnar.metadata is None:
        from delta_tpu.errors import DeltaError

        raise LogCorruptedError(
            f"log segment for version {segment.version} has no "
            f"{'protocol' if columnar.protocol is None else 'metadata'} action",
            error_class="DELTA_STATE_RECOVER_ERROR",
        )
    if check_protocol:
        check_read_supported(columnar.protocol)
    return SmallState(
        version=segment.version,
        protocol=columnar.protocol,
        metadata=columnar.metadata,
        set_transactions=columnar.set_transactions,
        domain_metadata=columnar.domain_metadata,
        latest_commit_info=columnar.latest_commit_info,
        commit_infos=columnar.commit_infos,
        timestamp_ms=segment.last_commit_timestamp,
    )


def advance_state(
    engine, prev: SnapshotState, delta: ColumnarActions, new_segment
) -> SnapshotState:
    """Replay a delta batch of commits ON TOP of a retained prior state
    — the incremental half of `update()` (SnapshotManagement log-segment
    deltas). Reuses the prior snapshot's columnar arrays: the new state's
    table is `concat(prev rows, delta rows)` (zero-copy) and only the
    delta keys' winners are recomputed; prior rows whose key is touched
    by the delta have their mask bits cleared. Produces a state
    bit-identical to a cold full replay at the same version.

    Callers must handle protocol changes BEFORE this (fallback to full
    replay) — a new protocol can change how existing actions are read.

    A resident stats index (`stats/device_index.py`) survives only an
    EMPTY delta: any landed file action releases it here, device copy
    and ledger entry at once. The new state keeps a seed of it
    (references to its lanes, to its parsed rows as it carried them,
    no table made of them here, and `prev`'s live mask),
    or the seed `prev` was itself still holding, and its first filtered
    scan makes the new index from that: the rows still live, and the
    stats of the rows landed since. The `update.advance` span says
    which happened (`stats_index`, `stats_index_seed`).
    """
    with obs.span("update.advance",
                  prev_rows=prev.file_actions_raw.num_rows) as sp:
        return _advance_state(engine, prev, delta, new_segment, sp)


def _advance_state(engine, prev, delta, new_segment, sp) -> SnapshotState:
    delta_fa = delta.file_actions_complete()  # delta stats: small, eager
    m = delta_fa.num_rows
    n_prev = prev.file_actions_raw.num_rows
    resident = prev.resident
    sp.set_attr("delta_rows", m)

    # the phases below are spans under `on` from PHASE_SPAN_ROWS rows held
    small = n_prev < obs.PHASE_SPAN_ROWS
    masks = None
    if m and resident is not None:
        with obs.span("advance.resident_append", _verbose=small,
                      rows=m) as ph:
            masks = resident.append(delta_fa, n_prev)
            ph.set_attr("appended", masks is not None)
    if m == 0:
        sp.set_attr("route", "empty")
        new_raw = prev.file_actions_raw
        live = prev.live_mask
        tomb = prev.tombstone_mask
        stats_thunk = prev.stats_thunk and _chained_prev_stats(prev, None)
    elif masks is not None:
        # device-resident path: only the delta rows crossed the link;
        # the device re-reconciled base+delta and the returned masks
        # already cover the concatenated table
        sp.set_attr("route", "resident")
        live, tomb = masks
        new_raw, stats_thunk, _ = _land_rows(prev, delta_fa)
    else:
        sp.set_attr("route", "host")
        if resident is not None:
            # the batch couldn't be expressed on device (DV rows,
            # capacity, ordering): residency ends here, host path takes
            # over for this and every later advancement
            resident.release()
            resident = None
            prev.resident = None
        live, tomb = _advance_masks_host(prev, delta_fa, small)
        with obs.span("advance.table", _verbose=small) as ph:
            new_raw, stats_thunk, merged = _land_rows(prev, delta_fa)
            ph.set_attrs(chunks=new_raw.column("path").num_chunks,
                         merged_rows=merged)
        with obs.span("advance.carry", _verbose=small,
                      commit_infos=len(prev.commit_infos)):
            return _carry_over(prev, delta, new_segment, new_raw, live, tomb,
                               stats_thunk, resident, sp)
    return _carry_over(prev, delta, new_segment, new_raw, live, tomb,
                       stats_thunk, resident, sp)


def _carry_over(prev, delta, new_segment, new_raw, live, tomb, stats_thunk,
                resident, sp) -> SnapshotState:
    """The new state round its rows and masks: what the delta did not
    replace carried over from `prev`, and what `prev` held on the
    device handed on (an empty delta) or released."""
    m = new_raw.num_rows - prev.file_actions_raw.num_rows
    set_txns = dict(prev.set_transactions)
    set_txns.update(delta.set_transactions)
    domains = dict(prev.domain_metadata)
    domains.update(delta.domain_metadata)
    commit_infos = dict(prev.commit_infos)
    commit_infos.update(delta.commit_infos)

    new_state = SnapshotState(
        version=new_segment.version,
        protocol=delta.protocol or prev.protocol,
        metadata=delta.metadata or prev.metadata,
        set_transactions=set_txns,
        domain_metadata=domains,
        file_actions_raw=new_raw,
        live_mask=live,
        tombstone_mask=tomb,
        latest_commit_info=delta.latest_commit_info or prev.latest_commit_info,
        commit_infos=commit_infos,
        timestamp_ms=new_segment.last_commit_timestamp,
        stats_thunk=stats_thunk,
        table_path=prev.table_path,
    )
    if resident is not None:
        # ownership moves: the append donated (mutated) the device
        # buffer, so the prior state's reference is stale by definition
        new_state.resident = resident
        prev.resident = None
    with prev._stats_index_lock:
        stats_index, seed = prev.stats_index, prev.stats_index_seed
        prev.stats_index = prev.stats_index_seed = None
    sp.set_attr("stats_index", "none" if stats_index is None
                else "carried" if m == 0 else "released")
    seed_is = "none" if seed is None else "passed_on"
    if stats_index is not None and m == 0:
        # empty delta: the live-file table is unchanged, so the index
        # is still exact — ownership moves like `resident`
        new_state.stats_index = stats_index
    elif stats_index is not None:
        # the prior version's lanes are stale: free the HBM now rather
        # than waiting for eviction. What the next scan of the new
        # state needs of them goes on as a seed: from here on prior
        # rows' live bits are only ever cleared and new rows land
        # behind them, so it stays exact across any number of advances
        seed = stats_index.seed(prev.live_mask)
        stats_index.release()
        seed_is = "none" if seed is None else "kept"
    sp.set_attr("stats_index_seed", seed_is)
    new_state.stats_index_seed = seed
    operand_cache = prev.operand_cache
    if operand_cache is not None:
        if m == 0:
            # empty delta: table content unchanged, the cached operand
            # lanes are still exact — ownership moves like `resident`
            new_state.operand_cache = operand_cache
            prev.operand_cache = None
        else:
            # version advance invalidates the per-(table, version,
            # column) artifacts; free the HBM now, the next device SQL
            # query over the new state re-uploads lazily
            operand_cache.release()
            prev.operand_cache = None
    return new_state


def _advance_masks_host(prev, delta_fa, small: bool):
    """(live, tombstone) masks over `prev`'s rows and the delta's behind
    them: the delta's own winners, and every held row that one of them
    supersedes cleared. Three phases, a span each (`small`: under
    `verbose` alone)."""
    import pyarrow.compute as pc

    from delta_tpu.ops.replay import delta_winner_masks

    n_prev = prev.file_actions_raw.num_rows
    with obs.span("advance.delta_keys", _verbose=small,
                  rows=delta_fa.num_rows) as ph:
        d_paths = delta_fa.column("path").to_pylist()
        d_dv = delta_fa.column("dv_id").to_pylist()
        d_keys = list(zip(d_paths, d_dv))
        d_live, d_tomb, winner = delta_winner_masks(
            d_keys,
            np.asarray(delta_fa.column("version"), np.int64),
            np.asarray(delta_fa.column("order"), np.int32),
            np.asarray(delta_fa.column("is_add"), bool),
        )
        ph.set_attr("winners", len(winner))
    with obs.span("advance.probe", _verbose=small, rows=n_prev) as ph:
        touched = sorted({p for p, _ in winner})
        cand = cleared = np.zeros(0, np.int64)
        if n_prev:
            # candidate prior rows: active AND path touched by the delta
            # (one vectorized hash probe over the big column; the exact
            # (path, dv_id) check runs only on the few candidates)
            hit = np.asarray(
                pc.is_in(prev.file_actions_raw.column("path"),
                         value_set=pa.array(touched, pa.string())
                         ).combine_chunks(),
                dtype=bool)
            cand = np.nonzero(hit & (prev.live_mask | prev.tombstone_mask))[0]
        if cand.size:
            # the candidates' two key columns, gathered out of the
            # chunks: `Table.take` concatenates every column of a
            # chunked table first (a copy of the whole held table at
            # every refresh; past 2 GiB in an Arrow `string` column it
            # cannot be done at all)
            sub = gather_rows(prev.file_actions_raw.select(
                ["path", "dv_id"]), cand)
            cleared = np.asarray(
                [j for j, p, dv in zip(cand,
                                       sub.column("path").to_pylist(),
                                       sub.column("dv_id").to_pylist())
                 if (p, dv) in winner], np.int64)
        ph.set_attrs(touched=len(touched), candidates=int(cand.size),
                     cleared=int(cleared.size))
    with obs.span("advance.masks", _verbose=small,
                  rows=n_prev + delta_fa.num_rows) as ph:
        prev_live = prev.live_mask.copy()
        prev_tomb = prev.tombstone_mask.copy()
        prev_live[cleared] = False
        prev_tomb[cleared] = False
        live = np.concatenate([prev_live, d_live])
        tomb = np.concatenate([prev_tomb, d_tomb])
        ph.set_attr("bytes", live.nbytes + tomb.nbytes)
    return live, tomb


def _land_rows(prev, delta_fa):
    """The held rows with the delta's behind them, the pending stats
    decode chained on, if there is one, and the rows of the table's end
    that were copied into one chunk (0: none)."""
    new_raw = pa.concat_tables([prev.file_actions_raw, delta_fa])
    stats_thunk = prev.stats_thunk and _chained_prev_stats(prev, delta_fa)
    merged = 0
    if not stats_thunk:
        # with a decode pending, the stats column still comes chunk by
        # chunk from the chain of thunks: merged then, the other
        # columns' chunks would no longer line up with its own
        new_raw, merged = _merge_small_chunks(new_raw)
    return new_raw, stats_thunk, merged


# Arrow's pool (mimalloc) keeps blocks of up to 2 GiB in its arenas; a
# filtered string column's buffer may double once, so a column is
# filtered whole only while it is under half of that
_FILTER_WHOLE_BYTES = 1 << 30
_FILTER_SLICE_BYTES = 1 << 28


def _widest_column(table: pa.Table) -> int:
    return max((col.nbytes for col in table.columns), default=0)


def _filter_rows(table: pa.Table, mask: np.ndarray) -> pa.Table:
    """`table.filter(mask)`; over a table with a column of more than
    1 GiB, a slice of rows at a time (zero-copy slices of ~256 MiB of
    that column, so the rows kept are copied once, as before). Arrow
    sizes a filtered string column's data buffer by the column's mean
    value length and doubles it when the rows kept turn out a byte
    longer: on a stats column of 1.2 GB (a table of a real row's width)
    that is a 2.4 GB block, past what Arrow's pool keeps in its arenas,
    so it is mapped, page-faulted and unmapped anew at every refresh,
    1.8 s where the filter takes 0.27 s, for as long as the lengths of
    the rows that went happen to fall that way (PERF.md, Findings,
    PR 33). By slices a buffer that doubles stays a block the pool
    keeps. A narrower table takes the one call it always took."""
    widest = _widest_column(table)
    if widest <= _FILTER_WHOLE_BYTES:
        return table.filter(pa.array(mask))
    rows = max(1, table.num_rows * _FILTER_SLICE_BYTES // widest)
    sliced = pa.Table.from_batches(table.to_batches(max_chunksize=rows),
                                   schema=table.schema)
    return sliced.filter(pa.array(mask))


# `live_columns` deals its filter from this many rows held x columns
# asked for: `log/parquet_stitch.py::small`'s own line, 100,000 rows of
# six columns, so 66,667 rows of a checkpoint's nine. Measured on the
# chip's host (13 cores; a state in the benchmark's shapes, the nine
# columns, four of them null throughout; one call / dealt in ms,
# medians of 25, the state in one chunk and in 41; PERF.md §6, PR 58):
# 1.8 / 1.5 and 6.1 / 6.9 at 25,000 rows, 3.2 / 1.7 and 8.3 / 6.3 at
# 50,000, 6.1 / 2.4 and 12.0 / 7.3 at 100,000, 30 / 11 and 36 / 13 at
# 400,000, 176 / 36 and 174 / 38 at 2.44M. Under the line a deal saves
# a millisecond or two at best and loses as much on a state in many
# chunks.
_DEAL_MIN_CELLS = 600_000
# A task filters about this much of one column, a wide column cut into
# ranges of rows: far under `_FILTER_SLICE_BYTES`, so the rule
# `_filter_rows` keeps for a column past 1 GiB (no filter sizes a
# string buffer it may double from more than ~256 MiB) holds by
# construction.
_DEAL_PIECE_BYTES = 16 << 20
# ... or this many rows, where that is less: a list's or a struct's
# filter costs by the row, whatever the bytes (an empty map a row,
# 10 MB over 2.4M rows, takes as long as 60 MB of strings)
_DEAL_PIECE_ROWS = 1 << 19


def _deal_small(rows: int, columns: int) -> bool:
    return rows * columns < _DEAL_MIN_CELLS


def _filter_piece(name: str, col: pa.ChunkedArray, keep: pa.Array,
                  lo: int, hi: int) -> list:
    """The kept rows of `col[lo:hi]`, chunk by chunk as they lie."""
    with obs.span("filter_live.piece", column=name, lo=lo,
                  rows=hi - lo) as sp:
        piece = col.slice(lo, hi - lo)
        if sp.recording:
            sp.set_attr("bytes", piece.nbytes)
        return piece.filter(keep.slice(lo, hi - lo)).chunks


def _concat_pieces(name: str, chunks: list) -> pa.Array:
    with obs.span("filter_live.concat", column=name, chunks=len(chunks)):
        return pa.concat_arrays(chunks)


def _string_offsets(chunk: pa.Array) -> np.ndarray:
    return np.frombuffer(chunk.buffers()[1], np.int32, len(chunk) + 1,
                         chunk.offset * 4)


def _copy_strings(name: str, chunks: list, offsets: np.ndarray,
                  data: np.ndarray, row: int, at: int) -> None:
    """`chunks`, neighbours, written behind one another from row `row`
    and byte `at` of the array being made: their values' bytes as they
    lie, their offsets moved to where those land."""
    with obs.span("filter_live.concat", column=name, chunks=len(chunks),
                  row=row) as sp:
        start = at
        for chunk in chunks:
            ends = _string_offsets(chunk)
            first, n = int(ends[0]), int(ends[-1]) - int(ends[0])
            np.add(ends[1:], at - first,
                   out=offsets[row + 1:row + len(chunk) + 1])
            if n:
                data[at:at + n] = np.frombuffer(chunk.buffers()[2], np.uint8,
                                                n, first)
            row, at = row + len(chunk), at + n
        sp.set_attr("bytes", at - start)


def _concat_tasks(pool, name: str, chunks: list) -> tuple:
    """The tasks that make a column's kept chunks one array, and what
    gives the array once they have ended. One `pa.concat_arrays` as a
    rule. That is one thread's copy of the whole column, and on a
    table of millions the stats strings' is the longest step of the
    hand-out by far: a `string` or `binary` column with no null among
    the rows kept, of two pieces' bytes or more, is laid out by hand,
    every run of chunks of about a piece copied into its place by a
    task of its own (numpy's copies let go of the interpreter's lock;
    PERF.md §6, PR 58: 244 MB of stats in 15 copies of 3.7 ms)."""
    typ = chunks[0].type
    if ((pa.types.is_string(typ) or pa.types.is_binary(typ))
            and not any(c.null_count for c in chunks)):
        sizes = [int(e[-1]) - int(e[0]) for e in map(_string_offsets, chunks)]
        total, rows = sum(sizes), sum(map(len, chunks))
        if 2 * _DEAL_PIECE_BYTES <= total < 1 << 31:
            offsets = pa.allocate_buffer(4 * (rows + 1))
            data = pa.allocate_buffer(total)
            out_offsets = np.frombuffer(offsets, np.int32)
            out_offsets[0] = 0
            out_data = np.frombuffer(data, np.uint8)
            # where each chunk lands; a run: the chunks that start in
            # the same piece's worth of bytes
            at = np.cumsum([0] + sizes[:-1])
            row = np.cumsum([0] + [len(c) for c in chunks[:-1]])
            copy = obs.wrap(_copy_strings)
            tasks = [pool.submit(copy, name, chunks[run[0]:run[-1] + 1],
                                 out_offsets, out_data, int(row[run[0]]),
                                 int(at[run[0]]))
                     for run in (list(g) for _, g in itertools.groupby(
                         range(len(chunks)),
                         key=lambda k: at[k] // _DEAL_PIECE_BYTES))]
            return tasks, lambda: pa.Array.from_buffers(
                typ, rows, [None, offsets, data])
    task = pool.submit(obs.wrap(_concat_pieces), name, chunks)
    return [task], task.result


def _dealt_live_columns(table: pa.Table, mask: np.ndarray):
    """`table.filter(mask)` with every column one contiguous array, and
    how it was made, for the span. A column that is null on every row
    held is not filtered: it is as many nulls as rows are kept. The
    others are filtered on `scan_pool()`, a task a column and, of a wide
    column, a range of rows, the heaviest first (it sets the pace, so it
    must not queue); then each column's kept chunks are made one array
    there (`_concat_tasks`). The same Arrow kernels over the same rows
    as the one call's, so the same values; a `string` column whose kept
    values pass 2 GiB raises Arrow's "offset overflow" here, as the
    checkpoint writer's `combine_chunks` of it did."""
    from delta_tpu.utils.threads import (
        default_scan_threads,
        scan_pool,
        settled,
    )

    rows, live = table.num_rows, int(np.count_nonzero(mask))
    keep, names, pool = pa.array(mask), table.column_names, scan_pool()
    filt = obs.wrap(_filter_piece)
    cuts, weight = {}, {}   # column -> its ranges' bounds; a range's bytes
    for i, col in enumerate(table.columns):
        if col.null_count < rows:
            nbytes = col.nbytes
            n = max(-(-nbytes // _DEAL_PIECE_BYTES),
                    -(-rows // _DEAL_PIECE_ROWS))
            cuts[i] = [rows * k // n for k in range(n + 1)]
            weight[i] = nbytes // n
    pieces = sorted(((i, lo, hi) for i, at in cuts.items()
                     for lo, hi in zip(at, at[1:])),
                    key=lambda p: -weight[p[0]])
    filters = {(i, lo): pool.submit(filt, names[i], table.column(i), keep,
                                    lo, hi) for i, lo, hi in pieces}
    # while the pool filters: the columns that need no filter
    columns = {i: pa.nulls(live, field.type)
               for i, field in enumerate(table.schema) if i not in cuts}
    null_columns = len(columns)
    settled(filters.values())
    joins = {}
    for i, at in cuts.items():
        chunks = [c for lo in at[:-1] for c in filters[i, lo].result()]
        if len(chunks) > 1:
            joins[i] = _concat_tasks(pool, names[i], chunks)
        else:               # one chunk, or no row kept
            columns[i] = (chunks[0] if chunks
                          else pa.nulls(0, table.schema.field(i).type))
    settled([task for tasks, _ in joins.values() for task in tasks])
    columns.update((i, array()) for i, (_, array) in joins.items())
    return pa.Table.from_arrays([columns[i] for i in range(len(names))],
                                schema=table.schema), {
        "null_columns": null_columns,
        "tasks": len(filters) + sum(len(t) for t, _ in joins.values()),
        "threads": default_scan_threads()}


# A gather copies only the rows it keeps, a filter walks every row held:
# the filter wins once the rows kept pass this share of them (1 / N)
_GATHER_SHARE = 4


def gather_rows(table: pa.Table, rows: np.ndarray) -> pa.Table:
    """The rows of `table` numbered `rows` (ascending), as
    `table.combine_chunks().take(rows)` gives them, without combining:
    each number is resolved against the chunks' offsets and only the
    chunks it touches are read, a run of neighbours as a slice (a
    commit's files, kept whole), scattered rows by a take. (`Table.take`
    on a chunked table concatenates every column before it takes,
    pyarrow 25.0: 68 ms for 6,000 rows of 3M in two chunks, where this
    takes under one.) A take costs ~0.1 ms a chunk touched whatever it
    takes, so where the columns lie in different chunks (a checkpoint's
    stats past 1 GiB stay the file's twenty, the narrow columns are one
    each: `replay/columnar.py::_extract_file_actions`) the columns of
    each layout are gathered together and apart from the others'."""
    if len({col.num_chunks for col in table.columns}) <= 1:
        return _gather_batches(table, rows)     # one layout, or as good
    layouts: dict = {}
    for i, col in enumerate(table.columns):
        layouts.setdefault(tuple(len(c) for c in col.chunks), []).append(i)
    columns = [None] * table.num_columns
    for mine in layouts.values():
        for i, col in zip(mine, _gather_batches(table.select(mine),
                                                rows).columns):
            columns[i] = col
    return pa.Table.from_arrays(columns, schema=table.schema)


def _gather_batches(table: pa.Table, rows: np.ndarray) -> pa.Table:
    """`gather_rows` of a table whose columns share their chunks (of
    any table, at a take for every stretch between two chunk ends of
    any column: `to_batches` cuts at them all)."""
    batches = table.to_batches()
    ends = np.cumsum([b.num_rows for b in batches])
    cuts = np.searchsorted(rows, ends)  # rows[cuts[i-1]:cuts[i]]: batch i's
    taken, lo, start = [], 0, 0
    for batch, end, hi in zip(batches, ends, cuts):
        if hi > lo:
            first, last = int(rows[lo]) - start, int(rows[hi - 1]) - start
            if last - first == hi - lo - 1:
                taken.append(batch.slice(first, hi - lo))
            else:
                taken.append(batch.take(pa.array(rows[lo:hi] - start)))
        lo, start = hi, end
    return pa.Table.from_batches(taken, schema=table.schema)


# An advance lands its commits' rows behind the table as one more
# chunk, and a scan resolves its survivors chunk by chunk
# (`gather_rows`): the small chunks at the table's end are merged once
# there are more than this many, so a held table keeps tens of chunks
# over any number of advances. A chunk that has grown to
# `_SMALL_CHUNK_ROWS` is left alone: no merge copies more than that and
# the chunks just landed, and the rows loaded first are never copied.
_MAX_SMALL_CHUNKS = 64
_SMALL_CHUNK_ROWS = 1 << 16


def _merge_small_chunks(table: pa.Table) -> tuple[pa.Table, int]:
    """`table` with the small chunks at its end made one, and the rows
    that copied (0 where there were too few to merge)."""
    lengths = [len(c) for c in max(
        table.columns, key=lambda col: col.num_chunks).chunks]
    small = rows = 0
    for n in reversed(lengths[1:]):     # the first chunk stays where it is
        if n >= _SMALL_CHUNK_ROWS:
            break
        small, rows = small + 1, rows + n
    if small <= _MAX_SMALL_CHUNKS:
        return table, 0
    at = table.num_rows - rows
    return pa.concat_tables([table.slice(0, at),
                             table.slice(at).combine_chunks()]), rows


def _chained_prev_stats(prev: SnapshotState, delta_fa: Optional[pa.Table]):
    """Deferred-stats chain for an advanced state: the prior state's
    pending decode runs (exactly once, under ITS splice lock) only when
    the NEW state's stats are first touched; the delta rows' stats are
    already real."""

    def thunk():
        col = prev.file_actions.column("stats")  # splices prev on demand
        chunks = list(col.chunks)
        if delta_fa is not None:
            chunks.extend(delta_fa.column("stats").chunks)
        return pa.chunked_array(chunks, pa.string())

    return thunk


def _table_root(log_path: Optional[str]) -> Optional[str]:
    """Table root for a ``.../_delta_log`` path (ledger attribution)."""
    if not log_path:
        return None
    trimmed = log_path.rstrip("/")
    if trimmed.endswith("_delta_log"):
        trimmed = trimmed[: -len("_delta_log")].rstrip("/")
    return trimmed or log_path


def reconstruct_state(engine, segment, check_protocol: bool = True) -> SnapshotState:
    """Full state reconstruction for a log segment."""
    from delta_tpu.metrics import SnapshotMetrics

    metrics = SnapshotMetrics()
    with metrics.columnarize_timer.time():
        columnar = columnarize_log_segment(engine, segment)
    if columnar.protocol is None or columnar.metadata is None:
        from delta_tpu.errors import DeltaError

        raise LogCorruptedError(
            f"log segment for version {segment.version} has no "
            f"{'protocol' if columnar.protocol is None else 'metadata'} action"
        )
    if check_protocol:
        check_read_supported(columnar.protocol)

    use_device = getattr(engine, "use_device_replay", False)
    with metrics.replay_timer.time():
        if use_device:
            live, tomb = compute_masks_device(columnar, engine)
        else:
            live, tomb = compute_masks_host(columnar)

    metrics.num_commit_files.increment(columnar.num_commit_files)
    metrics.num_checkpoint_parts.increment(len(segment.checkpoints))
    metrics.num_actions.increment(columnar.num_actions)
    metrics.bytes_parsed.increment(columnar.bytes_parsed)
    obs.set_attrs(
        num_actions=columnar.num_actions,
        num_commit_files=columnar.num_commit_files,
        num_checkpoint_parts=len(segment.checkpoints),
        bytes_parsed=columnar.bytes_parsed,
        replay_mode="device" if use_device else "host",
    )
    if getattr(engine, "metrics_reporters", None):
        engine.report_metrics(
            metrics.report(
                segment.log_path,
                segment.version,
                extra={"replayMode": "device" if use_device else "host"},
            )
        )

    state = SnapshotState(
        version=segment.version,
        protocol=columnar.protocol,
        metadata=columnar.metadata,
        set_transactions=columnar.set_transactions,
        domain_metadata=columnar.domain_metadata,
        file_actions_raw=columnar.file_actions,
        live_mask=live,
        tombstone_mask=tomb,
        latest_commit_info=columnar.latest_commit_info,
        commit_infos=columnar.commit_infos,
        timestamp_ms=segment.last_commit_timestamp,
        stats_thunk=columnar.stats_thunk,
        table_path=_table_root(segment.log_path),
    )
    # ownership of the deferred decode moves to the snapshot state
    columnar.stats_thunk = None
    # same for the device-resident sharded replay state, when one was
    # established during compute_masks_device
    state.resident = columnar.resident
    columnar.resident = None
    return state
