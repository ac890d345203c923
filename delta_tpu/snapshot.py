"""Snapshot: an immutable view of the table at one version.

Counterpart of kernel `SnapshotImpl.java` / spark `Snapshot.scala:81`.
State is reconstructed lazily on first access and cached on the object;
`Table` caches the newest snapshot and reuses it across `update()` calls
when the version is unchanged.
"""

from __future__ import annotations

import logging
import re
from typing import Dict, Optional

from delta_tpu import obs
from delta_tpu.log.segment import LogSegment
from delta_tpu.obs import hbm
from delta_tpu.models.actions import DomainMetadata, Metadata, Protocol, SetTransaction
from delta_tpu.replay.state import (
    SmallState,
    SnapshotState,
    reconstruct_small_state,
    reconstruct_state,
)

_log = logging.getLogger(__name__)

_CHECKPOINT_FALLBACKS = obs.counter("snapshot.checkpoint_fallbacks")
_TORN_FALLBACKS = obs.counter("snapshot.torn_commit_fallbacks")
_CRC_QUARANTINED = obs.counter("snapshot.crc_quarantined")
# why an update() could not advance the retained state, as the
# `snapshot.update` span's `reason` names it
_UPDATE_FALLBACKS = {
    "checkpoint": obs.counter("snapshot.update_fallbacks.checkpoint"),
    "compacted_delta": obs.counter(
        "snapshot.update_fallbacks.compacted_delta"),
    "gap": obs.counter("snapshot.update_fallbacks.gap"),
    "protocol": obs.counter("snapshot.update_fallbacks.protocol"),
    "no_state": obs.counter("snapshot.update_fallbacks.no_state"),
}

# a commit file in a FileNotFoundError message (vs a checkpoint part)
_COMMIT_JSON_RE = re.compile(r"\d{20}\.json")


class Snapshot:
    def __init__(self, table, segment: LogSegment, engine=None):
        self._table = table
        self._segment = segment
        self._engine = engine if engine is not None else table.engine
        self._state: Optional[SnapshotState] = None
        self._small: Optional[SmallState] = None
        self._pm: Optional[SmallState] = None  # crc-derived P&M only

    @property
    def version(self) -> int:
        return self._segment.version

    @property
    def log_segment(self) -> LogSegment:
        return self._segment

    @property
    def table_path(self) -> str:
        return self._table.path

    @property
    def state(self) -> SnapshotState:
        if self._state is None:
            with obs.span("snapshot.load", table=self._table.path,
                          version=self.version):
                self._state = self._load_state()
        return self._state

    def _load_state(self) -> SnapshotState:
        # ambient table attribution for any device artifact the replay
        # establishes (resident key lanes, checkpoint handoff lanes)
        with hbm.table_scope(self._table.path):
            state = self._replay_degrading(reconstruct_state)
        self._validate_crc(state)
        return state

    def _replay_degrading(self, replay_fn):
        """Run one replay function (full or small state) over the
        segment with the degradation ladder: a corrupt or incomplete
        checkpoint falls back to the previous complete checkpoint (or
        pure JSON replay), and a torn trailing commit — an interrupted
        writer's half-line, not a real commit — falls back to the last
        intact version. Both paths warn and count; corruption that no
        fallback can route around still raises. On fallback the
        snapshot's segment is replaced so later accesses reuse the
        repaired view."""
        import pyarrow as pa

        from delta_tpu.errors import LogCorruptedError, TornCommitError
        from delta_tpu.log.segment import build_log_segment

        seg = self._segment
        while True:
            try:
                state = replay_fn(self._engine, seg)
                break
            except TornCommitError as e:
                torn_v = e.context.get("version")
                if torn_v is None or torn_v != seg.version or torn_v <= 0:
                    # torn line below the tip: the log itself is
                    # damaged, no earlier version is trustworthy
                    raise
                _TORN_FALLBACKS.inc()
                _log.warning(
                    "commit %d of %s has a torn trailing line "
                    "(interrupted write); serving version %d",
                    torn_v, self._table.path, torn_v - 1)
                seg = build_log_segment(
                    self._engine.fs, seg.log_path,
                    target_version=torn_v - 1)
            except (LogCorruptedError, pa.ArrowException,
                    OSError) as e:
                # OSError covers pyarrow's footer/thrift damage too:
                # decoders raise it bare (not via ArrowException) when
                # the parquet magic or metadata length is garbled
                if not seg.checkpoints:
                    raise
                if isinstance(e, FileNotFoundError) and \
                        _COMMIT_JSON_RE.search(str(e)):
                    # a vanished commit file is not a checkpoint
                    # problem — excluding the checkpoint cannot bring
                    # the commit back, so don't burn a rebuild on it
                    raise
                cp_v = seg.checkpoint_version
                _CHECKPOINT_FALLBACKS.inc()
                _log.warning(
                    "checkpoint %d of %s unreadable (%s); rebuilding "
                    "from an earlier checkpoint or the JSON log",
                    cp_v, self._table.path, e)
                seg = build_log_segment(
                    self._engine.fs, seg.log_path,
                    target_version=seg.version,
                    max_checkpoint_version=cp_v - 1)
        if seg is not self._segment:
            self._segment = seg
        return state

    def _validate_crc(self, state: SnapshotState) -> None:
        """Check the replayed state against this version's `.crc` file
        when one exists. A mismatch means the checksum chain is lying —
        quarantine it by reseeding from the (authoritative) replayed
        state, warn and count, and never fail the read: the .crc is an
        accelerator, the log is the source of truth."""
        from delta_tpu.errors import ChecksumMismatchError
        from delta_tpu.log.checksum import (
            read_checksum,
            validate_state_against_checksum,
            write_checksum_from_state,
        )

        try:
            crc = read_checksum(self._engine.fs, self._table.log_path,
                                state.version)
        except Exception as e:
            _log.debug("checksum read failed at version %d (%s)",
                       state.version, e)
            return
        if crc is None:
            return
        try:
            validate_state_against_checksum(state, crc)
        except ChecksumMismatchError as e:
            _CRC_QUARANTINED.inc()
            _log.warning(
                "checksum at version %d of %s disagrees with replayed "
                "state (%s); quarantining by reseeding from state",
                state.version, self._table.path, e)
            try:
                write_checksum_from_state(self._engine,
                                          self._table.log_path, state)
            except Exception as e2:
                _log.debug("checksum reseed failed: %s", e2)

    @property
    def _small_state(self):
        """Small actions WITHOUT the file replay (P&M fast path,
        `Snapshot.scala:440`): metadata-only consumers on a large table
        never pay for decoding the checkpoint's add/remove columns. The
        full state, once materialized, serves as the small state too.

        Behind a checkpoint the read asks each part for the small
        columns with `present_only` (`ParquetHandler.read_parquet_files`):
        a hint that only rows holding a small action are wanted, which
        the host handler answers from the part's footer (the row groups
        that can hold one, read until the last one counted) and any
        other handler may ignore. Its one limit is stated there: a
        small action whose every leaf is null is invisible to leaf
        statistics, and no valid checkpoint holds one."""
        if self._state is not None:
            return self._state
        if self._small is None:
            if not self._segment.checkpoints:
                # JSON-only segment: the small projection saves no I/O
                # (there are no parquet columns to skip), but a later
                # full-state access would re-read and re-parse the whole
                # log — reconstruct once and serve both
                with obs.span("snapshot.load", table=self._table.path,
                              version=self.version):
                    self._state = self._load_state()
                return self._state
            with obs.span("snapshot.load_small", table=self._table.path,
                          version=self.version):
                # same degradation ladder as the full load: the small
                # projection reads the same checkpoint parts and commit
                # tail, so a torn artifact must fall back here too
                self._small = self._replay_degrading(reconstruct_small_state)
        return self._small

    @property
    def _pm_state(self):
        """Cheapest protocol/metadata source: full state if present,
        else an already-parsed small state, else this version's `.crc`
        checksum (one tiny read — the reference ChecksumReader path,
        `LogReplay.java:384-426`), else the small-action parse. Only
        protocol/metadata/timestamp come from a crc-derived view — txn
        and domain accessors always use the real small state."""
        if self._state is not None:
            return self._state
        if self._small is not None:
            return self._small
        if self._pm is None:
            from delta_tpu.log.checksum import read_checksum

            try:
                crc = read_checksum(self._engine.fs, self._table.log_path,
                                    self.version)
            except Exception as e:
                # the .crc is an accelerator: unreadable/corrupt means
                # fall back to log replay, never fail the read
                _log.debug("checksum read failed at version %d (%s); "
                           "using log replay", self.version, e)
                crc = None
            if crc is not None:
                from delta_tpu.config import IN_COMMIT_TIMESTAMPS, get_table_config
                from delta_tpu.replay.state import check_read_supported

                if (get_table_config(crc.metadata.configuration,
                                     IN_COMMIT_TIMESTAMPS)
                        and crc.inCommitTimestamp is None):
                    # an older crc without the ICT can't serve
                    # timestamp_ms on an ICT table (monotonicity feeds
                    # the next commit's ICT): use the real small parse
                    return self._small_state
                check_read_supported(crc.protocol)
                ts = self._segment.last_commit_timestamp
                if crc.inCommitTimestamp is not None:
                    ts = crc.inCommitTimestamp
                self._pm = SmallState(
                    version=self.version,
                    protocol=crc.protocol,
                    metadata=crc.metadata,
                    set_transactions={},
                    domain_metadata={},
                    timestamp_ms=ts,
                )
            else:
                return self._small_state
        return self._pm

    @property
    def protocol(self) -> Protocol:
        return self._pm_state.protocol

    @property
    def metadata(self) -> Metadata:
        return self._pm_state.metadata

    @property
    def schema(self):
        return self._pm_state.metadata.schema

    @property
    def partition_columns(self) -> list:
        return list(self._pm_state.metadata.partitionColumns)

    @property
    def timestamp_ms(self) -> int:
        """Commit timestamp of this version: in-commit timestamp when the
        feature is enabled, else file modification time."""
        pm = self._pm_state
        ci = pm.commit_infos.get(self.version)
        if ci is not None and ci.inCommitTimestamp is not None:
            return ci.inCommitTimestamp
        return pm.timestamp_ms

    @property
    def num_files(self) -> int:
        return self.state.num_files

    @property
    def size_in_bytes(self) -> int:
        return self.state.size_in_bytes

    def set_transaction_version(self, app_id: str) -> Optional[int]:
        txn = self._small_state.set_transactions.get(app_id)
        return txn.version if txn else None

    def set_transactions(self) -> Dict[str, SetTransaction]:
        return dict(self._small_state.set_transactions)

    def domain_metadata(self, domain: str) -> Optional[DomainMetadata]:
        dm = self._small_state.domain_metadata.get(domain)
        if dm is None or dm.removed:
            return None
        return dm

    def update(self, engine=None) -> Optional["Snapshot"]:
        """Incrementally advance to the latest version: LIST only commits
        past this one, parse just those, and replay them ON TOP of this
        snapshot's retained state (`SnapshotManagement.updateAfterCommit`
        semantics — one prefix listing, O(new commits) work).

        Returns `self` when nothing new landed (zero reads, zero
        parses), a new Snapshot sharing this one's columnar arrays when
        commits appended cleanly, or None when this snapshot's segment
        cannot be extended — a checkpoint/compaction boundary
        intervened, a listing gap appeared, or the protocol changed —
        and the caller must get the new version's segment another way:
        a full `latest_snapshot()` load, or at a checkpoint what
        `Table.update` does, which lists the segment anew and advances
        this snapshot's state over the commits between. The advanced
        state is bit-identical to a cold replay at the same version.
        """
        return self._update(engine)[0]

    def _update(self, engine=None):
        """`update()`, with the reason beside the snapshot where the
        retained state could not be advanced (`Table.update` names it
        on its own span): `checkpoint`, `compacted_delta`, `gap`,
        `protocol` or `no_state`, else None."""
        from delta_tpu.log.segment import (
            _IncrementalUnavailable,
            extend_log_segment,
        )

        eng = engine if engine is not None else self._engine
        with obs.span("snapshot.update", table=self._table.path,
                      from_version=self.version) as sp:
            try:
                ext = extend_log_segment(eng.fs, self._segment)
            except _IncrementalUnavailable as e:
                advanced, reason = None, e.reason
            else:
                if ext is None:
                    sp.set_attr("outcome", "unchanged")
                    return self, None
                new_segment, new_deltas = ext
                advanced, reason = self._update_advance(
                    eng, new_segment, new_deltas)
            if reason is None:
                sp.set_attrs(outcome="advanced",
                             to_version=new_segment.version,
                             new_commits=len(new_deltas))
            else:
                sp.set_attrs(outcome="fallback_full_load", reason=reason)
                _UPDATE_FALLBACKS[reason].inc()
            return advanced, reason

    def _update_advance(self, eng, new_segment, new_deltas):
        """(snapshot, None), or (what `update()` returns, the reason)
        where nothing retained was advanced. `new_segment` is the new
        version's, extended or listed anew; `new_deltas` are the commits
        from this version to it."""
        if self._state is None:
            # no replayed state retained to advance — a lazy snapshot
            # over the extended segment costs the same as advancing
            # would, and the parsed-commit cache still spares any
            # re-parse of commits this segment shares with prior loads
            return (Snapshot(self._table, new_segment, self._engine),
                    "no_state")

        import dataclasses

        from delta_tpu.replay.columnar import columnarize_log_segment
        from delta_tpu.replay.state import advance_state

        delta_seg = dataclasses.replace(
            new_segment,
            deltas=new_deltas,
            checkpoints=[],
            compacted_deltas=[],
            checkpoint_version=None,
        )
        # early_replay=False: the delta is replayed host-side by
        # advance_state; an early device dispatch would go unused
        delta = columnarize_log_segment(eng, delta_seg, early_replay=False)
        if delta.protocol is not None:
            # a protocol change can alter how existing actions must be
            # read — never replay across it incrementally
            return None, "protocol"
        with hbm.table_scope(self._table.path):
            new_state = advance_state(eng, self._state, delta, new_segment)
        snap = Snapshot(self._table, new_segment, self._engine)
        snap._state = new_state
        return snap, None

    def _advanced_with_blobs(self, blobs) -> Optional["Snapshot"]:
        """Advance with commit bytes already in memory (the post-commit
        fast path: a transaction hands over the actions it just wrote,
        so its own commit is never re-listed or re-read). `blobs` is
        [(version, bytes)] contiguous from `self.version + 1`. Returns
        None when this snapshot can't host the advancement (no retained
        state, version gap, or a protocol change in the blobs)."""
        if self._state is None:
            return None
        versions = [v for v, _ in blobs]
        if versions != list(range(self.version + 1,
                                  self.version + 1 + len(blobs))):
            return None
        with obs.span("snapshot.advance_blobs", table=self._table.path,
                      from_version=self.version, commits=len(blobs)):
            return self._advance_with_blobs_inner(blobs, versions)

    def _advance_with_blobs_inner(self, blobs, versions):

        import dataclasses
        import time

        from delta_tpu.replay.columnar import columnarize_commit_blobs
        from delta_tpu.replay.state import advance_state
        from delta_tpu.storage.logstore import FileStatus
        from delta_tpu.utils import filenames

        delta = columnarize_commit_blobs(blobs)
        if delta.protocol is not None:
            return None
        fs = self._engine.fs
        files = []
        last_ts = self._segment.last_commit_timestamp
        for v, data in blobs:
            path = filenames.delta_file(self._table.log_path, v)
            try:
                mtime = fs.file_status(path).modification_time
            except OSError:
                mtime = int(time.time() * 1000)
            files.append(FileStatus(path, len(data), mtime))
            last_ts = max(last_ts, mtime)
        new_segment = dataclasses.replace(
            self._segment,
            version=versions[-1],
            deltas=list(self._segment.deltas) + files,
            last_commit_timestamp=last_ts,
        )
        with hbm.table_scope(self._table.path):
            new_state = advance_state(self._engine, self._state, delta,
                                      new_segment)
        snap = Snapshot(self._table, new_segment, self._engine)
        snap._state = new_state
        return snap

    def scan_builder(self):
        from delta_tpu.scan import ScanBuilder

        return ScanBuilder(self)

    def scan(self, filter=None, columns=None):
        b = self.scan_builder()
        if filter is not None:
            b = b.with_filter(filter)
        if columns is not None:
            b = b.with_columns(columns)
        return b.build()

    def table_configuration(self) -> Dict[str, str]:
        return dict(self._pm_state.metadata.configuration)

    def get_config(self, key: str, default=None):
        from delta_tpu.config import TABLE_CONFIGS

        cfg = TABLE_CONFIGS.get(key)
        raw = self._pm_state.metadata.configuration.get(key)
        if cfg is not None:
            return cfg.parse(raw) if raw is not None else (
                cfg.default if default is None else default
            )
        return raw if raw is not None else default

    def __repr__(self):
        return f"Snapshot(path={self._table.path!r}, version={self.version})"
