"""Table: entry point handle for a Delta table at a path.

Combines the roles of kernel `Table.java:32` (forPath / getLatestSnapshot
/ getSnapshotAsOfVersion / getSnapshotAsOfTimestamp / checkpoint /
createTransactionBuilder) and the spark `DeltaLog` singleton (snapshot
caching + update()).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from delta_tpu import obs
from delta_tpu.engine.tpu import default_engine
from delta_tpu.errors import DeltaError, TableNotFoundError
from delta_tpu.log.last_checkpoint import read_last_checkpoint
from delta_tpu.log.segment import (
    _IncrementalUnavailable,
    build_log_segment,
    list_commits_after,
)
from delta_tpu.snapshot import Snapshot
from delta_tpu.utils import filenames

_log = logging.getLogger(__name__)

# When `update()` meets a checkpoint past the held version it advances
# the held state over the commits between and lists the new version's
# segment anew; nothing of the checkpoint is read. The full load stays
# the way a held state sheds rows it no longer needs and the cheaper
# way to catch up from far behind: two fixed rules, weighed from what
# the state and the listing show (`Table._cross_checkpoint`;
# docs/incremental_update.md has the sums).
#
# A state gains its commits' rows at every advance and never drops one.
# Once the rows that are neither live nor a tombstone (an add since
# removed or re-added, a remove whose file came back: what a cold load
# would not hold) pass this share of the rows held, the crossing
# reloads. Every new tombstone supersedes a row, so this bounds what a
# long-lived reader keeps of expired tombstones too. At the
# benchmark's table (2.4M rows, 100 a commit, 20 of them superseding):
# one 4.3 s reload (ledger, PR 31: `crossing_refresh_ms` 4,457) in
# 40,000 commits, among 4,000 crossings of 0.5 s.
CROSSING_MAX_SUPERSEDED_SHARE = 1 / 8
# Replaying a commit on the host costs ~0.6 ms (PERF.md §4: 5,000
# commits in 3.14 s on the host route), reloading and re-indexing a
# held row ~1.8 us (ledger, PR 31: 4,457 ms a crossing at 2.4M rows):
# a commit is worth 333 rows. A reader more commits behind than its
# rows are worth loads the checkpoint: at 2.4M rows from 7,200 commits,
# a day of a writer's commits every 12 s.
CROSSING_ROWS_PER_COMMIT = 333

_CROSSINGS = obs.counter("snapshot.checkpoint_crossings")
_CROSSING_RELOADS = obs.counter("snapshot.checkpoint_crossing_reloads")


class Table:
    def __init__(self, path: str, engine=None):
        self.path = path.rstrip("/")
        self.engine = engine if engine is not None else default_engine()
        self.log_path = f"{self.path}/{filenames.LOG_DIR_NAME}"
        self._lock = threading.Lock()
        self._cached_snapshot: Optional[Snapshot] = None
        self._coordinated = False  # learned from the last metadata read

    @staticmethod
    def for_path(path: str, engine=None) -> "Table":
        return Table(path, engine)

    def exists(self) -> bool:
        try:
            build_log_segment(self.engine.fs, self.log_path)
            return True
        # delta-lint: disable=except-swallow (audited: the contract is
        # "is there a readable Delta table here" — a missing log dir and
        # a malformed one both answer no, whatever the exception type)
        except Exception:
            return False

    # -- snapshots ----------------------------------------------------------

    def latest_snapshot(self) -> Snapshot:
        """LIST the log (from the `_last_checkpoint` hint) and return the
        newest snapshot; reuses the cached state when the version is
        unchanged. Coordinated-commit tables additionally merge the
        coordinator's unbackfilled commits (`Snapshot.scala:166-220`)."""
        with obs.span("table.latest_snapshot", table=self.path) as sp:
            hint = read_last_checkpoint(self.engine.fs, self.log_path)
            segment = build_log_segment(
                self.engine.fs,
                self.log_path,
                target_version=None,
                checkpoint_hint=hint.version if hint else None,
            )
            sp.set_attr("version", segment.version)
            with self._lock:
                cached = self._cached_snapshot
            if (
                cached is not None
                and cached.version == segment.version
                and not self._coordinated
            ):
                sp.set_attr("cache_hit", True)
                return cached
            snap = Snapshot(self, segment)
            merged = self._merge_unbackfilled(snap, segment)
            if merged is not segment:
                snap = Snapshot(self, merged)
            with self._lock:
                cached = self._cached_snapshot
                if cached is not None and cached.version == snap.version:
                    return cached
                self._cached_snapshot = snap
                return snap

    def _merge_unbackfilled(self, probe: Snapshot, segment):
        """Extend the listed segment with the commit coordinator's
        unbackfilled `_commits/` files, when the table uses one."""
        try:
            meta_conf = probe.metadata.configuration
        except Exception as e:
            _log.debug("metadata probe failed while merging unbackfilled "
                       "commits (%s); using listed segment", e)
            return segment
        from delta_tpu.coordinatedcommits import coordinator_for_table

        try:
            coordinator = coordinator_for_table(meta_conf)
        except KeyError:
            return segment
        self._coordinated = coordinator is not None
        if coordinator is None:
            return segment
        from delta_tpu.resilience import breaker_for, default_policy

        resp = default_policy().call(
            lambda: coordinator.get_commits(self.log_path,
                                            segment.version + 1),
            breaker=breaker_for("commit-coordinator"))
        extra = []
        next_v = segment.version + 1
        for c in sorted(resp.commits, key=lambda c: c.version):
            if c.version == next_v:
                extra.append(c.file_status)
                next_v += 1
        if not extra:
            return segment
        import dataclasses

        return dataclasses.replace(
            segment,
            version=next_v - 1,
            deltas=list(segment.deltas) + extra,
            last_commit_timestamp=max(
                segment.last_commit_timestamp,
                max(f.modification_time for f in extra),
            ),
        )

    def update(self) -> Snapshot:
        """Return the latest snapshot, advancing the cached one
        incrementally when possible (the `DeltaLog.update()` fast path):
        LIST only commits past the cached version and replay just those
        on top of the retained state. Where a checkpoint has landed past
        the cached version the state is advanced all the same and the
        segment listed anew, as a cold load would list it
        (`_cross_checkpoint`). Falls back to the full
        `latest_snapshot()` load when there is no usable cached snapshot
        or incremental maintenance is unavailable (compacted delta,
        listing gap, protocol change, coordinated tables), and at a
        checkpoint when the held state has rows enough to shed or
        commits enough to replay that the load is the better way."""
        with obs.span("table.update", table=self.path) as sp:
            with self._lock:
                cached = self._cached_snapshot
            if cached is None or self._coordinated:
                sp.set_attrs(outcome="full_load", reason=(
                    "no_state" if cached is None else "coordinated"))
                return self.latest_snapshot()
            advanced, reason = cached._update()
            if reason == "checkpoint":
                advanced, reason = self._cross_checkpoint(cached, sp)
            if reason is not None:
                sp.set_attrs(outcome="full_load", reason=reason)
            else:
                sp.set_attr("outcome", "unchanged" if advanced is cached
                            else "advanced")
            if advanced is None:
                # full-load fallback: the cached snapshot's device-
                # resident replay state (if any) can't be advanced
                # across the boundary and would leak HBM — release it
                from delta_tpu.parallel.resident import (
                    release_snapshot_resident,
                )

                release_snapshot_resident(cached)
                return self.latest_snapshot()
            if advanced is not cached:
                with self._lock:
                    cur = self._cached_snapshot
                    if cur is None or cur.version <= advanced.version:
                        self._cached_snapshot = advanced
                    else:
                        advanced = cur  # a racing full load got further
            return advanced

    def _cross_checkpoint(self, cached: Snapshot, sp):
        """`update()` where a checkpoint is listed past `cached`: a
        checkpoint at `v` is the replay of every commit up to `v`, which
        is what the held state is once it has been advanced over them.
        (snapshot, None) with the state advanced over the commits past
        `cached.version` and the segment `build_log_segment` lists for
        the last of them; (None, why the table is loaded in full
        instead): `checkpoint` where the state cannot be advanced (none
        retained, a commit missing, a protocol action among them, with
        `not_advanced` beside it), `superseded_rows` or `commits_behind`
        where it could and the load is the better way."""
        def not_advanced(why):
            sp.set_attr("not_advanced", why)
            return None, "checkpoint"

        fs = self.engine.fs
        state = cached._state
        if state is None:
            return not_advanced("no_state")
        try:
            commits = list_commits_after(fs, cached.log_segment)
        except _IncrementalUnavailable:
            commits = []
        if not commits:
            return not_advanced("gap")
        rows = state.file_actions_raw.num_rows
        superseded = (rows - int(state.live_mask.sum())
                      - int(state.tombstone_mask.sum()))
        if superseded > rows * CROSSING_MAX_SUPERSEDED_SHARE:
            _CROSSING_RELOADS.inc()
            return None, "superseded_rows"
        if len(commits) * CROSSING_ROWS_PER_COMMIT > rows:
            _CROSSING_RELOADS.inc()
            return None, "commits_behind"
        # the segment of the last commit THIS listing saw: one that
        # lands before the next listing is the next update()'s
        target = filenames.delta_version(commits[-1].path)
        hint = read_last_checkpoint(fs, self.log_path)
        try:
            segment = build_log_segment(
                fs, self.log_path, target_version=target,
                checkpoint_hint=(
                    hint.version
                    if hint is not None and hint.version <= target
                    else cached.log_segment.checkpoint_version))
        except DeltaError:  # clean-up raced the second listing
            return not_advanced("gap")
        advanced, why = cached._update_advance(self.engine, segment, commits)
        if why is not None:
            return not_advanced(why)
        _CROSSINGS.inc()
        sp.set_attrs(crossed="checkpoint", commits=len(commits))
        return advanced, None

    def notify_commit(self, version: int, data: bytes) -> None:
        """Post-commit handoff: a transaction that just wrote commit
        `version` gives its serialized actions to the snapshot cache, so
        the next `update()` (and the post-commit hooks) advance without
        re-listing or re-reading the commit this process just produced
        (`SnapshotManagement.updateAfterCommit`). Best-effort: any
        failure leaves the cache untouched and the next poll takes the
        normal path. Never raises."""
        try:
            with self._lock:
                cached = self._cached_snapshot
            if (cached is None or self._coordinated
                    or cached.version != version - 1
                    or cached._state is None):
                return
            advanced = cached._advanced_with_blobs([(version, data)])
            if advanced is None:
                return
            with self._lock:
                if self._cached_snapshot is cached:
                    self._cached_snapshot = advanced
        except Exception as e:
            # the handoff is purely an optimization: the next update()
            # rebuilds from the log if the delta-replay advance failed
            _log.debug("post-commit snapshot advance to version %d "
                       "failed (%s); next update() will list", version, e)

    def snapshot_at(self, version: int) -> Snapshot:
        hint = read_last_checkpoint(self.engine.fs, self.log_path)
        cp_hint = hint.version if hint and hint.version <= version else None
        try:
            segment = build_log_segment(
                self.engine.fs,
                self.log_path,
                target_version=version,
                checkpoint_hint=cp_hint,
            )
        except Exception as e:
            # hint past target or cleaned log — retry with full listing
            _log.debug("hinted listing for version %d failed (%s); "
                       "retrying without checkpoint hint", version, e)
            segment = build_log_segment(
                self.engine.fs, self.log_path, target_version=version, checkpoint_hint=None
            )
        return Snapshot(self, segment)

    snapshot_as_of_version = snapshot_at

    def snapshot_as_of_timestamp(self, timestamp_ms: int) -> Snapshot:
        """Latest version committed at or before `timestamp_ms`
        (`DeltaHistoryManager.getActiveCommitAtTime` semantics)."""
        from delta_tpu.history import version_at_timestamp

        version = version_at_timestamp(self, timestamp_ms)
        return self.snapshot_at(version)

    # -- transactions -------------------------------------------------------

    def create_transaction_builder(self, operation: str = "WRITE", engine_info: str = None):
        from delta_tpu.txn.transaction import TransactionBuilder

        return TransactionBuilder(self, operation=operation, engine_info=engine_info)

    def start_transaction(self, operation: str = "WRITE"):
        return self.create_transaction_builder(operation).build()

    # -- maintenance --------------------------------------------------------

    def checkpoint(self, version: Optional[int] = None) -> None:
        """Write a checkpoint for `version` (default: latest)."""
        from delta_tpu.log.checkpointer import write_checkpoint
        from delta_tpu.log.checksum import write_checksum_from_state

        try:
            snap = (self.latest_snapshot() if version is None
                    else self.snapshot_at(version))
        except TableNotFoundError as e:
            from delta_tpu.errors import CheckpointError

            raise CheckpointError(
                f"cannot checkpoint a non-existent table: {e}") from e
        from delta_tpu.log.last_checkpoint import read_last_checkpoint

        with obs.span("table.checkpoint", table=self.path,
                      version=snap.version):
            # the previous hint's partManifest lets the writer reuse
            # unchanged parts/sidecars (best-effort: None → full write)
            prev = read_last_checkpoint(self.engine.fs, self.log_path)
            write_checkpoint(self.engine, snap, prev_info=prev)
        # reseed the incremental .crc chain from the full state: a commit
        # whose checksum couldn't be derived (e.g. removes without sizes)
        # breaks the chain, and the checkpoint is the natural recovery
        # point (reference recomputes the checksum from the snapshot too)
        try:
            write_checksum_from_state(self.engine, self.log_path, snap.state)
        except Exception as e:
            # the checksum is an accelerator, never a failure cause
            _log.debug("checksum reseed after checkpoint failed: %s", e)

    def history(self, limit: Optional[int] = None):
        from delta_tpu.history import get_history

        return get_history(self, limit)

    def vacuum(self, retention_hours: Optional[float] = None,
               dry_run: bool = False, inventory=None,
               vacuum_type: str = "FULL"):
        from delta_tpu.commands.vacuum import vacuum

        return vacuum(self, retention_hours=retention_hours,
                      dry_run=dry_run, inventory=inventory,
                      vacuum_type=vacuum_type)

    def optimize(self):
        from delta_tpu.commands.optimize import OptimizeBuilder

        return OptimizeBuilder(self)

    def __repr__(self):
        return f"Table({self.path!r})"
