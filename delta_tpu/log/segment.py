"""LogSegment: the minimal set of log files that reproduces a version.

Construction semantics follow the reference (spark
`SnapshotManagement.scala:329,461`; kernel
`internal/snapshot/SnapshotManager.java:311`):

1. LIST `_delta_log` from the last-known checkpoint version (hint) —
   lexicographic listing == version order thanks to zero padding.
2. Partition the listing into commit files, checkpoint files, compacted
   deltas; drop everything after the target version.
3. Pick the newest *complete* checkpoint at or below the target version.
4. Keep commit files with `checkpoint_version < v <= target`; verify they
   are contiguous and reach the target (a gap means a corrupt/raced
   listing).
5. Prefer compacted delta files covering whole sub-ranges when allowed
   (fewer files to parse; PROTOCOL.md:270).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from delta_tpu import obs
from delta_tpu.errors import DeltaError, TableNotFoundError, VersionNotFoundError
from delta_tpu.storage.logstore import FileStatus
from delta_tpu.utils import filenames
from delta_tpu.utils.filenames import CheckpointInstance, group_complete_checkpoints

_HINT_DISCARDED = obs.counter("log.hint_discarded")


@dataclass
class LogSegment:
    log_path: str
    version: int
    deltas: List[FileStatus] = field(default_factory=list)       # ascending version
    checkpoints: List[FileStatus] = field(default_factory=list)  # parts of ONE checkpoint
    compacted_deltas: List[FileStatus] = field(default_factory=list)  # chosen replacements
    checkpoint_version: Optional[int] = None
    last_commit_timestamp: int = 0

    @property
    def delta_versions(self) -> List[int]:
        return [filenames.delta_version(f.path) for f in self.deltas]

    def commit_files_descending(self) -> List[FileStatus]:
        return list(reversed(self.deltas))


class CorruptLogError(DeltaError):
    error_class = "DELTA_CORRUPT_LOG"


class _IncrementalUnavailable(Exception):
    """The given segment can't be extended — a checkpoint/compaction
    landed past it, or the listing has a gap (concurrent log cleanup).
    The caller rebuilds the segment (`Table.update`: over the held
    state where it can, else with a full load); this is a control-flow
    signal, never a user-facing error. `reason` names the cause for
    the `snapshot.update` span and its counter: `checkpoint`,
    `compacted_delta` or `gap`."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


def extend_log_segment(fs, prev: LogSegment):
    """LIST only log files with version > `prev.version` and extend the
    segment with the new commits — the incremental half of snapshot
    maintenance (`SnapshotManagement.getUpdatedLogSegment`).

    Returns None when there is nothing new (the common poll outcome —
    one directory listing, zero reads/parses), or
    `(new_segment, new_deltas)` where `new_deltas` are just the appended
    commit FileStatus entries.

    Raises _IncrementalUnavailable when a checkpoint or compacted delta
    newer than `prev.version` appeared: the canonical segment for the
    new version starts from that checkpoint, and a segment is only ever
    what a cold load would list, so this one cannot be *extended*. The
    held state can still be advanced: `Table.update` replays the
    commits `list_commits_after` shows and has `build_log_segment`
    list the new version's segment, as a cold load would. Also raised
    when the new commit versions aren't contiguous with `prev` (log
    cleanup raced the listing).
    """
    with obs.span("log.list_incremental", log_path=prev.log_path,
                  from_version=prev.version) as sp:
        ext = _extend_log_segment(fs, prev)
        if ext is not None:
            sp.set_attrs(to_version=ext[0].version, new_commits=len(ext[1]))
        return ext


def list_commits_after(fs, prev: LogSegment) -> List[FileStatus]:
    """The single-commit files with version > `prev.version`, ascending
    and contiguous from `prev.version + 1`, as one prefix listing shows
    them: what a held state at `prev.version` replays to reach the
    last of them, whatever checkpoints or compacted deltas are listed
    beside them. Empty when none is listed; raises
    _IncrementalUnavailable("gap") where one is missing."""
    with obs.span("log.list_commits", log_path=prev.log_path,
                  from_version=prev.version) as sp:
        commits = _contiguous_commits(_list_after(fs, prev)[0],
                                      prev.version + 1)
        sp.set_attr("new_commits", len(commits))
        return commits


def _list_after(fs, prev: LogSegment):
    """One prefix listing past `prev.version`: the single commits there
    as (version, FileStatus), and as (reason, message) the first file
    that keeps `prev` from being extended, if any."""
    start = prev.version + 1
    prefix = filenames.listing_prefix(prev.log_path, start)
    # same stat-skipping policy as build_log_segment: commit entries
    # keep (size=-1, mtime=0), so the parsed-commit cache keys of an
    # incremental load match a later full listing's keys exactly
    fast = getattr(fs, "list_from_fast", None)
    try:
        if fast is not None:
            listing = list(fast(
                prefix, lambda n: filenames.DELTA_FILE_RE.match(n)
                is not None))
        else:
            listing = list(fs.list_from(prefix))
    except FileNotFoundError:
        raise TableNotFoundError(f"no _delta_log at {prev.log_path}",
                                 error_class="DELTA_EMPTY_DIRECTORY")

    new_deltas: List[tuple] = []
    blocker = None
    delta_match = filenames.DELTA_FILE_RE.match
    for fstat in listing:
        name = filenames.file_name(fstat.path)
        if delta_match(name):
            v = int(name.split(".", 1)[0])
            if v >= start:
                new_deltas.append((v, fstat))
        elif blocker is not None:
            continue
        elif filenames.CHECKPOINT_FILE_RE.match(name) and fstat.size > 0:
            ci = CheckpointInstance.parse(fstat.path)
            if ci is not None and ci.version > prev.version:
                blocker = ("checkpoint",
                           f"checkpoint appeared at version {ci.version}")
        elif filenames.COMPACTED_DELTA_FILE_RE.match(name):
            _, hi = filenames.compacted_delta_versions(fstat.path)
            if hi > prev.version:
                blocker = ("compacted_delta",
                           f"compacted delta appeared covering up to {hi}")
    new_deltas.sort(key=lambda t: t[0])
    return new_deltas, blocker


def _contiguous_commits(new_deltas: List[tuple],
                        start: int) -> List[FileStatus]:
    versions = [v for v, _ in new_deltas]
    if versions != list(range(start, start + len(versions))):
        raise _IncrementalUnavailable(
            "gap",
            f"non-contiguous new commits {versions[:5]}..., expected "
            f"[{start}, {versions[-1]}]")
    return [f for _, f in new_deltas]


def _extend_log_segment(fs, prev: LogSegment):
    new_deltas, blocker = _list_after(fs, prev)
    if blocker is not None:
        raise _IncrementalUnavailable(*blocker)
    if not new_deltas:
        return None
    files = _contiguous_commits(new_deltas, prev.version + 1)
    last_ts = max(prev.last_commit_timestamp,
                  max(f.modification_time for f in files))
    if files[-1].modification_time == 0:
        # stat-deferred listing: the newest commit's mtime is the
        # snapshot timestamp — fetch just that one
        try:
            last_ts = max(last_ts,
                          fs.file_status(files[-1].path).modification_time)
        except FileNotFoundError:
            pass

    import dataclasses

    seg = dataclasses.replace(
        prev,
        version=new_deltas[-1][0],
        deltas=list(prev.deltas) + files,
        last_commit_timestamp=last_ts,
    )
    return seg, files


def _verify_deltas_contiguous(versions: List[int], expected_start: int, target: int) -> None:
    if versions != list(range(expected_start, target + 1)):
        raise CorruptLogError(
            error_class="DELTA_TRUNCATED_TRANSACTION_LOG",
            message=f"Log is missing commit files: have versions {versions[:5]}..., "
            f"expected contiguous [{expected_start}, {target}]"
        )


def _apply_compaction(
    deltas: List[FileStatus], compacted: List[FileStatus], start: int, target: int
) -> tuple[List[FileStatus], List[FileStatus]]:
    """Greedily substitute compacted-delta files for runs of single-commit
    files inside [start, target]. Returns (kept singles, chosen compacted).
    Mirrors the listing-time substitution in `SnapshotManagement.scala:329`.
    """
    if not compacted:
        return deltas, []
    by_version = {filenames.delta_version(f.path): f for f in deltas}
    chosen: List[FileStatus] = []
    covered: set[int] = set()
    # Prefer widest ranges first.
    ranges = sorted(
        ((filenames.compacted_delta_versions(f.path), f) for f in compacted),
        key=lambda t: (t[0][0], -(t[0][1] - t[0][0])),
    )
    for (lo, hi), f in ranges:
        if lo < start or hi > target:
            continue
        rng = set(range(lo, hi + 1))
        if rng & covered:
            continue
        if not rng <= set(by_version):
            # compaction may cover commits we no longer list; only usable
            # when every covered single exists in-window or is irrelevant
            if not rng <= (set(by_version) | covered):
                continue
        chosen.append(f)
        covered |= rng
    singles = [f for v, f in sorted(by_version.items()) if v not in covered]
    return singles, chosen


def build_log_segment(
    fs,
    log_path: str,
    target_version: Optional[int] = None,
    checkpoint_hint: Optional[int] = None,
    use_compacted_deltas: bool = True,
    max_checkpoint_version: Optional[int] = None,
) -> LogSegment:
    """LIST the log and assemble the segment for `target_version` (or the
    latest version when None).

    `max_checkpoint_version` caps which checkpoints may anchor the
    segment (corruption fallback: a reader that failed to consume the
    checkpoint at version V rebuilds with `max_checkpoint_version=V - 1`
    to replay from the previous complete checkpoint, or from the JSON
    commits alone when none remains)."""
    with obs.span("log.list_segment", log_path=log_path) as sp:
        try:
            seg = _build_log_segment(fs, log_path, target_version,
                                     checkpoint_hint, use_compacted_deltas,
                                     max_checkpoint_version)
        except CorruptLogError:
            if checkpoint_hint is None:
                raise
            # the hint is only an accelerator: a window that can't be
            # assembled from it (e.g. the hinted checkpoint lost a part)
            # may still assemble from a full listing anchored earlier
            _HINT_DISCARDED.inc()
            sp.set_attr("hint_discarded", True)
            seg = _build_log_segment(fs, log_path, target_version,
                                     None, use_compacted_deltas,
                                     max_checkpoint_version)
        sp.set_attrs(version=seg.version, num_deltas=len(seg.deltas),
                     num_checkpoint_parts=len(seg.checkpoints),
                     num_compacted=len(seg.compacted_deltas))
        return seg


def _build_log_segment(
    fs,
    log_path: str,
    target_version: Optional[int],
    checkpoint_hint: Optional[int],
    use_compacted_deltas: bool,
    max_checkpoint_version: Optional[int] = None,
) -> LogSegment:
    start = checkpoint_hint if checkpoint_hint is not None else 0
    prefix = filenames.listing_prefix(log_path, start)
    # commit files skip the per-entry stat (their sizes come from the
    # reader; only the segment's LAST commit needs an mtime, stat'd
    # below) — checkpoint/compacted files still stat (size>0 checks)
    fast = getattr(fs, "list_from_fast", None)
    try:
        if fast is not None:
            listing = list(fast(
                prefix, lambda n: filenames.DELTA_FILE_RE.match(n)
                is not None))
        else:
            listing = list(fs.list_from(prefix))
    except FileNotFoundError:
        raise TableNotFoundError(f"no _delta_log at {log_path}",
                                 error_class="DELTA_EMPTY_DIRECTORY")

    # (version, fstat) pairs: each name is parsed exactly once — at 100k
    # commits the repeated delta_version() calls below were measurable
    deltas: List[tuple] = []
    checkpoint_files: List[CheckpointInstance] = []
    compacted: List[FileStatus] = []
    delta_match = filenames.DELTA_FILE_RE.match
    for fstat in listing:
        name = filenames.file_name(fstat.path)
        if delta_match(name):
            v = int(name.split(".", 1)[0])
            if target_version is None or v <= target_version:
                deltas.append((v, fstat))
        elif filenames.CHECKPOINT_FILE_RE.match(name) and fstat.size > 0:
            ci = CheckpointInstance.parse(fstat.path)
            if (ci is not None
                    and (target_version is None
                         or ci.version <= target_version)
                    and (max_checkpoint_version is None
                         or ci.version <= max_checkpoint_version)):
                checkpoint_files.append(ci)
        elif filenames.COMPACTED_DELTA_FILE_RE.match(name):
            lo, hi = filenames.compacted_delta_versions(fstat.path)
            if target_version is None or hi <= target_version:
                compacted.append(fstat)

    if not deltas and not checkpoint_files:
        if checkpoint_hint is not None and checkpoint_hint > 0:
            # stale hint (log may have been cleaned differently) — retry full
            return build_log_segment(
                fs, log_path, target_version, checkpoint_hint=None,
                use_compacted_deltas=use_compacted_deltas,
                max_checkpoint_version=max_checkpoint_version,
            )
        raise TableNotFoundError(f"no commits found in {log_path}",
                                 error_class="DELTA_NO_COMMITS_FOUND")

    complete = group_complete_checkpoints(checkpoint_files)
    chosen_checkpoint: List[CheckpointInstance] = complete[-1] if complete else []
    cp_version = chosen_checkpoint[0].version if chosen_checkpoint else None

    window_start = (cp_version + 1) if cp_version is not None else 0
    deltas_in_window = [(v, f) for v, f in deltas if v >= window_start]
    versions = [v for v, _ in deltas_in_window]

    if target_version is None:
        if versions:
            version = versions[-1]
        elif cp_version is not None:
            version = cp_version
        else:
            raise TableNotFoundError(f"no commits found in {log_path}")
    else:
        version = target_version
        have_max = versions[-1] if versions else cp_version
        if have_max is None or have_max < target_version:
            raise VersionNotFoundError(
                version=target_version,
                earliest=versions[0] if versions else cp_version,
                latest=have_max,
            )

    deltas_needed = [f for v, f in deltas_in_window if v <= version]
    needed_versions = [v for v, _ in deltas_in_window if v <= version]
    if needed_versions:
        _verify_deltas_contiguous(needed_versions, window_start, version)
    elif cp_version is None:
        raise VersionNotFoundError(version=version, earliest=None, latest=None)
    elif cp_version != version:
        raise CorruptLogError(
            f"checkpoint at {cp_version} but no commits up to requested {version}"
        )

    chosen_compacted: List[FileStatus] = []
    if use_compacted_deltas and compacted:
        deltas_needed, chosen_compacted = _apply_compaction(
            deltas_needed, compacted, window_start, version
        )

    checkpoint_statuses = []
    for ci in chosen_checkpoint:
        try:
            checkpoint_statuses.append(
                next(
                    fstat
                    for fstat in listing
                    if fstat.path == ci.path
                )
            )
        except StopIteration:  # pragma: no cover - listing produced it
            pass

    last_ts = 0
    if deltas_needed:
        for f in deltas_needed:
            last_ts = max(last_ts, f.modification_time)
        if deltas_needed[-1].modification_time == 0:
            # fast listing deferred the stat; the last commit's mtime is
            # the snapshot timestamp, so fetch just that one (through the
            # fs abstraction — a non-local store may defer too)
            try:
                last_ts = max(
                    last_ts,
                    fs.file_status(deltas_needed[-1].path)
                    .modification_time)
            except FileNotFoundError:
                pass
    else:
        # checkpoint-at-head: the snapshot's timestamp is the LAST
        # COMMIT's (the checkpoint parquet is written after it and its
        # mtime would overshoot — history/time-travel use commit mtimes)
        cp_commit = next(
            (f for v, f in deltas if v == version), None)
        if cp_commit is not None:
            ts = cp_commit.modification_time
            if ts == 0:
                try:
                    ts = fs.file_status(cp_commit.path).modification_time
                except FileNotFoundError:
                    ts = 0
            last_ts = ts
        if last_ts == 0:
            for f in checkpoint_statuses:
                last_ts = max(last_ts, f.modification_time)

    return LogSegment(
        log_path=log_path,
        version=version,
        deltas=deltas_needed,
        checkpoints=checkpoint_statuses,
        compacted_deltas=chosen_compacted,
        checkpoint_version=cp_version,
        last_commit_timestamp=last_ts,
    )
