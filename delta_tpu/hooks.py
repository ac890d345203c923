"""Post-commit hooks (reference `hook/PostCommitHook.java`, spark hooks
registered at `OptimisticTransaction.scala:378-385`).

Built-ins: CheckpointHook (every `delta.checkpointInterval` commits),
ChecksumHook (`.crc` per version). Custom hooks register process-wide via
`register_post_commit_hook`.
"""

from __future__ import annotations

import logging
from typing import Callable, List

from delta_tpu import obs
from delta_tpu.config import CHECKPOINT_INTERVAL, get_table_config, settings

_log = logging.getLogger(__name__)

Hook = Callable[..., None]  # (table, txn, version, metadata)

_EXTRA_HOOKS: List[Hook] = []


def _snapshot_for_hook(table, version: int):
    """Snapshot at the just-committed `version` for a hook's use. The
    commit's own bytes were just handed to the snapshot cache
    (`Table.notify_commit`), so `update()` normally serves this from the
    incrementally-advanced state with zero log reads; `snapshot_at` is
    the fallback when another writer got past `version` already. The
    span `hook.snapshot` says which of the two served it, so a hook's
    read of the state is told apart from what it then does with it."""
    with obs.span("hook.snapshot", version=version) as sp:
        try:
            snap = table.update()
            if snap.version == version:
                sp.set_attr("served", "update")
                return snap
        except Exception as e:
            _log.debug("update() fast path failed for hook snapshot at "
                       "version %d (%s); rebuilding via snapshot_at",
                       version, e)
        sp.set_attr("served", "snapshot_at")
        return table.snapshot_at(version)


def register_post_commit_hook(hook: Hook) -> None:
    _EXTRA_HOOKS.append(hook)


def checkpoint_hook(table, txn, version: int, metadata) -> None:
    interval = get_table_config(metadata.configuration, CHECKPOINT_INTERVAL)
    if interval > 0 and version > 0 and version % interval == 0:
        from delta_tpu.log.checkpointer import write_checkpoint
        from delta_tpu.log.last_checkpoint import read_last_checkpoint

        snap = _snapshot_for_hook(table, version)
        # the previous hint carries the part manifest that lets the
        # writer reuse unchanged parts (best-effort: None → full write)
        prev = read_last_checkpoint(table.engine.fs, table.log_path)
        write_checkpoint(table.engine, snap, prev_info=prev)


def checksum_hook(table, txn, version: int, metadata) -> None:
    if not settings.write_checksum_enabled:
        return
    from delta_tpu.log.checksum import write_checksum_for_commit

    write_checksum_for_commit(table, txn, version)


AUTO_COMPACT_MIN_FILES = 50
AUTO_COMPACT_MAX_FILE_SIZE = 128 * 1024 * 1024


def auto_compact_hook(table, txn, version: int, metadata) -> None:
    """AutoCompact (`hooks/AutoCompact.scala`): after a data-changing
    commit on a table with delta.autoOptimize.autoCompact, compact
    partitions that accumulated enough small files."""
    conf = metadata.configuration
    # delta.autoOptimize is the legacy umbrella switch implying
    # autoCompact (DeltaConfig.scala autoOptimize)
    enabled = (conf.get("delta.autoOptimize.autoCompact", "").lower()
               == "true"
               or conf.get("delta.autoOptimize", "").lower() == "true")
    if not enabled:
        return
    if txn.operation == "OPTIMIZE" or not txn._adds:
        return
    snap = _snapshot_for_hook(table, version)
    small = sum(
        1 for s in snap.state.add_files_table.column("size").to_pylist()
        if (s or 0) < AUTO_COMPACT_MAX_FILE_SIZE
    )
    if small < AUTO_COMPACT_MIN_FILES:
        return
    from delta_tpu.commands.optimize import _run_optimize

    _run_optimize(
        table, None, zorder_by=None,
        min_file_size=AUTO_COMPACT_MAX_FILE_SIZE,
        max_file_size=AUTO_COMPACT_MAX_FILE_SIZE,
    )


def uniform_hooks(table, txn, version: int, metadata) -> None:
    formats = metadata.configuration.get("delta.universalFormat.enabledFormats", "")
    if "iceberg" in formats:
        from delta_tpu.interop.iceberg import iceberg_converter_hook

        iceberg_converter_hook(table, txn, version, metadata)
    if "hudi" in formats:
        from delta_tpu.interop.hudi import hudi_converter_hook

        hudi_converter_hook(table, txn, version, metadata)


def symlink_manifest_hook(table, txn, version: int, metadata) -> None:
    from delta_tpu.commands.generate import incremental_symlink_manifest_hook

    incremental_symlink_manifest_hook(table, txn, version, metadata)


# A failed manifest update means external engines keep serving stale —
# possibly soft-deleted — rows, so unlike best-effort hooks its error
# must surface (the commit itself has already landed), matching the
# reference's GenerateSymlinkManifest.handleError.
symlink_manifest_hook.critical = True


class PostCommitHookError(Exception):
    """A critical post-commit hook failed. The commit itself succeeded."""

    error_class = "DELTA_POST_COMMIT_HOOK_FAILED"

    def __init__(self, hook_name: str, version: int, cause: Exception):
        super().__init__(
            f"post-commit hook {hook_name!r} failed after version "
            f"{version} committed: {cause}")
        self.hook_name = hook_name
        self.version = version
        self.__cause__ = cause


def run_post_commit_hooks(table, txn, version: int, metadata) -> None:
    with obs.span("txn.post_commit_hooks", version=version):
        for hook in (
            checksum_hook, checkpoint_hook, auto_compact_hook, uniform_hooks,
            symlink_manifest_hook,
            *_EXTRA_HOOKS,
        ):
            # per-hook child spans make "the commit is slow" diagnosable:
            # checkpoint vs checksum vs auto-compact cost separates here,
            # and a swallowed best-effort failure still leaves an
            # error-status span behind
            with obs.span(f"hook.{hook.__name__}") as sp:
                try:
                    hook(table, txn, version, metadata)
                except Exception as e:
                    sp.set_attrs(hook_error=type(e).__name__,
                                 swallowed=not getattr(
                                     hook, "critical", False))
                    if getattr(hook, "critical", False):
                        raise PostCommitHookError(
                            hook.__name__, version, e) from e
