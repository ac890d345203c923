"""Engine SPI: all I/O and compute the table core needs, supplied as five
pluggable handlers (mirrors kernel-api `engine/Engine.java:30-63`).

Two implementations ship in-tree:
- `HostEngine` — CPU/pyarrow execution (the rebuild's `DefaultEngine`
  analogue, and the honest baseline for the ≥8× target).
- `TpuEngine` — the same handlers with replay dedup, stats reduction, and
  predicate evaluation lowered onto TPU via jit'd columnar kernels.

Batches crossing this boundary are Arrow record batches / tables — the
engine-neutral columnar format (the kernel's `ColumnarBatch` analogue).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import pyarrow as pa

from delta_tpu.storage.logstore import FileStatus


class JsonHandler:
    """Parse/read/write newline-delimited JSON (commit files, _last_checkpoint)."""

    def parse_json(self, json_strings: Sequence[str], schema: pa.Schema) -> pa.Table:
        raise NotImplementedError

    def read_json_files(self, paths: Sequence[str]) -> Iterator[tuple[str, bytes]]:
        """Yield (path, raw bytes) per file; decoding to actions is the
        caller's columnarizer's job."""
        raise NotImplementedError

    def write_json_file_atomically(self, path: str, data: bytes, overwrite: bool = False) -> None:
        raise NotImplementedError


class ParquetHandler:
    """Read/write Parquet (checkpoints, data files)."""

    def read_parquet_files(
        self, paths: Sequence[str], columns: Optional[List[str]] = None,
        present_only: bool = False, sizes: Optional[Sequence[int]] = None,
    ) -> Iterator[pa.Table]:
        """One table a file, in the order of `paths`; `columns` projects
        onto those of them the file has.

        `sizes` is a hint too: the files' bytes as the caller knows them
        (the log's `size`, one a path), by which a handler that reads a
        batch of files on several threads may share them out. A handler
        may ignore it; whatever it does, the tables come in the order of
        `paths` and a file that is missing raises as it does read alone.

        `present_only` is a hint in the manner of the predicate of Delta
        Kernel's `readParquetFiles(files, schema, predicate)`: the caller
        will use only the rows in which at least one of `columns` is
        non-null, so the handler may leave out any of the other rows
        (and number the rows it hands back as they come). Handing back
        more rows, or all of them, is always right, so a handler may
        ignore it. Only the small-action read of a checkpoint part
        passes it; a read of a data file must not, since it wants every
        row. Its one limit: a handler that decides by the footer's
        statistics, which count nulls leaf by leaf, cannot see a struct
        that is there with every leaf null. PROTOCOL.md gives each small
        action a required field, so no valid checkpoint holds one."""
        raise NotImplementedError

    def write_parquet_file(self, path: str, table: pa.Table) -> FileStatus:
        raise NotImplementedError

    def write_parquet_file_atomically(self, path: str, table: pa.Table) -> None:
        raise NotImplementedError

    def write_serialized(self, path: str, data: bytes,
                         overwrite: bool = False) -> FileStatus:
        """Upload already-encoded Parquet bytes. Splitting encode from
        upload lets the pipelined checkpoint writer overlap the two
        stages (and byte-copy reused parts without re-encoding);
        overwrite=False is the atomic put-if-absent contract."""
        raise NotImplementedError


class FileSystemClient:
    def list_from(self, path: str) -> Iterator[FileStatus]:
        raise NotImplementedError

    def read_file(self, path: str) -> bytes:
        raise NotImplementedError

    def write_file(self, path: str, data: bytes) -> None:
        """Non-atomic data-file write (data files are immutable once
        committed; atomicity is only required for the log, via
        JsonHandler.write_json_file_atomically)."""
        raise NotImplementedError

    def resolve_path(self, path: str) -> str:
        raise NotImplementedError

    def os_path(self, path: str) -> "str | None":
        """An operating-system path for `path` when it is directly
        readable from the local filesystem (lets native components
        bypass per-file interpreter I/O), else None."""
        return None

    def mkdirs(self, path: str) -> None:
        raise NotImplementedError

    def walk(self, path: str) -> Iterator[FileStatus]:
        """Recursively yield every file under `path`."""
        raise NotImplementedError

    def delete(self, path: str) -> None:
        raise NotImplementedError

    def exists(self, path: str) -> bool:
        raise NotImplementedError

    def file_status(self, path: str) -> FileStatus:
        raise NotImplementedError


class ExpressionHandler:
    """Evaluate expressions over columnar batches (partition pruning,
    data-skipping predicates, stats aggregation)."""

    def evaluate(self, expr, batch: pa.Table):
        """Return an Arrow array (projection) for `expr` over `batch`."""
        raise NotImplementedError

    def evaluate_predicate(self, expr, batch: pa.Table):
        """Return a boolean selection mask (numpy bool array) for `expr`."""
        raise NotImplementedError


class MetricsReporter:
    def report(self, report: dict) -> None:
        raise NotImplementedError


class Engine:
    """Bundle of the five handlers."""

    def __init__(
        self,
        json_handler: JsonHandler,
        parquet_handler: ParquetHandler,
        fs_client: FileSystemClient,
        expression_handler: ExpressionHandler,
        metrics_reporters: Optional[List[MetricsReporter]] = None,
    ):
        self.json = json_handler
        self.parquet = parquet_handler
        self.fs = fs_client
        self.expressions = expression_handler
        self.metrics_reporters = list(metrics_reporters or [])

    def report_metrics(self, report: dict) -> None:
        # correlation: with tracing on, every emitted report is also
        # pinned to the active span as an event, so a SnapshotReport /
        # TransactionReport can be matched to the exact trace that
        # produced it (the reportUUID rides along)
        from delta_tpu import obs

        obs.add_event("metrics_report",
                      report_type=report.get("type"),
                      report_uuid=report.get("reportUUID"))
        for r in self.metrics_reporters:
            r.report(report)
