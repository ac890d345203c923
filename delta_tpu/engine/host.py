"""HostEngine: CPU/pyarrow implementation of the Engine SPI.

This is the rebuild's analogue of `kernel-defaults`' `DefaultEngine`
(`DefaultEngine.java:24`): Parquet via pyarrow (the parquet-mr role), JSON
via the stdlib, an interpreted expression evaluator over Arrow batches.
It is both the portability fallback and the measured baseline that the
TpuEngine must beat.
"""

from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.json as pa_json
import pyarrow.parquet as pq

from delta_tpu import obs
from delta_tpu.engine.spi import (
    Engine,
    ExpressionHandler,
    FileSystemClient,
    JsonHandler,
    MetricsReporter,
    ParquetHandler,
)
from delta_tpu.log import parquet_stitch
from delta_tpu.resilience import (
    current_deadline,
    deadline_scope_at,
    endpoint_of,
    io_call,
)
from delta_tpu.storage.logstore import (
    FileStatus,
    LocalLogStore,
    LogStore,
    logstore_for_path,
)

# process-wide storage I/O counters; per-file spans are verbose-only
# (a 100k-commit load would emit 100k spans), the counters always run
_READ_CALLS = obs.counter("storage.read.calls")
_READ_BYTES = obs.counter("storage.read.bytes")
_LIST_CALLS = obs.counter("storage.list.calls")
_WRITE_CALLS = obs.counter("storage.write.calls")
_WRITE_BYTES = obs.counter("storage.write.bytes")
_PARQUET_PREFETCHED = obs.counter("storage.parquet.prefetched_files")
_ENCODES_DEALT = obs.counter("write.encodes_dealt")
_ENCODES_SERIAL = obs.counter("write.encodes_serial")

_SMALL_GROUPS_SKIPPED = obs.counter("checkpoint.small_row_groups_skipped")
_PARTS_DEALT = obs.counter("checkpoint.parts_decoded_dealt")
_FILES_DEALT = obs.counter("scan.files_dealt")
_FILES_INLINE = obs.counter("scan.files_inline")

# how many parquet byte-reads to keep in flight ahead of the decoder
_PARQUET_PREFETCH_DEPTH = 2

_UNTIMED = contextlib.nullcontext()


def _untimed(key: str) -> contextlib.AbstractContextManager:
    """`Span.timed` of a batch read under no span: nothing is timed."""
    return _UNTIMED


# rows a batch of a `present_only` read: a row group is left as soon as
# the batches read hold every present row its footer counts
_PRESENT_BATCH_ROWS = 65536


# The weight from which a piece of a file's full decode is a task of its
# own (`_deal_plan`). A leaf weighs in a row group what the footer counts
# of it there: its bytes before compression, and a byte a value at the
# least (a million nulls in 2 KB still cost a million levels to walk).
# A file none of whose row groups weighs this much is read whole by
# `pq.read_table`, as is one that comes to a single task; any other is
# dealt out over the scan pool: a task a row group and, within a group
# of this weight, a task a column of this weight (a top-level column or a
# top-level struct's child, with all beneath it), the lighter columns
# sharing tasks of at most this weight.
# Set by the sandbox's sweep (PR 48; 8 cores, pyarrow 25; a struct of
# path, size and a stats string, snappy, 4-66 MiB in 1, 2, 4 and 8 row
# groups; ms whole -> dealt): `read_table` does well where many small
# groups keep its own readahead busy and badly where a group is large or
# one leaf is most of it. At 4 MiB the rule dealt out files of eight
# 4 MiB groups, 7.0 -> 9.2; at 8 MiB every shape gains or stays: one
# group of 8 MiB 10.0 -> 5.8, of 16 MiB 17.6 -> 10.5, of 66 MiB 68 -> 41;
# two groups of 8 MiB 10.0 -> 6.3; four of 16 MiB 20 -> 13-14; 4 MiB
# files and files of eight 1-4 MiB groups are read whole (2.5-7 ms);
# only eight groups of 8.4 MiB lose, 12.5-13.3 -> 14.5-14.9. The cold
# loads' checkpoint (69 MB, three groups, `add.stats` 105 MB of a full
# group's 136): 215 -> 112.
_DEAL_MIN_BYTES = 8 << 20


# A projected read of a batch of files (`read_parquet_files(paths,
# columns)`: a scan's data files) is cut into contiguous runs of files,
# a run a task of the scan pool (`_file_runs`). A file weighs its bytes
# and, whatever it holds, an open and a footer (`_FILE_FIXED_BYTES`). A
# task is worth its hop from `_RUN_MIN_BYTES` of weight (half a dozen
# files of the smallest kind), and a worker gets `_RUNS_A_WORKER` of
# them so that a run of heavy files does not set the pace alone. A batch
# that does not come to a run a worker is read where it is called, file
# after file with Arrow's own fan-out over the columns, as before there
# were runs: a few large files keep the cores busy that way, and a few
# small ones are not worth a hop.
# On the chip's host (13 cores, pyarrow 25, PR 50; TPC-DS `store_sales`
# at scale factor 1: 1,824 files of ~60 KB, eight of 23 columns): the
# loop on the caller's thread 2.9-3.2 s, 52 runs 0.94-1.2 s, the same
# with the pool held to four workers 0.91-1.16 s: past a handful of
# workers the per-file Python (an open, a footer, two calls into Arrow)
# takes turns on the interpreter's lock, and no count of runs mends that.
_FILE_FIXED_BYTES = 256 << 10
_RUN_MIN_BYTES = 2 << 20
_RUNS_A_WORKER = 4


def _file_runs(sizes: Sequence[int], workers: int) -> List[Tuple[int, int]]:
    """The batch's files `[start, stop)` a task, by the rule above; one
    run where the batch is not worth dealing out."""
    n = len(sizes)
    ends = np.cumsum(np.maximum(np.asarray(sizes, dtype=np.int64), 0)
                     + _FILE_FIXED_BYTES)
    total = int(ends[-1]) if n else 0
    want = min(n, workers * _RUNS_A_WORKER, total // _RUN_MIN_BYTES)
    if workers < 2 or want < workers:
        return [(0, n)]
    marks = total * np.arange(1, want) // want
    cuts = np.unique(np.searchsorted(ends, marks, side="left") + 1)
    bounds = [0] + [int(c) for c in cuts if c < n] + [n]
    return list(zip(bounds[:-1], bounds[1:]))


def _local_os_path(store: LogStore, path: str) -> Optional[str]:
    """`path` as the operating system names it where `store` is the
    local one, else None."""
    if not isinstance(store, LocalLogStore):
        return None
    return path[len("file://"):] if path.startswith("file://") else path


def _leaves_under(f: pq.ParquetFile, cols: List[str]) -> dict:
    """column of `cols` -> [(leaf's index in the file, it does not
    repeat)] of the leaves under it. A column whose name holds a dot
    finds none, and is then one the footer says nothing of."""
    leaves: dict = {c: [] for c in cols}
    for i in range(f.metadata.num_columns):
        leaf = f.schema.column(i)
        top = leaf.path.split(".", 1)[0]
        if top in leaves:
            leaves[top].append((i, leaf.max_repetition_level == 0))
    return leaves


def _present_counts(f: pq.ParquetFile, leaves: dict) -> List[dict]:
    """Per row group of `f`, by the footer's statistics alone: for each
    column of `leaves` (`_leaves_under`), how many rows of the group
    hold it. 0 where every leaf under the column reads `null_count ==
    num_values`; the largest count of non-null values among its leaves
    that do not repeat (a struct that is there has at least that many
    rows); None where the footer cannot say (a leaf without statistics
    or without a `null_count`, values under repeated leaves only, no
    leaf found), which reads the group to its end."""
    groups = []
    for g in range(f.metadata.num_row_groups):
        rg = f.metadata.row_group(g)
        counts = {}
        for c, under in leaves.items():
            some, flat = not under, 0
            for i, is_flat in under:
                chunk = rg.column(i)
                st = chunk.statistics if chunk.is_stats_set else None
                if st is None or not st.has_null_count:
                    some, flat = True, None
                    break
                held = chunk.num_values - st.null_count
                some = some or held > 0
                if is_flat:
                    flat = max(flat, held)
            counts[c] = (flat or None) if some else 0
        groups.append(counts)
    return groups


def _read_present(f: pq.ParquetFile, cols: List[str],
                  fetched: Optional[int] = None) -> pa.Table:
    """The `present_only` read of `cols` (all in the file): the row
    groups that may hold a non-null value of one of them, each read in
    batches until as many present rows of every column were seen as its
    footer counts. The active span learns what the footer spared;
    `fetched` is the file's size where it was fetched whole, else the
    bytes read are the footer and the chosen groups' column chunks."""
    md = f.metadata
    leaves = _leaves_under(f, cols)
    want = _present_counts(f, leaves)
    chosen = [g for g, counts in enumerate(want)
              if any(n != 0 for n in counts.values())]
    _SMALL_GROUPS_SKIPPED.inc(md.num_row_groups - len(chosen))
    batches = []
    for g in chosen:
        need = want[g]
        bounded = None not in need.values()
        seen = dict.fromkeys(cols, 0)
        for batch in f.iter_batches(batch_size=_PRESENT_BATCH_ROWS,
                                    row_groups=[g], columns=cols):
            batches.append(batch)
            if not bounded:
                continue
            for c in cols:
                seen[c] += len(batch) - batch.column(c).null_count
            if all(seen[c] >= need[c] for c in cols):
                break
    if fetched is None:
        fetched = md.serialized_size + 8 + sum(
            md.row_group(g).column(i).total_compressed_size
            for g in chosen for under in leaves.values() for i, _ in under)
    obs.set_attrs(row_groups=md.num_row_groups, row_groups_read=len(chosen),
                  file_rows=md.num_rows, bytes_read=fetched)
    schema = pa.schema([f.schema_arrow.field(c) for c in cols],
                       metadata=f.schema_arrow.metadata)
    return pa.Table.from_batches(batches, schema=schema)


def _deal_plan(md: pq.FileMetaData,
               schema: pa.Schema) -> List[Tuple[int, Optional[List[str]]]]:
    """The tasks of a file's full decode by its footer, `_DEAL_MIN_BYTES`
    stating the rule: [(row group, the columns of the task by their
    dotted prefix, None for every column)]; empty where the file is read
    whole. A column here is a top-level one or, of a top-level struct,
    a child with all beneath it. A file whose names hold a dot is read
    whole: a prefix would not say which column it means."""
    groups = [md.row_group(g) for g in range(md.num_row_groups)]
    weights = [max(rg.total_byte_size, rg.num_rows * md.num_columns)
               for rg in groups]
    if max(weights, default=0) < _DEAL_MIN_BYTES:
        return []
    children = {fld.name: [c.name for c in fld.type] for fld in schema
                if pa.types.is_struct(fld.type)}
    if any("." in n for n in schema.names) or any(
            "." in c for under in children.values() for c in under):
        return []
    pq_schema = md.schema
    unit_of = []         # leaf -> the column it decodes with
    for i in range(md.num_columns):
        at = pq_schema.column(i).path.split(".")
        unit_of.append(".".join(at[:2]) if at[0] in children else at[0])
    if set(unit_of) != {u for fld in schema for u in (
            [f"{fld.name}.{c}" for c in children[fld.name]]
            if fld.name in children else [fld.name])}:
        return []        # a column without a leaf: nothing to go by
    tasks = []           # (weight, row group, columns)
    for g, rg in enumerate(groups):
        if weights[g] < _DEAL_MIN_BYTES:
            tasks.append((weights[g], g, None))
            continue
        weight = dict.fromkeys(unit_of, 0)
        for i, unit in enumerate(unit_of):
            chunk = rg.column(i)
            weight[unit] += max(chunk.total_uncompressed_size,
                                chunk.num_values)
        of_group, shared, held = [], [], 0
        for unit, n in weight.items():
            if n >= _DEAL_MIN_BYTES:
                of_group.append((n, g, [unit]))
                continue
            if held + n > _DEAL_MIN_BYTES:
                of_group.append((held, g, shared))
                shared, held = [], 0
            shared.append(unit)
            held += n
        if shared:
            of_group.append((held, g, shared))
        tasks += (of_group if len(of_group) > 1
                  else [(weights[g], g, None)])
    if len(tasks) < 2:
        return []
    # the heaviest first: it sets the pace, so it must not queue
    tasks.sort(key=lambda task: task[0], reverse=True)
    return [(g, cols) for _, g, cols in tasks]


def _one_chunk(col: pa.ChunkedArray) -> pa.Array:
    return col.chunk(0) if col.num_chunks == 1 else col.combine_chunks()


def _read_dealt(f: pq.ParquetFile, tasks, reopen) -> pa.Table:
    """The file's table from `tasks` (`_deal_plan`), each read on the
    scan pool through a handle of its own with the footer `f` parsed,
    and put together without a copy: a row group's struct from the
    children its tasks read (validity from the first of them: every
    read of a struct's child rebuilds the struct's own), the groups
    by `concat_tables`. Equals `pq.read_table`'s but for the chunking:
    a chunk a row group. The tasks are leaves: none waits for the pool."""
    from delta_tpu.utils.threads import scan_pool

    md, schema = f.metadata, f.schema_arrow

    def read(task) -> pa.Table:
        g, cols = task
        with reopen() as source:
            return pq.ParquetFile(source, metadata=md).read_row_group(
                g, columns=cols, use_threads=False)

    by_group: dict = {}
    for (g, cols), tbl in zip(tasks, scan_pool().map(read, tasks)):
        by_group.setdefault(g, []).append((cols, tbl))
    pieces = []
    for g in sorted(by_group):
        reads = by_group[g]
        if reads[0][0] is None:          # the group whole, one task
            pieces.append(reads[0][1])
            continue
        read_by = {u: tbl for cols, tbl in reads for u in cols}
        columns = []
        for fld in schema:
            if fld.name in read_by:      # not a struct
                columns.append(read_by[fld.name].column(fld.name))
                continue
            under = [read_by[f"{fld.name}.{c.name}"] for c in fld.type]
            if all(tbl is under[0] for tbl in under):
                columns.append(under[0].column(fld.name))   # as read
                continue
            parts = [_one_chunk(tbl.column(fld.name)) for tbl in under]
            nulls = parts[0].is_null() if parts[0].null_count else None
            columns.append(pa.StructArray.from_arrays(
                [part.field(c.name) for part, c in zip(parts, fld.type)],
                fields=list(fld.type), mask=nulls))
        pieces.append(pa.Table.from_arrays(columns, schema=schema))
    return pa.concat_tables(pieces)


class HostJsonHandler(JsonHandler):
    def __init__(self, store_resolver=logstore_for_path):
        self._store_for = store_resolver

    def parse_json(self, json_strings: Sequence[str], schema: pa.Schema) -> pa.Table:
        rows = [json.loads(s) if s is not None else {} for s in json_strings]
        return pa.Table.from_pylist(rows, schema=schema)

    def read_json_files(self, paths: Sequence[str]) -> Iterator[tuple[str, bytes]]:
        for p in paths:
            store = self._store_for(p)
            yield p, io_call(endpoint_of(p), lambda: store.read(p))

    def write_json_file_atomically(self, path: str, data: bytes, overwrite: bool = False) -> None:
        # Retrying a put-if-absent write is safe even when the outcome
        # is ambiguous (the PUT landed but its response was lost): the
        # retry raises FileAlreadyExistsError — permanent, so it flows
        # to the conflict machinery, where CommitInfo.txnId self-commit
        # detection distinguishes our own landed write from a real loss.
        store = self._store_for(path)
        with obs.span("storage.commit_write", path=path, bytes=len(data),
                      overwrite=overwrite):
            io_call(endpoint_of(path),
                    lambda: store.write(path, data, overwrite=overwrite))
        _WRITE_CALLS.inc()
        _WRITE_BYTES.inc(len(data))

    def write_json_files_atomically(self, items,
                                    overwrite: bool = False) -> None:
        """Batched put-if-absent for the group-commit emit: one
        breaker-scoped `io_call` covers the whole batch, and stores
        with a batch protocol (`LogStore.write_batch` — the external
        arbiter claims every version in one round trip) get the items
        together. On failure the already-written prefix stays durable
        (the store contract), so the caller must resolve member fates
        by read-back rather than resubmitting."""
        items = list(items)
        if not items:
            return
        first = items[0][0]
        store = self._store_for(first)
        total = sum(len(d) for _, d in items)
        with obs.span("storage.commit_write_batch", path=first,
                      members=len(items), bytes=total,
                      overwrite=overwrite):
            io_call(endpoint_of(first),
                    lambda: store.write_batch(items, overwrite=overwrite))
        _WRITE_CALLS.inc(len(items))
        _WRITE_BYTES.inc(total)


class HostParquetHandler(ParquetHandler):
    def __init__(self, store_resolver=logstore_for_path):
        self._store_for = store_resolver

    def _decode(self, source: pa.NativeFile,
                reopen: Callable[[], pa.NativeFile],
                columns: Optional[List[str]], present_only: bool = False,
                fetched: Optional[int] = None) -> pa.Table:
        """`source`'s table: a full read or a `present_only` one (a
        batch's projected read is `_read_projected`'s). `reopen` gives a
        further handle on the same bytes: a full read that is dealt out
        opens one a task."""
        f = pq.ParquetFile(source)
        if columns is None:
            tasks = _deal_plan(f.metadata, f.schema_arrow)
            obs.set_attrs(row_groups=f.metadata.num_row_groups,
                          decode_tasks=len(tasks) or 1,
                          decode="dealt" if tasks else "whole")
            if not tasks:
                return pq.read_table(source)
            _PARTS_DEALT.inc()
            return _read_dealt(f, tasks, reopen)
        # one footer parse serves both the schema check and the
        # read. Project onto the columns the file actually has — a
        # checkpoint from another engine may omit e.g. txn or
        # domainMetadata, and erroring would force callers into
        # read-twice fallbacks. An empty intersection stays an empty
        # projection (0 columns, correct row count) — never a
        # decode-everything full read.
        present = set(f.schema_arrow.names)
        cols = [c for c in columns if c in present]
        if present_only and cols:
            return _read_present(f, cols, fetched)
        return f.read(columns=cols)

    def _decode_bytes(self, data: bytes, columns: Optional[List[str]],
                      present_only: bool) -> pa.Table:
        """The table of a file fetched whole (zero copy: every handle
        reads `data` where it lies)."""
        return self._decode(pa.BufferReader(data),
                            lambda: pa.BufferReader(data), columns,
                            present_only, len(data))

    def _fetch(self, path: str) -> bytes:
        store = self._store_for(path)
        return io_call(endpoint_of(path), lambda: store.read(path))

    def _open(self, path: str) -> pa.NativeFile:
        """A handle on `path`'s bytes by one storage call: the local
        store's file opened by path (Arrow then reads the footer and the
        column chunks it is asked for and nothing else; a missing file
        raises from the open), any other store's fetched whole."""
        store = self._store_for(path)
        local = _local_os_path(store, path)
        if local is not None:
            return io_call(endpoint_of(path), lambda: pa.OSFile(local))
        return pa.BufferReader(self._fetch(path))

    def _open_parquet(self, path: str) -> Tuple[pa.NativeFile,
                                                pq.ParquetFile]:
        """`path` opened (`_open`) and its footer read; the handle is
        the caller's to close."""
        source = self._open(path)
        try:
            # no pre-buffering: the bytes are in memory or in the page
            # cache, and a read handed to Arrow's I/O pool is one more
            # thread to wait for (1,824 files of ~60 KB on the chip's
            # host, PR 50: 2.43 against 2.72 s on one thread, 0.92
            # against 0.97 on four, 0.87 against 0.81 on thirteen)
            return source, pq.ParquetFile(source, pre_buffer=False)
        except BaseException:
            source.close()
            raise

    def _project_files(self, paths: Sequence[str], columns: List[str],
                       use_threads: bool,
                       timed: Callable[
                           [str], contextlib.AbstractContextManager]
                       ) -> Iterator[pa.Table]:
        """Of `columns`, those each of `paths` has, decoded from it in
        the file's own order (none of them: no column and the file's
        rows), one file after another on the calling thread, each by a
        storage call of its own (`_open`). Which columns a file has is
        asked of its footer once a Parquet schema, not once a file.
        `use_threads` is Arrow's own fan-out over the columns: off where
        the files are a task of a pool. `timed` is the `Span.timed` of
        the span that answers for these files: it adds up `open_ms` (the
        storage call and the footer) and `decode_ms` (the columns) over
        them, since a span a file would be thousands a scan."""
        wanted = set(columns)
        seen = cols = None
        # entered once a file each, made once: every call in this loop
        # is a point where a task hands the interpreter's lock on
        opening, decoding = timed("open_ms"), timed("decode_ms")
        for p in paths:
            with opening:
                source, f = self._open_parquet(p)
            with source:
                schema = f.metadata.schema
                if seen is None or not schema.equals(seen):
                    seen = schema
                    cols = [c for c in f.schema_arrow.names if c in wanted]
                with decoding:
                    tbl = f.read(columns=cols, use_threads=use_threads)
            yield tbl

    def _read_projected(self, paths: List[str], columns: List[str],
                        sizes: Optional[Sequence[int]]) -> Iterator[pa.Table]:
        """The batch's tables in the order of `paths`
        (`_project_files`): on the scan pool, a run of files a task
        (`_file_runs`), or here where the batch is not worth that. A
        task decodes its files on its one thread and deals nothing out
        further, so none waits for the pool it runs on. The active span
        (`scan.read`) learns what was done: how the batch was dealt and,
        dealt, how long this thread was blocked waiting for the pool
        (`wait_ms`); each task says the rest on a `scan.read_run` of its
        own (`obs.wrap` makes it that span's child, on the worker's
        thread). An inline batch opens none and times its files on the
        active span itself."""
        from delta_tpu.utils.threads import default_scan_threads, scan_pool

        workers = default_scan_threads()
        runs = _file_runs([0] * len(paths) if sizes is None else sizes,
                          workers)
        sp = obs.current_span()
        timed = _untimed if sp is None else sp.timed
        if len(runs) < 2:
            _FILES_INLINE.inc(len(paths))
            obs.set_attrs(tasks=0, threads=1, inline=True)
            yield from self._project_files(paths, columns, True, timed)
            return
        _FILES_DEALT.inc(len(paths))
        obs.set_attrs(tasks=len(runs), threads=workers, inline=False)
        deadline = current_deadline()

        def read_run(run: Tuple[int, int]) -> List[pa.Table]:
            with deadline_scope_at(deadline), obs.span(
                    "scan.read_run", files=run[1] - run[0]) as task:
                # the thread's own CPU: what the span lasts beyond it,
                # the thread was not running (on a warm local store,
                # waiting for the interpreter's lock)
                cpu_ns = time.thread_time_ns() if task.recording else 0
                tables = list(self._project_files(
                    paths[run[0]:run[1]], columns, False, task.timed))
                if task.recording:
                    task.set_attrs(
                        rows=sum(t.num_rows for t in tables),
                        bytes=sum(t.get_total_buffer_size() for t in tables),
                        cpu_ms=(time.thread_time_ns() - cpu_ns) / 1e6)
                return tables

        with timed("wait_ms"):
            dealt = scan_pool().map(obs.wrap(read_run), runs)
        for tables in dealt:
            yield from tables

    def read_parquet_files(
        self, paths: Sequence[str], columns: Optional[List[str]] = None,
        present_only: bool = False, sizes: Optional[Sequence[int]] = None,
    ) -> Iterator[pa.Table]:
        paths = list(paths)
        if columns is not None and not present_only:
            yield from self._read_projected(paths, columns, sizes)
            return
        if len(paths) <= 1:
            for p in paths:
                store = self._store_for(p)
                local = _local_os_path(store, p)
                if local is not None:
                    # opened by path, Arrow reads the footer and the
                    # column chunks it is asked for and nothing else (a
                    # full read: no copy of the file into `bytes`
                    # first); only the open is a storage call: a torn
                    # footer raises from the decode, as on bytes read
                    # whole
                    with io_call(endpoint_of(p),
                                 lambda: pa.OSFile(local)) as source:
                        tbl = self._decode(source, lambda: pa.OSFile(local),
                                           columns, present_only)
                    yield tbl
                    continue
                yield self._decode_bytes(self._fetch(p), columns,
                                         present_only)
            return
        # Byte-prefetch, for a full read of a few large files: keep the
        # next reads in flight on the shared I/O pool so decoding file i
        # overlaps reading file i+1 (checkpoint parts, V2 sidecars).
        # Reads are leaf pool tasks; decode stays with the consuming
        # thread (which deals a large part's out to the scan pool,
        # `_decode`) and consumption stays in input order.
        from delta_tpu.utils.threads import shared_pool

        pool = shared_pool()
        read = obs.wrap(self._fetch)
        pending: deque = deque()
        i = 0
        try:
            while pending or i < len(paths):
                while i < len(paths) and len(pending) <= _PARQUET_PREFETCH_DEPTH:
                    if pending:
                        _PARQUET_PREFETCHED.inc()
                    pending.append(pool.submit(read, paths[i]))
                    i += 1
                data = pending.popleft().result()
                yield self._decode_bytes(data, columns, present_only)
        finally:
            for fut in pending:
                fut.cancel()

    def write_parquet_file(self, path: str, table: pa.Table) -> FileStatus:
        # The stitcher deals a large table's encode over `scan_pool()`
        # and waits for it, so a file must not be written from a task of
        # that pool: none is (its tasks are leaf reads). The span is
        # verbose under the stitcher's line, as `storage.parquet_write`
        # is: a sink's thousands of small files are one call each.
        with obs.span("write.encode", _verbose=parquet_stitch.small(table),
                      rows=table.num_rows,
                      columns=table.num_columns) as sp:
            buf, how = parquet_stitch.encode(table)
            sp.set_attrs(bytes=len(buf), **how)
        (_ENCODES_DEALT if how["dealt"] else _ENCODES_SERIAL).inc()
        store = self._store_for(path)
        with obs.span("storage.parquet_write", _verbose=True, path=path,
                      bytes=len(buf)):
            io_call(endpoint_of(path),
                    lambda: store.write(path, buf, overwrite=True))
        _WRITE_CALLS.inc()
        _WRITE_BYTES.inc(len(buf))
        return store.file_status(path)

    def write_parquet_file_atomically(self, path: str, table: pa.Table) -> None:
        sink = pa.BufferOutputStream()
        pq.write_table(table, sink, compression="snappy")
        buf = sink.getvalue().to_pybytes()
        store = self._store_for(path)
        with obs.span("storage.parquet_write", path=path, bytes=len(buf)):
            io_call(endpoint_of(path),
                    lambda: store.write(path, buf, overwrite=False))
        _WRITE_CALLS.inc()
        _WRITE_BYTES.inc(len(buf))

    def write_serialized(self, path: str, data: bytes,
                         overwrite: bool = False) -> FileStatus:
        store = self._store_for(path)
        with obs.span("storage.parquet_write", _verbose=True, path=path,
                      bytes=len(data), overwrite=overwrite):
            io_call(endpoint_of(path),
                    lambda: store.write(path, data, overwrite=overwrite))
        _WRITE_CALLS.inc()
        _WRITE_BYTES.inc(len(data))
        return store.file_status(path)


class HostFileSystemClient(FileSystemClient):
    # I/O call counters (cheap, process-local, never reset implicitly):
    # tests and bench diagnostics assert e.g. that a no-change poll does
    # one listing and zero reads, or that a cache-covered reload
    # re-reads nothing
    def __init__(self, store_resolver=logstore_for_path):
        self._store_for = store_resolver
        self.read_calls = 0
        self.list_calls = 0

    def list_from(self, path: str) -> Iterator[FileStatus]:
        self.list_calls += 1
        _LIST_CALLS.inc()
        store = self._store_for(path)
        # Materialize inside the retry so a listing that fails mid-walk
        # is redone whole, never resumed half-consumed.
        return iter(io_call(endpoint_of(path),
                            lambda: list(store.list_from(path))))

    def list_from_fast(self, path: str, skip_stat):
        """Stat-skipping listing when the store supports it (local
        stores); falls back to the full listing."""
        self.list_calls += 1
        _LIST_CALLS.inc()
        store = self._store_for(path)
        fast = getattr(store, "list_from_fast", None)
        if fast is not None:
            return iter(io_call(endpoint_of(path),
                                lambda: list(fast(path, skip_stat))))
        return iter(io_call(endpoint_of(path),
                            lambda: list(store.list_from(path))))

    def read_file(self, path: str) -> bytes:
        self.read_calls += 1
        _READ_CALLS.inc()
        store = self._store_for(path)
        with obs.span("storage.read", _verbose=True, path=path) as sp:
            data = io_call(endpoint_of(path), lambda: store.read(path))
            sp.set_attr("bytes", len(data))
        _READ_BYTES.inc(len(data))
        return data

    def write_file(self, path: str, data: bytes) -> None:
        _WRITE_CALLS.inc()
        _WRITE_BYTES.inc(len(data))
        store = self._store_for(path)
        with obs.span("storage.write", _verbose=True, path=path,
                      bytes=len(data)):
            io_call(endpoint_of(path),
                    lambda: store.write(path, data, overwrite=True))

    def resolve_path(self, path: str) -> str:
        return path

    def os_path(self, path: str):
        return _local_os_path(self._store_for(path), path)

    def mkdirs(self, path: str) -> None:
        self._store_for(path).mkdirs(path)

    def walk(self, path: str):
        return self._store_for(path).walk(path)

    def delete(self, path: str) -> None:
        store = self._store_for(path)
        io_call(endpoint_of(path), lambda: store.delete(path))

    def exists(self, path: str) -> bool:
        store = self._store_for(path)
        return io_call(endpoint_of(path), lambda: store.exists(path))

    def file_status(self, path: str):
        store = self._store_for(path)
        return io_call(endpoint_of(path), lambda: store.file_status(path))


class HostExpressionHandler(ExpressionHandler):
    """Interpreted evaluator over Arrow batches (via numpy); expression
    trees come from delta_tpu.expressions."""

    def evaluate(self, expr, batch: pa.Table):
        from delta_tpu.expressions.eval import evaluate_host

        return evaluate_host(expr, batch)

    def evaluate_predicate(self, expr, batch: pa.Table) -> np.ndarray:
        from delta_tpu.expressions.eval import evaluate_host

        result = evaluate_host(expr, batch)
        arr = np.asarray(result)
        if arr.dtype != np.bool_:
            # three-valued logic: NULL -> cannot prune -> treated True by
            # skipping callers; plain predicate callers get False
            arr = np.nan_to_num(arr.astype(np.float64), nan=0.0) != 0
        return arr


class LoggingMetricsReporter(MetricsReporter):
    def __init__(self):
        self.reports: List[dict] = []

    def report(self, report: dict) -> None:
        self.reports.append(report)


_ARROW_POOL_SET = False


def _configure_arrow_pool() -> None:
    """Size Arrow's compute pool like our own I/O pool: containers here
    advertise 1 CPU (so Arrow defaults to single-threaded parquet decode
    / filter / JSON parse) while the host actually schedules several
    workers. Never shrink a user-configured pool."""
    global _ARROW_POOL_SET
    if _ARROW_POOL_SET:
        return
    _ARROW_POOL_SET = True
    try:
        import pyarrow as _pa

        from delta_tpu.utils.threads import default_io_threads

        n = default_io_threads()
        if _pa.cpu_count() < n:
            _pa.set_cpu_count(n)
        if _pa.io_thread_count() < n:
            _pa.set_io_thread_count(n)
    # delta-lint: disable=except-swallow (audited: pool sizing is an
    # optimization probed at engine construction — any pyarrow API drift
    # must leave the default pools, never fail engine startup)
    except Exception:
        pass


class HostEngine(Engine):
    use_device_sql = False  # pandas relational path (parity oracle)

    def __init__(self, store_resolver=logstore_for_path, metrics_reporters=None):
        _configure_arrow_pool()
        from delta_tpu.utils.alloc import tune_allocator

        tune_allocator()
        super().__init__(
            json_handler=HostJsonHandler(store_resolver),
            parquet_handler=HostParquetHandler(store_resolver),
            fs_client=HostFileSystemClient(store_resolver),
            expression_handler=HostExpressionHandler(),
            metrics_reporters=metrics_reporters,
        )
