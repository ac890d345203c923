"""Pipelined snapshot load: overlap storage I/O, parse, and ingest
across chunked windows of the commit log.

The serial product path is phase-sequential — read ALL commit bytes,
then one monolithic parse, then extraction, then device replay — so a
cold load pays storage latency and parse CPU back to back. The
reference hides exactly this behind Spark's task pipeline
(`Snapshot.scala` loadActions is a distributed scan); a single-process
engine has to hide it behind an explicit producer/consumer pipeline,
the same overlap structure a training-input pipeline uses to keep an
accelerator fed.

Structure (two stage threads + the calling thread, bounded queues):

    reader thread   windows the commit list into ~64MB chunks and
                    fills one buffer per window via the shared I/O pool
                    (leaf reads only — never nested pool work)
    parser thread   native scanner (lazy stats) or Arrow read_json per
                    window; both release the GIL and are internally
                    multithreaded, so ONE parser thread saturates
    caller          consumes parsed windows in order (ordered
                    small-action resolution), then merges the
                    per-window replay-key sidecars into one dense
                    first-appearance coding and dispatches the device
                    replay BEFORE the final Arrow concat — the device
                    sorts while the host assembles

Backpressure: both queues are bounded by DELTA_TPU_PIPELINE_DEPTH
(default 2 windows), so at most depth+1 window buffers are resident per
stage boundary. Error propagation: a failing stage forwards its
exception down the queue chain; the consumer re-raises it after setting
the stop event, draining both queues, and joining both threads — no
stage ever blocks on a queue without polling the stop event, so a
mid-window failure can never hang the load or leak a thread.

Env knobs:
  DELTA_TPU_PIPELINE=on|off|force  (default on; off = serial path;
                                    on engages only where overlap can
                                    win — see `profitable`; force
                                    engages everywhere)
  DELTA_TPU_PIPELINE_WINDOW_BYTES  (default 64MB)
  DELTA_TPU_PIPELINE_DEPTH         (default 2 windows per queue)
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from delta_tpu import obs

_WINDOWS = obs.counter("pipeline.windows")
_WINDOW_FALLBACKS = obs.counter("pipeline.window_fallbacks")
_PART_BYTES_PREFETCHED = obs.counter("pipeline.part_bytes_prefetched")
_BYTES_READ = obs.counter("pipeline.bytes_read")
_READ_STALL_NS = obs.counter("pipeline.read_stall_ns")
_PARSE_STALL_NS = obs.counter("pipeline.parse_stall_ns")
_INGEST_STALL_NS = obs.counter("pipeline.ingest_stall_ns")
_READQ_DEPTH = obs.histogram("pipeline.read_queue_depth")
_PARSEQ_DEPTH = obs.histogram("pipeline.parse_queue_depth")
# same instrument as replay/device_parse.py: absorbed device-parse
# exceptions bump the cataloged parse fallback counter at this site
_PARSE_FALLBACKS = obs.counter("parse.device_fallbacks")

_DEFAULT_WINDOW_BYTES = 64 << 20
_DEFAULT_DEPTH = 2
# listing deferred the stat: assume a typical commit size for windowing
# (same nominal value the serial path uses for its compile heuristic)
_NOMINAL_COMMIT_BYTES = 8192
_POLL_S = 0.05
_JOIN_S = 30.0


def enabled() -> bool:
    return os.environ.get("DELTA_TPU_PIPELINE", "on").lower() not in (
        "off", "0", "false", "no")


def forced() -> bool:
    """`DELTA_TPU_PIPELINE=force` engages the pipeline even where the
    profitability gate would prefer the serial path (A/B runs, tests)."""
    return os.environ.get("DELTA_TPU_PIPELINE", "").lower() == "force"


def profitable(engine, commit_infos, allow_native: bool) -> bool:
    """Engage only where overlap can beat the serial path.

    The native direct reader (`scan_commit_files`) already acquires
    LOCAL commit bytes and scans them in one C++ round-trip with no
    interpreter copies — measured strictly faster than windowed
    staging on warm local storage, so the pipeline stands down there.
    It engages when byte acquisition is the bottleneck it can hide:
    any non-local path (object stores, remote mounts — per-file
    latency overlaps with parse), or no native scanner (the generic
    parse is slow enough that windows pipeline against it)."""
    if forced():
        return True
    if not allow_native:
        return True
    os_path = getattr(engine.fs, "os_path", None)
    if os_path is None:
        return True
    return any(os_path(p) is None for _, p, _ in commit_infos)


def window_bytes() -> int:
    try:
        return max(1, int(os.environ.get("DELTA_TPU_PIPELINE_WINDOW_BYTES",
                                         _DEFAULT_WINDOW_BYTES)))
    except ValueError:
        return _DEFAULT_WINDOW_BYTES


def pipeline_depth() -> int:
    try:
        return max(1, int(os.environ.get("DELTA_TPU_PIPELINE_DEPTH",
                                         _DEFAULT_DEPTH)))
    except ValueError:
        return _DEFAULT_DEPTH


def resolve_sizes(
    engine,
    commit_infos: Sequence[Tuple[int, str, int]],
) -> List[Tuple[int, str, int]]:
    """Fill in stat-deferred (-1) sizes so windows split on REAL bytes
    rather than the nominal estimate — but only when every deferred path
    is local, where a stat is microseconds. On remote stores a stat
    round-trip costs as much as the GET it precedes, so deferred sizes
    are left alone: windows split on the nominal estimate and the read
    stage fetches whole blobs without needing sizes up front. A local
    file that fails to stat keeps its -1 — the read stage surfaces the
    proper vanished-commit error (same contract as the serial path)."""
    from delta_tpu.utils.threads import parallel_map

    deferred = [p for _, p, s in commit_infos if int(s) < 0]
    if not deferred:
        return list(commit_infos)
    os_path = getattr(engine.fs, "os_path", None)
    if os_path is None or any(os_path(p) is None for p in deferred):
        return list(commit_infos)

    def stat(info):
        v, p, s = info
        if int(s) >= 0:
            return info
        try:
            return (v, p, engine.fs.file_status(p).size)
        except OSError:
            return info

    return parallel_map(stat, list(commit_infos))


def plan_windows(
    commit_infos: Sequence[Tuple[int, str, int]],
) -> List[List[Tuple[int, str, int]]]:
    """Split (version, path, size) infos into contiguous windows of
    roughly `window_bytes()` listed bytes each (a window always takes
    at least one file)."""
    target = window_bytes()
    wins: List[List[Tuple[int, str, int]]] = []
    cur: List[Tuple[int, str, int]] = []
    cur_bytes = 0
    for info in commit_infos:
        size = int(info[2])
        if size < 0:
            size = _NOMINAL_COMMIT_BYTES
        cur.append(info)
        cur_bytes += size + 1
        if cur_bytes >= target:
            wins.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        wins.append(cur)
    return wins


# ------------------------------------------------------- queue plumbing

_DONE = object()


class _Cancelled(Exception):
    """Internal: the consumer set the stop event; unwind quietly."""


class _StageError:
    """An exception crossing a queue boundary toward the consumer."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _put(q: "queue.Queue", item, stop: threading.Event, stall) -> None:
    # delta-lint: disable=obs-span-leak (audited: stall accounting runs
    # once per queue hand-off inside stage threads — a span here would
    # add a trace node per window per stage; the counter is the right
    # aggregate and the span clock is unaffected)
    t0 = time.perf_counter_ns()
    while True:
        if stop.is_set():
            raise _Cancelled()
        try:
            q.put(item, timeout=_POLL_S)
            break
        except queue.Full:
            continue
    # delta-lint: disable=obs-span-leak (audited: see above)
    stall.inc(time.perf_counter_ns() - t0)


def _get(q: "queue.Queue", stop: threading.Event, stall):
    # delta-lint: disable=obs-span-leak (audited: see _put)
    t0 = time.perf_counter_ns()
    while True:
        if stop.is_set():
            raise _Cancelled()
        try:
            item = q.get(timeout=_POLL_S)
            break
        except queue.Empty:
            continue
    # delta-lint: disable=obs-span-leak (audited: see _put)
    stall.inc(time.perf_counter_ns() - t0)
    return item


def _drain(q: "queue.Queue") -> None:
    while True:
        try:
            q.get_nowait()
        except queue.Empty:
            return


def _offer_error(q: "queue.Queue", exc: BaseException,
                 stop: threading.Event, stall) -> None:
    try:
        _put(q, _StageError(exc), stop, stall)
    except _Cancelled:
        pass  # consumer already unwinding; it drains the queues


# ------------------------------------------------------------- stages


@dataclass
class _Window:
    """Read-stage output: one window's bytes assembled into a single
    newline-terminated buffer (every parser consumes the same layout,
    whether the bytes came from the sized buffered read or from
    per-blob fetches)."""

    index: int
    infos: List[Tuple[int, str, int]]
    buf: bytearray
    starts: np.ndarray
    versions: np.ndarray
    nbytes: int


@dataclass
class _Parsed:
    """Parse-stage output for one window, normalized across the native
    and generic parsers. `keys`/`uniq` are None on the generic path (or
    when percent-decoding collapsed path spellings); `dv_any` is
    conservatively True there too."""

    index: int
    block: pa.Table
    others: List[Tuple[int, int, dict]]
    keys: Optional[object]
    uniq: Optional[pa.Array]
    dv_any: bool
    stats_thunk: Optional[object]
    n_files: int
    nbytes: int


def _assemble_blobs(
    blobs: List[Tuple[int, bytes]],
) -> Tuple[bytearray, np.ndarray, np.ndarray]:
    """Lay per-file blobs out in the same newline-terminated buffer
    format `_read_commits_buffer` produces, so every parser path
    (native scan, Arrow, generic) consumes one layout."""
    sizes = np.fromiter((len(b) for _, b in blobs), np.int64,
                        count=len(blobs))
    starts = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum(sizes + 1, out=starts[1:])
    buf = bytearray(int(starts[-1]))
    mv = memoryview(buf)
    for (_, b), off, sz in zip(blobs, starts[:-1], sizes):
        off = int(off)
        sz = int(sz)
        mv[off:off + sz] = b
        mv[off + sz] = 0x0A
    versions = np.fromiter((v for v, _ in blobs), np.int64,
                           count=len(blobs))
    return buf, starts, versions


def _read_window(engine, index: int,
                 win: List[Tuple[int, str, int]]) -> _Window:
    from delta_tpu.replay.columnar import _read_commits_buffer
    from delta_tpu.utils.threads import parallel_map

    with obs.span("pipeline.read_window", index=index, files=len(win)) as sp:
        read = None
        blob_read = not all(int(s) >= 0 for _, _, s in win)
        if not blob_read:
            read = _read_commits_buffer(engine, win)
            if read is None:
                # a listed size disagreed with the bytes read
                _WINDOW_FALLBACKS.inc()
                blob_read = True
        if read is None:
            # whole-blob fetches (ordered, shared I/O pool) — the
            # planned path for stat-deferred remote windows, the
            # fallback when a sized read mismatched
            blobs = parallel_map(
                lambda vp: (vp[0], engine.fs.read_file(vp[1])),
                [(v, p) for v, p, _ in win])
            read = _assemble_blobs(blobs)
        buf, starts, versions = read
        nbytes = int(starts[-1])
        _BYTES_READ.inc(nbytes)
        sp.set_attrs(bytes=nbytes, blob_read=blob_read)
        return _Window(index, win, buf, starts, versions, nbytes)


def _reader_main(engine, windows, out_q, stop) -> None:
    from delta_tpu.resilience import default_policy

    # Storage ops inside _read_window already retry transients through
    # io_call (shared policy + breaker); stacking the full policy here
    # again would multiply attempts (~max_attempts² per window) and
    # double-count breaker failures. The outer policy only restarts a
    # whole window ONCE, with no sleeps of its own, if the inner budget
    # exhausts mid-window; permanent errors (corruption, missing files)
    # still flow to the consumer via _offer_error for a fail-fast drain
    # + clean join.
    policy = default_policy().with_overrides(max_attempts=2, base_s=0.0,
                                             cap_s=0.0)
    try:
        for i, win in enumerate(windows):
            item = policy.call(lambda: _read_window(engine, i, win))
            _put(out_q, item, stop, _READ_STALL_NS)
        _put(out_q, _DONE, stop, _READ_STALL_NS)
    except _Cancelled:
        pass
    except BaseException as e:
        _offer_error(out_q, e, stop, _READ_STALL_NS)


def _parse_window(w: _Window, allow_native: bool,
                  lazy_stats: bool, allow_device: bool = False) -> _Parsed:
    from delta_tpu.replay import columnar as C

    with obs.span("pipeline.parse_window", index=w.index,
                  files=len(w.infos), bytes=w.nbytes) as sp:
        from delta_tpu.parallel import gate

        if gate.parse_route(w.nbytes, allow_device) == "device":
            from delta_tpu.replay.device_parse import parse_window_device
            from delta_tpu.resilience import device_faults

            out = device_faults.guarded(
                "parse",
                lambda: parse_window_device(w.buf, w.starts, w.versions,
                                            lazy_stats=lazy_stats),
                _PARSE_FALLBACKS)
            if out.value is not None:
                table, others, keys, uniq, dv_any, sthunk = out.value
                sp.set_attrs(rows=table.num_rows, device=True)
                return _Parsed(w.index, table, others, keys, uniq,
                               dv_any, sthunk, len(w.infos), w.nbytes)
            # mid-flight fallback: the host branches below reuse the
            # window buffer, and calibration prices the device attempt
            # PLUS the host parse against the "device" prediction
            if out.fell_back is None:
                obs.gate_fell_back("parse", "host",
                                   reason="device-parse-unavailable")
        if allow_native:
            from delta_tpu.replay.native_parse import parse_window_native

            with obs.gate_observation("parse", "host"):
                out = parse_window_native(w.buf, w.starts, w.versions,
                                          lazy_stats=lazy_stats)
            if out is not None:
                table, others, keys, uniq, dv_any, sthunk = out
                sp.set_attrs(rows=table.num_rows, native=True)
                return _Parsed(w.index, table, others, keys, uniq,
                               dv_any, sthunk, len(w.infos), w.nbytes)
        with obs.gate_observation("parse", "host"):
            generic = C._parse_buffer_generic(w.buf, w.starts, w.versions)
            if generic is None:
                # line accounting disagreed; per-file byte extents are
                # exact (verified read or blob assembly), so slicing the
                # buffer back into per-file blobs is equivalent to the
                # serial path's re-read
                mv = memoryview(w.buf)
                blobs = [(int(v), bytes(mv[int(s):int(e) - 1]))
                         for v, s, e in zip(w.versions, w.starts[:-1],
                                            w.starts[1:])]
                generic = C.parse_commit_batch(blobs)
        tbl, versions, orders, _ = generic
        small_rows: List[Tuple[int, int, dict]] = []
        gen_blocks: List[pa.Table] = []
        if tbl is not None:
            small_rows = C._extract_small_rows(tbl, versions, orders)
            for col in ("add", "remove"):
                b = C._extract_file_actions(tbl, col, versions, orders)
                if b is not None:
                    gen_blocks.append(b)
        block = (pa.concat_tables(gen_blocks) if gen_blocks
                 else C.CANONICAL_FILE_ACTION_SCHEMA.empty_table())
        sp.set_attrs(rows=block.num_rows, native=False)
        return _Parsed(w.index, block, small_rows, None, None, True, None,
                       len(w.infos), w.nbytes)


def _parser_main(in_q, out_q, stop, allow_native, lazy_stats,
                 allow_device=False) -> None:
    try:
        while True:
            item = _get(in_q, stop, _PARSE_STALL_NS)
            if item is _DONE or isinstance(item, _StageError):
                _put(out_q, item, stop, _PARSE_STALL_NS)
                return
            parsed = _parse_window(item, allow_native, lazy_stats,
                                   allow_device)
            _put(out_q, parsed, stop, _PARSE_STALL_NS)
    except _Cancelled:
        pass
    except BaseException as e:
        _offer_error(out_q, e, stop, _PARSE_STALL_NS)


# ------------------------------------------------------------ assembly


class _MergedScan:
    """Duck-typed stand-in for a ScanResult over the merged window
    stream — exactly the attributes the early-replay launch closure
    reads (`_columnarize_log_segment`)."""

    __slots__ = ("path_code", "path_new", "refs", "n_uniq", "is_add",
                 "n_rows")

    def __init__(self, keys, is_add: np.ndarray):
        self.path_code = keys.path_code
        self.path_new = keys.path_new
        self.refs = keys.refs
        self.n_uniq = keys.n_uniq
        self.is_add = is_add
        self.n_rows = len(is_add)


def _col_numpy(blocks: List[pa.Table], name: str, dtype) -> np.ndarray:
    out = []
    for b in blocks:
        for ch in b.column(name).chunks:
            out.append(ch.to_numpy(zero_copy_only=False))
    if not out:
        return np.empty(0, dtype)
    return np.concatenate(out)


def parse_commits_pipelined(
    engine,
    windows: List[List[Tuple[int, str, int]]],
    *,
    allow_native: bool,
    lazy_stats: bool,
    launch=None,
    allow_device: bool = False,
):
    """Drive the read → parse → ingest pipeline over `windows` and
    return (ParsedSpan over ALL windows, pending replay handle or None,
    total bytes read). The span is shaped exactly like the serial
    path's fresh span (one consolidated block, merged replay-key
    sidecar, combined stats thunk), so caching and downstream
    consumption are unchanged.

    Exceptions from any stage propagate to the caller after both queues
    drain and both stage threads join."""
    from delta_tpu.replay import columnar as C
    from delta_tpu.replay.native_parse import merge_replay_keys

    depth = pipeline_depth()
    read_q: "queue.Queue" = queue.Queue(maxsize=depth)
    parsed_q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = threading.Event()
    n_files = sum(len(w) for w in windows)
    with obs.span("pipeline.load", windows=len(windows),
                  files=n_files) as sp:
        # obs.wrap: bind this span as the stage threads' parent (the
        # contextvar stack does not cross thread boundaries)
        reader = threading.Thread(
            target=obs.wrap(_reader_main),
            args=(engine, windows, read_q, stop),
            name="delta-pipeline-read", daemon=True)
        parser = threading.Thread(
            target=obs.wrap(_parser_main),
            args=(read_q, parsed_q, stop, allow_native, lazy_stats,
                  allow_device),
            name="delta-pipeline-parse", daemon=True)
        reader.start()
        parser.start()
        parts: List[_Parsed] = []
        try:
            while True:
                item = _get(parsed_q, stop, _INGEST_STALL_NS)
                _READQ_DEPTH.observe(read_q.qsize())
                _PARSEQ_DEPTH.observe(parsed_q.qsize())
                if item is _DONE:
                    break
                if isinstance(item, _StageError):
                    raise item.exc
                _WINDOWS.inc()
                parts.append(item)
        finally:
            stop.set()
            _drain(read_q)
            _drain(parsed_q)
            reader.join(timeout=_JOIN_S)
            parser.join(timeout=_JOIN_S)

        row_blocks = [p.block for p in parts if p.block.num_rows]
        others = [r for p in parts for r in p.others]
        merged = merge_replay_keys(
            [(p.keys, p.uniq, p.block.num_rows) for p in parts])
        pending = None
        if (merged is not None and launch is not None and row_blocks
                and not any(p.dv_any for p in parts)):
            versions = _col_numpy(row_blocks, "version", np.int64)
            orders = _col_numpy(row_blocks, "order", np.int32)
            is_add = _col_numpy(row_blocks, "is_add", bool)
            # dispatch BEFORE the Arrow concat: the device sorts the
            # merged key stream while the host assembles the table
            pending = launch(_MergedScan(merged, is_add), versions,
                             orders.astype(np.int32, copy=False))
        block = (pa.concat_tables(row_blocks) if row_blocks
                 else C.CANONICAL_FILE_ACTION_SCHEMA.empty_table())
        sthunk = C._combined_stats_thunk(
            [(p.block, p.stats_thunk) for p in parts if p.block.num_rows])
        span = C.ParsedSpan(
            block=block, others=others, keys=merged,
            stats_thunk=C._OnceThunk(sthunk) if sthunk is not None else None,
            n_files=n_files, nbytes=C._span_nbytes(block, others))
        nbytes = sum(p.nbytes for p in parts)
        sp.set_attrs(bytes=nbytes, rows=block.num_rows,
                     merged_keys=merged is not None)
        return span, pending, nbytes


def prefetch_file_bytes(engine, paths: Sequence[str], depth: int = 2):
    """Yield each file's raw bytes in input order with a bounded
    read-ahead on the shared I/O pool, so consuming file i overlaps
    reading file i+1. The device checkpoint page decode consumes part
    BYTES (the one-lane plan builder parses them itself), so the
    engine's parquet-table prefetcher can't serve it — this is the
    byte-level twin of `HostParquetHandler.read_parquet_files`. Reads
    are leaf pool tasks; a cancelled tail never leaks a future."""
    from collections import deque

    from delta_tpu.utils.threads import shared_pool

    paths = list(paths)
    if len(paths) <= 1:
        for p in paths:
            yield engine.fs.read_file(p)
        return
    pool = shared_pool()
    read = obs.wrap(engine.fs.read_file)
    pending: deque = deque()
    i = 0
    try:
        while pending or i < len(paths):
            while i < len(paths) and len(pending) <= depth:
                if pending:
                    _PART_BYTES_PREFETCHED.inc()
                pending.append(pool.submit(read, paths[i]))
                i += 1
            yield pending.popleft().result()
    finally:
        for fut in pending:
            fut.cancel()
