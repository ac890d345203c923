"""Host-side thread parallelism for I/O-bound table operations.

The reference keeps a family of named daemon thread pools
(`spark/src/main/scala/org/apache/spark/sql/delta/util/threads/` —
`DeltaThreadPool.scala`, `SparkThreadLocalForwardingThreadPoolExecutor`)
for parallel LIST/DELETE in VACUUM (`commands/VacuumCommand.scala:224`),
parallel manifest reads in CONVERT, and async post-commit work. The JAX
engine is single-process, so the equivalent here is a plain shared
`ThreadPoolExecutor` wrapper: ordered `map`, `submit`, and a bounded
default size. Note CPython joins executor workers at interpreter exit —
in-flight I/O (e.g. an unlink against a dead mount) delays shutdown
until it returns; `shutdown(wait=False)` only stops new work.
"""

from __future__ import annotations

import os
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Iterable, List, Optional, Sequence, TypeVar

T = TypeVar("T")
R = TypeVar("R")


def default_io_threads() -> int:
    """Worker count for I/O-bound and GIL-releasing native work.

    Deliberately floored at 16 rather than trusting `os.cpu_count()`:
    containerized/cgroup environments (including this one) routinely
    advertise 1 CPU while the host schedules many more, and measured
    native-scan throughput here scales ~4x from 1 to 16 threads on a
    "1-CPU" box. Oversubscription on a genuinely single-core machine
    costs a few percent; undersubscription costs multiples. Override
    with DELTA_TPU_THREADS."""
    env = os.environ.get("DELTA_TPU_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return min(32, max(16, (os.cpu_count() or 1) * 4))


def default_scan_threads() -> int:
    """Worker count for CPU-bound native parsing. Unlike I/O threads,
    oversubscribing a genuinely single-core host HURTS here (measured
    ~2x slower at 16 threads: context switches plus the multi-builder
    merge path replace the single-builder move path), so this trusts
    the schedulable-CPU set. Override with DELTA_TPU_SCAN_THREADS."""
    env = os.environ.get("DELTA_TPU_SCAN_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    try:
        return min(32, len(os.sched_getaffinity(0)))
    except AttributeError:  # non-Linux
        return min(32, os.cpu_count() or 1)


_DEFAULT_WORKERS = default_io_threads()


class DeltaThreadPool:
    """Named daemon pool with ordered map semantics."""

    def __init__(self, name: str, max_workers: Optional[int] = None):
        self.name = name
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers or _DEFAULT_WORKERS,
            thread_name_prefix=f"delta-tpu-{name}")

    def submit(self, fn: Callable[..., R], *args, **kwargs) -> "Future[R]":
        return self._pool.submit(fn, *args, **kwargs)

    def map(self, fn: Callable[[T], R], items: Iterable[T]) -> List[R]:
        """Apply `fn` to every item concurrently; results in input order.
        The first exception propagates (after all tasks were submitted)."""
        futures = [self._pool.submit(fn, it) for it in items]
        return [f.result() for f in futures]

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


_SHARED: Optional[DeltaThreadPool] = None


def shared_pool() -> DeltaThreadPool:
    """The process-wide pool used by VACUUM/CONVERT/listing."""
    global _SHARED
    if _SHARED is None:
        _SHARED = DeltaThreadPool("io")
    return _SHARED


_SCAN: Optional[DeltaThreadPool] = None


def scan_pool() -> DeltaThreadPool:
    """The process-wide pool for CPU-bound leaf work in native code that
    releases the GIL (Arrow kernels, numpy passes): as many workers as
    `default_scan_threads()` counts, so it never oversubscribes."""
    global _SCAN
    if _SCAN is None:
        _SCAN = DeltaThreadPool("scan", default_scan_threads())
    return _SCAN


def settled(futures: Iterable[Future]) -> list:
    """Every task's result once ALL have ended; the first error after
    that (`write/ckpt_pipeline.py::_run_serial`'s rule: whoever cleans
    up after the error must not race a task still running)."""
    results, first = [], None
    for f in futures:
        try:
            results.append(f.result())
        except BaseException as e:
            results.append(None)
            first = first or e
    if first is not None:
        raise first
    return results


def parallel_map(fn: Callable[[T], R], items: Sequence[T],
                 min_parallel: int = 8) -> List[R]:
    """Ordered parallel map over an I/O-bound function; falls back to a
    sequential loop for tiny inputs where pool dispatch costs more than
    it saves."""
    if len(items) < min_parallel:
        return [fn(it) for it in items]
    return shared_pool().map(fn, items)
