"""Operation: `scan_under_ingest`'s (YCSB Core Workload E on a table a
streaming sink keeps; its Zipfian, its FNV scramble, its ranges and its
key space are used as they stand), on a table whose writer keeps
upstream's `delta.checkpointInterval` = 10 up while the reader runs
(`gen/deltastream_ckpt.py`): the landing of every tenth commit brings
a checkpoint and `_last_checkpoint` with it.

Three things differ. An operation that landed a checkpoint is of kind
`crossing`, any other landing `refresh`, the rest `plan`. The warm-up
runs the schedule until one `crossing` and one `refresh` have run, so
that every shape of the window has compiled. And `check` compares more:
on a `crossing` the snapshot's count of files and their size with the
manifest's, and on the window's closing operation the digest of all
live paths. Landing stays outside the timed interval; `Table.update()`
and the plan are timed together.
"""

from __future__ import annotations

import hashlib
import os
import time

from chipbench.drivers import scan_under_ingest


def held_now() -> str:
    """What the process holds after a crossing, on the chip and on the
    host: one index and one state, or one more of each a crossing."""
    import jax

    chip = jax.devices()[0].memory_stats() or {}
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    return (f"chip bytes_in_use {chip.get('bytes_in_use')}, "
            f"peak_bytes_in_use {chip.get('peak_bytes_in_use')}, "
            f"bytes_reserved {chip.get('bytes_reserved')}, "
            f"peak_bytes_reserved {chip.get('peak_bytes_reserved')}; "
            f"process RSS {rss}")


class Driver(scan_under_ingest.Driver):
    warming = False
    window_t0 = None    # the first operation after the warm-up begins

    def warm_up(self, run_op, schedule) -> None:
        self.table, self.snapshot = self.system.load(
            self.manifest.table_path)
        self.warming = True
        seen = set()
        for params in schedule:
            seen.add(run_op(params))
            if {"crossing", "refresh"} <= seen:
                break
        self.warming = False

    def prepare(self, params):
        before = self.manifest.checkpoint_version
        prep = super().prepare(params)
        crossed = self.manifest.checkpoint_version != before
        if self.window_t0 is None and not self.warming:
            self.window_t0 = time.perf_counter()
        self.began = time.perf_counter()
        return prep, crossed

    def timed(self, prep):
        return super().timed(prep[0])

    def check(self, prep, answer, full: bool):
        prep, crossed = prep
        kind, compared = super().check(prep, answer, full)
        kind = "crossing" if crossed else kind
        want = self.manifest
        where = ("in the warm-up" if self.warming else
                 f"{self.began - self.window_t0:.3f} s into the window")
        if crossed:     # where the sawtooth of `ops_per_s` stands
            print(f"crossing to version {want.version}, begun {where}: "
                  f"{held_now()}", flush=True)
        if full and not self.warming:
            print(f"the window's closing operation, a {kind}, began {where}",
                  flush=True)
        # every warm-up operation is checked in `full`: of those, only
        # the ones that landed something pay for the digest
        if crossed or (full and not (self.warming and kind == "plan")):
            num_files, size, paths = self.system.state(self.snapshot)
            compared += [("num_files", num_files, want.num_files()),
                         ("size_in_bytes", size, want.size_in_bytes())]
            if full:
                got = hashlib.sha256("\n".join(
                    sorted(paths.to_pylist())).encode()).hexdigest()
                compared.append(("live_paths_sha256", got, want.digest()))
        return kind, compared
