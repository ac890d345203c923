"""Operation: `scan_under_ingest`'s, draw for draw (YCSB Core Workload
E on a table a streaming sink keeps; its Zipfian, its FNV scramble, its
ranges, its key space and its warm-up as they stand). `check` alone
differs: it also holds the *whole* snapshot to the manifest.

The sibling compares what a plan returns, so it sees the files of the
planned range and no others. A landed commit's 20 removes fall anywhere
in the table, and a refresh that rebuilds the live mask of every row
held (the resident key lanes' way: `parallel/resident.py`) can leave a
bit standing, or clear one, outside every range the window happens to
plan. So on the warm-up's refresh and on the window's closing operation
the state the reader holds is read whole, `system.state(snapshot)`: the
number of live files, their total size and the sha256 of the sorted
live paths against the generator's manifest, limit 0, after the timed
interval. Not on every operation: a digest of 6.0M paths takes seconds,
and the warm-up's plans are checked in `full` too.
"""

from __future__ import annotations

import hashlib

from chipbench.drivers import scan_under_ingest


class Driver(scan_under_ingest.Driver):
    warming = False

    def warm_up(self, run_op, schedule) -> None:
        self.warming = True
        super().warm_up(run_op, schedule)
        self.warming = False

    def check(self, prep, answer, full: bool):
        kind, compared = super().check(prep, answer, full)
        if full and (kind == "refresh" or not self.warming):
            num_files, size, paths = self.system.state(self.snapshot)
            want = self.manifest
            got = hashlib.sha256(
                "\n".join(sorted(paths.to_pylist())).encode()).hexdigest()
            compared += [("num_files", num_files, want.num_files()),
                         ("size_in_bytes", size, want.size_in_bytes()),
                         ("live_paths_sha256", got, want.digest())]
        return kind, compared
