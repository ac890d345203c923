"""Operation: a process that has never seen the table asks for its
newest snapshot. The caches are dropped outside the timed interval;
timed are `Table.for_path(path).latest_snapshot()` and reading the
number of files, their total size and the column of live paths."""

from __future__ import annotations

import hashlib


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        self.manifest = manifest

    def warm_up(self, run_op, schedule) -> None:
        run_op(next(schedule))

    def prepare(self, params):
        self.system.drop_caches()
        return None

    def timed(self, prep):
        _, snapshot = self.system.load(self.manifest.table_path)
        return self.system.state(snapshot)

    def check(self, prep, answer, full: bool):
        """Count and size on every operation; the digest of the sorted
        live paths where the harness asks for the comparison in `full`:
        on the warm-up load, before the window, and on the window's
        last load, once the window has closed."""
        num_files, size, paths = answer
        want = self.manifest
        compared = [("num_files", num_files, want.num_files()),
                    ("size_in_bytes", size, want.size_in_bytes()),
                    ("live_paths", len(paths), want.num_files())]
        if full:
            got = hashlib.sha256(
                "\n".join(sorted(paths.to_pylist())).encode()).hexdigest()
            compared.append(("live_paths_sha256", got, want.digest()))
        return "load", compared
