"""Operation: a reader that holds a snapshot plans a scan of
`lo <= x < hi`. Where the mix's `commits` draw is above 0, a writer's
batch of that many commits lands first (outside the timed interval) and
the operation is `table.update()` and the plan on the new snapshot,
timed together. `position` in [0, 1) places the range over the live
files and `width_share` is the share of them it spans."""

from __future__ import annotations

import hashlib

from chipbench.gen.deltalog import X_STEP, digest_of


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        self.manifest = manifest
        self.table = self.snapshot = self.live = None

    def warm_up(self, run_op, schedule) -> None:
        """Load the table, then operations of the schedule until one has
        refreshed: every shape of the window has then run once."""
        self.table, self.snapshot = self.system.load(
            self.manifest.table_path)
        for params in schedule:
            if run_op(params) == "refresh":
                break

    def prepare(self, params):
        landed = int(params["commits"])
        if landed:
            if len(self.manifest.staged) < landed:
                raise RuntimeError(
                    "the staged commits are used up: the configuration's "
                    "mix needs more `staged_commits` for a system this fast")
            self.manifest.land(landed)
            self.live = None
        if self.live is None:
            self.live = self.manifest.live_ids()
        n = len(self.live)
        width = max(1, int(params["width_share"] * n))
        rank = int(params["position"] * (n - width))
        lo = (int(self.live[rank]) + 1) * X_STEP
        hi = (int(self.live[rank + width - 1]) + 1) * X_STEP + 1
        return landed, lo, hi

    def timed(self, prep):
        landed, lo, hi = prep
        if landed:
            self.snapshot = self.system.refresh(self.table)
        return self.system.plan(self.snapshot, lo, hi)

    def check(self, prep, answer, full: bool):
        landed, lo, hi = prep
        want = self.manifest.scan_expected(lo, hi)
        got = hashlib.sha256("\n".join(sorted(answer)).encode()).hexdigest()
        compared = [("planned_files", len(answer), len(want)),
                    ("planned_paths_sha256", got, digest_of(want))]
        if landed:
            compared.append(("version", self.snapshot.version,
                             self.manifest.version))
        return ("refresh" if landed else "plan"), compared
