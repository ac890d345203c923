"""Operation: YCSB Core Workload E ("short ranges": Cooper et al.,
SoCC 2010; 95% scans, 5% inserts) on a table that a streaming sink
keeps (`gen/deltastream.py`), where a record is one micro-batch.

A scan: the reader plans `lo <= x < hi` on the snapshot it holds, a
range of event time that covers `L` micro-batches from the one commit
`c` wrote: `lo = (c + 1) * width`, `hi = (c + L + 1) * width`. An
insert: one staged commit lands first, outside the timed interval, and
`table.update()` and the plan on the new snapshot are timed together.

The mix's draws are points of [0, 1) and get their meaning here, as
YCSB's core workload gives it: `length` is the scan length, uniform in
1..`MAX_SCAN_LENGTH`; `start` is a quantile of the Zipfian
(`ZIPFIAN_CONSTANT`) over the table's commits, whose rank is scattered
over them by the FNV-1a hash that YCSB's scrambled Zipfian uses, so the
popular commits are no neighbours; a scan starts at a landed commit.
"""

from __future__ import annotations

import hashlib

import numpy as np

from chipbench.gen.deltalog import digest_of
from chipbench.gen.deltastream import batch_width

ZIPFIAN_CONSTANT = 0.99     # YCSB's ZipfianGenerator.ZIPFIAN_CONSTANT
MAX_SCAN_LENGTH = 100       # Workload E: maxscanlength=100, uniform


def fnv1a_64(value: int) -> int:
    """YCSB's `Utils.fnvhash64` of a whole number: FNV-1a over its
    eight octets, the lowest first."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (value & 0xFF)) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        value >>= 8
    return h


def scan_length(u: float) -> int:
    return 1 + int(u * MAX_SCAN_LENGTH)


class ScrambledZipfian:
    """Quantiles of Zipfian(`ZIPFIAN_CONSTANT`) over `n` items, the
    rank scattered over the items by `fnv1a_64`. As YCSB's core
    workload does where inserts run beside the scans, `n` is fixed from
    the start and counts the items still to come; an item that is not
    there yet is drawn again (here: the hash of the rank plus `n`, then
    plus `2n`, ...), so the popular items stay the same ones while the
    table grows."""

    def __init__(self, n: int):
        weights = np.arange(1, n + 1, dtype=np.float64) ** -ZIPFIAN_CONSTANT
        self.cdf = np.cumsum(weights) / weights.sum()
        self.n = n

    def item(self, u: float, newest: int) -> int:
        """The item at quantile `u`, among the items 0..`newest`."""
        rank = min(int(np.searchsorted(self.cdf, u, side="right")),
                   self.n - 1)
        while True:
            item = fnv1a_64(rank) % self.n
            if item <= newest:
                return item
            rank += self.n      # the same rank's next draw


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        self.manifest = manifest
        self.width = batch_width(manifest.adds_per_commit)
        self.commits = ScrambledZipfian(
            manifest.version + 1 + len(manifest.staged))
        self.table = self.snapshot = None

    def warm_up(self, run_op, schedule) -> None:
        """Load the table, then operations of the schedule until one has
        refreshed: every shape of the window has then run once."""
        self.table, self.snapshot = self.system.load(
            self.manifest.table_path)
        for params in schedule:
            if run_op(params) == "refresh":
                break

    def prepare(self, params):
        landed = int(params["refresh"])
        if landed:
            if len(self.manifest.staged) < landed:
                raise RuntimeError(
                    "the staged commits are used up: the mix needs more "
                    "`staged_commits` for a system this fast")
            self.manifest.land(landed)
        c = self.commits.item(params["start"], self.manifest.version)
        lo = (c + 1) * self.width
        hi = (c + scan_length(params["length"]) + 1) * self.width
        return landed, lo, hi

    def timed(self, prep):
        landed, lo, hi = prep
        if landed:
            self.snapshot = self.system.refresh(self.table)
        return self.system.plan(self.snapshot, lo, hi)

    def check(self, prep, answer, full: bool):
        """Every operation in full: the plan is the min/max-intersection
        set, no file more and none less, on the newest landed version."""
        landed, lo, hi = prep
        want = self.manifest.scan_expected(lo, hi)
        got = hashlib.sha256("\n".join(sorted(answer)).encode()).hexdigest()
        return ("refresh" if landed else "plan"), [
            ("planned_files", len(answer), len(want)),
            ("planned_paths_sha256", got, digest_of(want)),
            ("version", self.snapshot.version, self.manifest.version)]
