"""Operation: YCSB Core Workload E (95% scans, 5% inserts; `drivers/
scan_under_ingest.py`, whose Zipfian, FNV scramble, scan length and key
space are used as they stand) on a table of NEXmark bids that a
streaming sink keeps (`gen/nexmark_bids.py`), where a record is one
micro-batch.

A scan: the reader plans, on the snapshot it holds, the event-time
window `t_c <= dateTime < t_(c+L)`: `L` micro-batches from the start of
the one commit `c` wrote, as zone-aware `datetime`s (`dateTime` is a
Delta `timestamp`). Late events make its answer reach one batch past
`c + L`, and the truncated millisecond of a stored max a few files back
into batch `c - 1`. Three scans of four add NEXmark Query 2's
selection, `auction IN` five ids among the auctions opened inside the
window, which cuts the answer to the batches in which a listed auction
was in flight (7 atoms on two columns); the fourth is the window alone
(2 atoms). An insert: one staged commit lands first, outside the timed
interval, and `table.update()` and the plan on the new snapshot, with
the selection, are timed together.

The mix's draws are points of [0, 1): `start` and `length` as in
`scan_under_ingest`; `selection` under 0.25 is the window alone;
`id0`..`id4` are the listed auctions' places among the window's.
"""

from __future__ import annotations

import hashlib
import os
import time

from chipbench import bid_queries
from chipbench.drivers.scan_under_ingest import ScrambledZipfian, scan_length
from chipbench.drivers.scan_under_ingest_ckpt import held_now
from chipbench.gen.deltalog import digest_of
from chipbench.gen.nexmark_bids import instant

ALONE_BELOW = 0.25      # of the scans, the share that is the window alone
LISTED = 5              # auctions of a selection
SETTLED_BYTES = 64 << 20    # a refresh that grows the process by less
MOST_WARM_REFRESHES = 8     # has found it settled; give up after these


def resident_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        # its own where it has one (the tests' broken systems)
        self.plan = getattr(system, "plan_bids", bid_queries.plan_bids)
        self.manifest = manifest
        self.batch = manifest.stats.batch
        self.commits = ScrambledZipfian(
            manifest.version + 1 + len(manifest.staged))
        self.table = self.snapshot = None
        self.shapes = set()     # of the operations prepared so far
        self.update_s = 0.0     # the last refresh's `update()` alone

    def warm_up(self, run_op, schedule) -> None:
        """Load the table, then operations of the schedule until a
        refresh, a window alone and a window with its selection have
        run (every shape of the window has then compiled) and the
        process has stopped growing: a refresh holds the old state and
        index beside the new ones for a moment, and a long-lived reader
        has long since touched the memory for that, while a new process
        first pays a page fault for every page of it (seconds, on a
        fresh machine: PERF.md, Findings, PR 33)."""
        self.table, self.snapshot = self.system.load(
            self.manifest.table_path)
        grown = [resident_bytes()]
        for params in schedule:
            if run_op(params) == "refresh":
                grown.append(resident_bytes())
            settled = (len(grown) >= 3
                       and grown[-1] - grown[-2] < SETTLED_BYTES)
            if ({"refresh", "alone", "selection"} <= self.shapes and settled
                    or len(grown) > MOST_WARM_REFRESHES):
                break
        print(f"warm-up: process RSS after the load and each refresh "
              f"{grown}", flush=True)

    def prepare(self, params):
        landed = int(params["refresh"])
        if landed:
            if len(self.manifest.staged) < landed:
                raise RuntimeError(
                    "the staged commits are used up: the mix needs more "
                    "`staged_commits` for a system this fast")
            self.manifest.land(landed)
        c = self.commits.item(params["start"], self.manifest.version)
        length = scan_length(params["length"])
        t0 = self.batch.start_us(c)
        t1 = self.batch.start_us(c + length)
        alone = (not landed) and params["selection"] < ALONE_BELOW
        auctions = () if alone else tuple(
            self.batch.first_auction(c)
            + int(params[f"id{k}"] * length * self.batch.auctions)
            for k in range(LISTED))
        self.shapes.add("refresh" if landed else
                        "alone" if alone else "selection")
        return landed, t0, t1, auctions

    def timed(self, prep):
        landed, t0, t1, auctions = prep
        if landed:
            start = time.perf_counter()
            self.snapshot = self.system.refresh(self.table)
            self.update_s = time.perf_counter() - start
        return self.plan(self.snapshot, instant(t0), instant(t1), auctions)

    def check(self, prep, answer, full: bool):
        """Every operation in full: the plan is the set the manifest's
        stored stats admit, no file more and none less, on the newest
        landed version."""
        landed, t0, t1, auctions = prep
        want = self.manifest.scan_expected(t0, t1, auctions)
        got = hashlib.sha256("\n".join(sorted(answer)).encode()).hexdigest()
        if landed:
            print(f"after the refresh to version {self.manifest.version} "
                  f"(update() {self.update_s * 1e3:.0f} ms): {held_now()}",
                  flush=True)
        return ("refresh" if landed else "plan"), [
            ("planned_files", len(answer), len(want)),
            ("planned_paths_sha256", got, digest_of(want)),
            ("version", self.snapshot.version, self.manifest.version)]
