"""Operation: YCSB Core Workload E (95% scans, 5% inserts; `drivers/
scan_under_ingest.py`, whose Zipfian, FNV scramble, scan length and key
space are used as they stand, and `drivers/bid_windows_under_ingest.py`,
whose warm-up and operations these are) on a table of TPC-DS
`store_sales` that a streaming sink keeps (`gen/tpcds_store_sales.py`),
where a record is one micro-batch.

A scan: the reader plans, on the snapshot it holds, the sold dates
`day(c) <= ss_sold_date_sk <= day(c + L)`: from the date of the commit
`c` to that of the commit `L` micro-batches on (1 to 6 dates). Three
scans of four add one of TPC-DS Query 28's six buckets, its quantity
range the bucket's and its list price, coupon amount and wholesale cost
drawn as `query28.tpl` draws them (28 atoms once the OR over the three
ranges is distributed, 24 of them on decimal lanes); the fourth is the
window alone (2 atoms). An insert: one staged commit lands first,
outside the timed interval, and `table.update()` and the plan on the new
snapshot, with a bucket, are timed together.

The mix's draws are points of [0, 1): `start` and `length` as in
`scan_under_ingest`; `bucket` under 0.25 is the window alone, and from
there on one of the six buckets by equal shares (an insert's plan
takes its bucket from `refresh_bucket`, by equal shares too);
`list_price`, `coupon_amt` and `wholesale_cost` are the bucket's three
numbers.
"""

from __future__ import annotations

import time

from chipbench import sales_queries
from chipbench.drivers import bid_windows_under_ingest as bids
from chipbench.drivers.scan_under_ingest import ScrambledZipfian, scan_length

ALONE_BELOW = bids.ALONE_BELOW  # of the scans, the share that is the window alone
# query28.tpl's six ranges of ss_quantity, both ends in
QUANTITIES = ((0, 5), (6, 10), (11, 15), (16, 20), (21, 25), (26, 30))
LIST_PRICE_TO = 190                     # p uniform in 0..190
COUPON_AMT_TO = 18_000                  # c in 0..18,000
WHOLESALE_COST_TO = 80                  # w in 0..80


def bucket_of(params: dict):
    """`(q_lo, q_hi, p, c, w)` of the operation's draws; None: the
    window alone."""
    if int(params["refresh"]):
        share = params["refresh_bucket"]
    elif params["bucket"] < ALONE_BELOW:
        return None
    else:
        share = (params["bucket"] - ALONE_BELOW) / (1.0 - ALONE_BELOW)
    return QUANTITIES[min(int(share * len(QUANTITIES)),
                          len(QUANTITIES) - 1)] + (
        int(params["list_price"] * (LIST_PRICE_TO + 1)),
        int(params["coupon_amt"] * (COUPON_AMT_TO + 1)),
        int(params["wholesale_cost"] * (WHOLESALE_COST_TO + 1)))


class Driver(bids.Driver):
    """`bid_windows_under_ingest.Driver`'s warm-up (a refresh, both
    plan shapes, a process that has stopped growing) and its check of
    every answer (the manifest's `scan_expected` of what `prepare`
    returned: count, sha256 of the sorted paths, version), with this
    table's window and selection."""

    def __init__(self, system, manifest):
        super().__init__(system, manifest)
        # its own where it has one (the tests' broken systems)
        self.plan = getattr(system, "plan_sales", sales_queries.plan_sales)

    def prepare(self, params):
        landed = int(params["refresh"])
        if landed:
            if len(self.manifest.staged) < landed:
                raise RuntimeError(
                    "the staged commits are used up: the mix needs more "
                    "`staged_commits` for a system this fast")
            self.manifest.land(landed)
        c = self.commits.item(params["start"], self.manifest.version)
        day_lo = int(self.batch.day(c))
        day_hi = int(self.batch.day(c + scan_length(params["length"])))
        bucket = bucket_of(params)
        # the warm-up's names for the two shapes are the sibling's
        self.shapes.add("refresh" if landed else
                        "alone" if bucket is None else "selection")
        return landed, day_lo, day_hi, bucket

    def timed(self, prep):
        landed, day_lo, day_hi, bucket = prep
        if landed:
            start = time.perf_counter()
            self.snapshot = self.system.refresh(self.table)
            self.update_s = time.perf_counter() - start
        return self.plan(self.snapshot, day_lo, day_hi, bucket)
