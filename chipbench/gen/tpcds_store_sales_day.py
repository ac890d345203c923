"""Generator of the configuration `tpcds-store-sales-day-zorder`: two
sold dates of TPC-DS `store_sales` at the documented 3 TB run's size
(scale factor 3000: 8,639,936,081 rows over 1,823 sold dates,
4,739,406 rows a date), partitioned by `ss_sold_date_sk` as upstream's
`TPCDSDataLoad` partitions the table, written by this library's writer
(`delta_tpu.api.write_table`) on the default engine.

- *the day before*, `d - 1`: its rows in one commit, left as a finished
  day is left (one large file; the writer cuts none by size). The
  commit creates the table. OPTIMIZE's predicate never matches it.
- *the day*, `d`: its rows as a streaming sink lands them, files of
  `file_rows` rows (1,000), `files_a_commit` files a commit
  (micro-batch): 4,739 files of 1,000 rows and one of 406.
- *late batches*, staged in memory and landed by the driver, one commit
  each: `late_files` files of `file_rows` rows of the day.

The rows are `gen/tpcds_sf1.py`'s `store_sales` (its `Columns`, its
tickets of 1..23 rows, dsdgen's pricing as recalled there), called with
the key domains of scale factor 3000; the sold date is then the one
date, and a ticket's number one of `tickets` (720,000,000) drawn
without order, as a date's tickets lie anywhere in the table's run of
them. The dimensions are not loaded: OPTIMIZE reads none.

While the day before is being written (Arrow, off the interpreter's
lock), the day's rows are drawn on a second thread.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import glob
import os
import time

import numpy as np
import pyarrow as pa

from chipbench.gen import tpcds_sf1
from chipbench.reference import zorder_oracle

PARTITION_BY = "ss_sold_date_sk"
TICKET = "ss_ticket_number"


def rows_of_a_date(rows: int, date_sk: int, params: dict, rng) -> pa.Table:
    """`rows` rows of `store_sales` sold on `date_sk`."""
    made = tpcds_sf1.store_sales(rows, rng, dict(params["domains"]), 1)
    at = made.schema.get_field_index(PARTITION_BY)
    made = made.set_column(at, made.schema.field(at),
                           pa.array(np.full(rows, date_sk, np.int32)))
    # the generator's tickets count from 1 in the order they come; a
    # date's are anywhere among the table's
    ticket = np.asarray(made.column(TICKET).combine_chunks()) - 1
    numbers = rng.choice(int(params["tickets"]), int(ticket[-1]) + 1,
                         replace=False) + 1
    at = made.schema.get_field_index(TICKET)
    return made.set_column(at, made.schema.field(at),
                           pa.array(numbers[ticket], pa.int64()))


@dataclasses.dataclass
class Manifest:
    """What was landed, for the harness's fixture line and the driver."""

    table_path: str
    day_sk: int
    rows_a_date: int
    day_files: int
    day_digest: int         # `zorder_oracle.key_digest` of the day's rows
    late: list              # Arrow tables, one a late micro-batch
    late_files: int
    file_rows: int
    zorder_by: list
    version: int
    load_actions: int
    log_bytes: int
    took: dict              # seconds by phase, for the set-up's account

    def num_files(self) -> int:
        return self.day_files + 1


def generate(root: str, params: dict, seed: int) -> Manifest:
    import delta_tpu.api as dta

    rows, day = int(params["rows_a_date"]), int(params["sold_date_sk"])
    file_rows = int(params["file_rows"])
    a_commit = int(params["files_a_commit"]) * file_rows
    late_rows = int(params["late_files"]) * file_rows
    path = os.path.join(root, "store_sales")
    rngs = [np.random.default_rng([seed, i]) for i in range(3)]
    took, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        now = time.perf_counter()
        took[name] = took.get(name, 0.0) + now - t0
        t0 = now

    before = rows_of_a_date(rows, day - 1, params, rngs[0])
    lap("generate")
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        the_day = pool.submit(rows_of_a_date, rows, day, params, rngs[1])
        version = dta.write_table(path, before, mode="error",
                                  partition_by=[PARTITION_BY])
        assert version == 0, version
        del before
        lap("land the day before")
        the_day = the_day.result()
    late = rows_of_a_date(int(params["late_batches"]) * late_rows, day,
                          params, rngs[2])
    lap("generate")
    for start in range(0, rows, a_commit):
        version = dta.write_table(path, the_day.slice(start, a_commit),
                                  mode="append",
                                  target_rows_per_file=file_rows)
    lap("land the day")
    digest = zorder_oracle.key_digest(the_day)
    actions = log_bytes = 0
    for commit in glob.glob(os.path.join(path, "_delta_log", "*.json")):
        log_bytes += os.path.getsize(commit)
        with open(commit, "rb") as f:
            actions += sum(1 for _ in f)
    lap("account")
    return Manifest(
        table_path=path, day_sk=day, rows_a_date=rows,
        day_files=-(-rows // file_rows), day_digest=digest,
        late=[late.slice(start, late_rows)
              for start in range(0, late.num_rows, late_rows)],
        late_files=int(params["late_files"]), file_rows=file_rows,
        zorder_by=list(params["zorder_by"]), version=version,
        load_actions=actions, log_bytes=log_bytes, took=took)
