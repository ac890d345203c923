"""Benchmark workloads.

- `replay`: the north-star — snapshot state reconstruction over a
  synthetic `_delta_log` (BASELINE.md config 2: 100k commits / 10M adds
  at `--scale full`; smaller presets for CI). Compares the sequential
  reference replay, the single-device kernel, and (where >1 device) the
  sharded path, plus end-to-end table load including JSON parse.
- `checkpoint`: checkpoint write throughput from a reconstructed state
  (config 2's GB/s half).
- `optimize`: bin-packing compaction + ZORDER rewrite (configs 3/4).
- `merge`: upsert MERGE throughput (reference MergeBenchmark role).
- `streaming`: micro-batch ingest + per-batch stats (config 5).
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import sys
import time

import numpy as np
import pyarrow as pa

from benchmarks.harness import Benchmark, QueryResult

SCALES = {
    "smoke": dict(commits=50, files_per_commit=20, rows=5_000),
    "small": dict(commits=1_000, files_per_commit=100, rows=50_000),
    "medium": dict(commits=10_000, files_per_commit=100, rows=200_000),
    "large": dict(commits=30_000, files_per_commit=100, rows=500_000),
    "full": dict(commits=100_000, files_per_commit=100, rows=1_000_000),
}


def synth_delta_log(path: str, commits: int, files_per_commit: int,
                    remove_fraction: float = 0.2, seed: int = 0) -> None:
    """Write a synthetic `_delta_log` directly (no data files — replay
    only touches the log)."""
    rng = np.random.default_rng(seed)
    # compact separators, as Delta writers emit them: the device JSON
    # parse (ops/json_parse.py) matches `"key":` patterns and sends
    # anything else to the host scanner
    dumps = functools.partial(json.dumps, separators=(",", ":"))
    log = os.path.join(path, "_delta_log")
    os.makedirs(log, exist_ok=True)
    protocol = '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}'
    metadata = json.dumps({
        "metaData": {
            "id": "bench", "format": {"provider": "parquet", "options": {}},
            "schemaString": '{"type":"struct","fields":[{"name":"x","type":"long","nullable":true,"metadata":{}}]}',
            "partitionColumns": [], "configuration": {},
        }
    })
    alive: list = []
    fid = 0
    for v in range(commits):
        lines = []
        if v == 0:
            lines += [protocol, metadata]
        n_rm = int(files_per_commit * remove_fraction)
        if alive and n_rm:
            for _ in range(min(n_rm, len(alive))):
                p = alive.pop(rng.integers(0, len(alive)))
                lines.append(dumps({
                    "remove": {"path": p, "deletionTimestamp": v, "dataChange": True}
                }))
        for _ in range(files_per_commit - n_rm):
            p = f"part-{fid:010d}.parquet"
            fid += 1
            alive.append(p)
            stats = dumps({"numRecords": 1000,
                           "minValues": {"x": int(fid) * 1000},
                           "maxValues": {"x": int(fid + 1) * 1000},
                           "nullCount": {"x": 0}})
            lines.append(dumps({
                "add": {"path": p, "partitionValues": {}, "size": 1 << 20,
                        "modificationTime": v, "dataChange": True,
                        "stats": stats}
            }))
        with open(os.path.join(log, f"{v:020d}.json"), "w") as f:
            f.write("\n".join(lines) + "\n")


class ReplayBenchmark(Benchmark):
    name = "replay"

    def run(self):
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.engine.tpu import TpuEngine
        from delta_tpu.replay.columnar import columnarize_log_segment
        from delta_tpu.replay.state import compute_masks_device, compute_masks_host
        from delta_tpu.log.segment import build_log_segment
        from delta_tpu.table import Table

        cfg = SCALES[self.scale]
        path = os.path.join(self.workdir, f"replay_{self.scale}")
        if not os.path.exists(os.path.join(path, "_delta_log")):
            print(f"  generating {cfg['commits']} commits...", end=" ", flush=True)
            t0 = time.perf_counter()
            synth_delta_log(path, cfg["commits"], cfg["files_per_commit"])
            print(f"{time.perf_counter() - t0:.1f}s")

        engine = TpuEngine()
        with self.timed("list+segment"):
            segment = build_log_segment(engine.fs, os.path.join(path, "_delta_log"))
        with self.timed("columnarize(parse json)"):
            columnar = columnarize_log_segment(engine, segment)
        n = columnar.num_actions

        with self.timed("replay-host-dict", extra={"actions": n}):
            live_h, _ = compute_masks_host(columnar)
        # device (includes key factorization + transfers)
        with self.timed("replay-device-e2e", 0):
            live_d, _ = compute_masks_device(columnar)
        with self.timed("replay-device-e2e", 1):
            live_d, _ = compute_masks_device(columnar)
        assert live_h.sum() == live_d.sum()

        host_ms = next(r.duration_ms for r in self.report.results
                       if r.name == "replay-host-dict")
        dev_ms = min(r.duration_ms for r in self.report.results
                     if r.name == "replay-device-e2e")
        self.metric("replay_actions_per_sec_host", n / host_ms * 1000, "actions/s")
        self.metric("replay_actions_per_sec_device", n / dev_ms * 1000, "actions/s",
                    vs_host=round(host_ms / dev_ms, 2))

        # full table load end-to-end on both engines
        for label, eng in (("host", HostEngine()), ("tpu", TpuEngine())):
            with self.timed(f"full-load-{label}"):
                snap = Table.for_path(path, eng).latest_snapshot()
                _ = snap.num_files
        return self.report


class CheckpointBenchmark(Benchmark):
    name = "checkpoint"

    def run(self):
        from delta_tpu.engine.tpu import TpuEngine
        from delta_tpu.log.checkpointer import write_checkpoint
        from delta_tpu.table import Table

        cfg = SCALES[self.scale]
        path = os.path.join(self.workdir, f"replay_{self.scale}")
        if not os.path.exists(os.path.join(path, "_delta_log")):
            synth_delta_log(path, cfg["commits"], cfg["files_per_commit"])
        table = Table.for_path(path, TpuEngine())
        snap = table.latest_snapshot()
        _ = snap.num_files
        with self.timed("checkpoint-write", extra={"numFiles": snap.num_files}):
            info = write_checkpoint(table.engine, snap)
        size = info.sizeInBytes or 0
        dur_s = self.report.results[-1].duration_ms / 1000
        if size:
            self.metric("checkpoint_write_mb_per_sec", size / 1e6 / dur_s, "MB/s")
        self.metric("checkpoint_files_per_sec", snap.num_files / dur_s, "files/s")
        # re-load from checkpoint
        with self.timed("reload-from-checkpoint"):
            snap2 = Table.for_path(path, TpuEngine()).latest_snapshot()
            _ = snap2.num_files
        return self.report


class OptimizeBenchmark(Benchmark):
    name = "optimize"

    def run(self):
        import delta_tpu.api as dta
        from delta_tpu.table import Table

        cfg = SCALES[self.scale]
        rows = cfg["rows"]
        path = os.path.join(self.workdir, f"optimize_{self.scale}")
        shutil.rmtree(path, ignore_errors=True)
        rng = np.random.default_rng(1)
        n_commits = 20
        per = rows // n_commits
        for i in range(n_commits):
            data = pa.table({
                "k1": pa.array(rng.integers(0, 1 << 30, per).astype(np.int64)),
                "k2": pa.array(rng.integers(0, 1 << 30, per).astype(np.int64)),
                "k3": pa.array(rng.integers(0, 1 << 30, per).astype(np.int64)),
                "payload": pa.array(rng.normal(size=per)),
            })
            dta.write_table(path, data)
        table = Table.for_path(path)
        with self.timed("compaction", extra={"rows": rows}):
            m = table.optimize().execute_compaction()
        self.metric("compaction_files_per_sec",
                    m.num_files_removed / (self.report.results[-1].duration_ms / 1000),
                    "files/s")
        with self.timed("zorder-3col", extra={"rows": rows}):
            mz = Table.for_path(path).optimize().execute_zorder_by("k1", "k2", "k3")
        dur_s = self.report.results[-1].duration_ms / 1000
        self.metric("zorder_rows_per_sec", rows / dur_s, "rows/s")
        # curve-key kernel alone
        from delta_tpu.ops.zorder import zorder_sort_indices

        cols = [rng.integers(0, 1 << 30, rows).astype(np.int64) for _ in range(3)]
        zorder_sort_indices([c[:1000] for c in cols])  # compile
        with self.timed("curve-key-kernel", extra={"rows": rows}):
            zorder_sort_indices(cols)
        dur_s = self.report.results[-1].duration_ms / 1000
        self.metric("curve_key_rows_per_sec", rows / dur_s, "rows/s")
        return self.report


class MergeBenchmark(Benchmark):
    name = "merge"

    def run(self):
        import delta_tpu.api as dta
        from delta_tpu.commands.merge import merge
        from delta_tpu.expressions import col
        from delta_tpu.table import Table

        cfg = SCALES[self.scale]
        rows = cfg["rows"]
        path = os.path.join(self.workdir, f"merge_{self.scale}")
        shutil.rmtree(path, ignore_errors=True)
        rng = np.random.default_rng(2)
        base = pa.table({
            "id": pa.array(np.arange(rows, dtype=np.int64)),
            "v": pa.array(rng.normal(size=rows)),
        })
        dta.write_table(path, base, target_rows_per_file=max(1, rows // 20))
        n_src = rows // 10
        src = pa.table({
            "id": pa.array(np.concatenate([
                rng.choice(rows, n_src // 2, replace=False),
                np.arange(rows, rows + n_src // 2),
            ]).astype(np.int64)),
            "v": pa.array(rng.normal(size=2 * (n_src // 2))),
        })
        with self.timed("merge-upsert", extra={"source_rows": src.num_rows}):
            m = (merge(Table.for_path(path), src,
                       on=col("target.id") == col("source.id"))
                 .when_matched_update(set={"v": col("source.v")})
                 .when_not_matched_insert_all()
                 .execute())
        dur_s = self.report.results[-1].duration_ms / 1000
        self.metric("merge_source_rows_per_sec", src.num_rows / dur_s, "rows/s",
                    updated=m.num_target_rows_updated,
                    inserted=m.num_target_rows_inserted)
        return self.report


class StreamingBenchmark(Benchmark):
    name = "streaming"

    def run(self):
        from delta_tpu.streaming import DeltaSink

        cfg = SCALES[self.scale]
        rows = cfg["rows"]
        path = os.path.join(self.workdir, f"streaming_{self.scale}")
        shutil.rmtree(path, ignore_errors=True)
        rng = np.random.default_rng(3)
        sink = DeltaSink(path, query_id="bench")
        n_batches = 20
        per = max(1, rows // n_batches)
        with self.timed("ingest", extra={"batches": n_batches, "rows": rows}):
            for b in range(n_batches):
                data = pa.table({
                    "id": pa.array(np.arange(b * per, (b + 1) * per, dtype=np.int64)),
                    "v": pa.array(rng.normal(size=per)),
                })
                sink.add_batch(b, data)
        dur_s = self.report.results[-1].duration_ms / 1000
        self.metric("ingest_batches_per_sec", n_batches / dur_s, "batches/s")
        self.metric("ingest_rows_per_sec", n_batches * per / dur_s, "rows/s")
        return self.report


class TpcdsLiteBenchmark(Benchmark):
    """Star-schema load + query shapes, the role of the reference's
    TPC-DS harness (`benchmarks/src/main/scala/benchmark/
    TPCDSDataLoad.scala:71`, `TPCDSBenchmark.scala:74`). A dsdgen-scale
    run needs a Spark cluster; this generates a store_sales-shaped fact
    table (partitioned by month) plus item/date dims, loads them as
    Delta tables, and times representative query shapes through the
    framework surface: partition-pruned scans, stats-skipped range
    scans, dimension joins + aggregation (Arrow host compute — the
    framework's query-integration layer), and full-scan aggregates."""

    name = "tpcds_lite"

    FACT_ROWS = {"smoke": 50_000, "small": 1_000_000,
                 "medium": 10_000_000, "large": 25_000_000,
                 "full": 50_000_000}

    def run(self):
        import delta_tpu.api as dta
        from delta_tpu.expressions import col, lit

        rows = self.FACT_ROWS[self.scale]
        root = os.path.join(self.workdir, f"tpcds_{self.scale}")
        shutil.rmtree(root, ignore_errors=True)
        rng = np.random.default_rng(42)

        n_items = max(100, rows // 1000)
        item = pa.table({
            "i_item_sk": pa.array(np.arange(n_items, dtype=np.int64)),
            "i_brand_id": pa.array(rng.integers(0, 50, n_items)),
            "i_category_id": pa.array(rng.integers(0, 10, n_items)),
        })
        date_dim = pa.table({
            "d_date_sk": pa.array(np.arange(365 * 5, dtype=np.int64)),
            "d_year": pa.array(2019 + np.arange(365 * 5) // 365),
            "d_moy": pa.array((np.arange(365 * 5) % 365) // 31 + 1),
        })
        with self.timed("load_dims"):
            dta.write_table(os.path.join(root, "item"), item)
            dta.write_table(os.path.join(root, "date_dim"), date_dim)

        fact_path = os.path.join(root, "store_sales")
        # at least 12 chunks so every month partition exists at any scale
        chunk = min(max(1, rows // 12), 1_000_000)
        with self.timed("load_fact", extra={"rows": rows}):
            for start in range(0, rows, chunk):
                n = min(chunk, rows - start)
                ci = start // chunk
                month = ci % 12 + 1
                # each chunk covers a narrow date window (like real
                # time-ordered ingest) so per-file min/max stats are
                # tight and range queries can actually skip files
                date_base = (ci * 150) % (365 * 5 - 150)
                data = pa.table({
                    "ss_sold_date_sk": pa.array(
                        (date_base
                         + rng.integers(0, 150, n)).astype(np.int64)),
                    "ss_item_sk": pa.array(
                        rng.integers(0, n_items, n).astype(np.int64)),
                    "ss_quantity": pa.array(rng.integers(1, 100, n)),
                    "ss_sales_price": pa.array(rng.uniform(1, 500, n)),
                    "ss_month": pa.array(np.full(n, f"{month:02d}")),
                })
                dta.write_table(fact_path, data, mode="append",
                                partition_by=["ss_month"])
        dur_s = self.report.results[-1].duration_ms / 1000
        self.metric("load_rows_per_sec", rows / dur_s, "rows/s")

        import pyarrow.compute as pc

        from delta_tpu.table import Table

        snap = Table.for_path(fact_path).latest_snapshot()
        n_files = len(snap.state.add_files_table)

        # Q1: partition-pruned aggregate (one month of sales)
        with self.timed("q1_partition_prune"):
            scan1 = snap.scan(filter=col("ss_month") == lit("03"))
            t = scan1.to_arrow()
            q1 = pc.sum(t.column("ss_sales_price")).as_py() or 0.0
        self.metric("q1_files_scanned", len(scan1.files()), "files",
                    total=n_files)

        # Q2: stats-skipped range scan (narrow date window; chunks are
        # date-correlated so per-file stats prune)
        with self.timed("q2_range_skip"):
            pred = (col("ss_sold_date_sk") >= lit(100)) & (
                col("ss_sold_date_sk") < lit(130))
            scan2 = snap.scan(filter=pred)
            t = scan2.to_arrow()
            q2 = t.num_rows
        self.metric("q2_files_scanned", len(scan2.files()), "files",
                    total=n_files)

        # Q3: fact-dim join + group-by through the SQL frontend
        # (TPC-DS Q3 shape: brand revenue for one year)
        from delta_tpu.sql import sql as run_sql

        with self.timed("q3_join_groupby_sql"):
            out = run_sql(
                f"SELECT i.i_brand_id AS brand, "
                f"SUM(f.ss_sales_price) AS rev "
                f"FROM '{fact_path}' f "
                f"JOIN '{os.path.join(root, 'date_dim')}' d "
                f"ON f.ss_sold_date_sk = d.d_date_sk "
                f"JOIN '{os.path.join(root, 'item')}' i "
                f"ON f.ss_item_sk = i.i_item_sk "
                f"WHERE d.d_year = 2020 "
                f"GROUP BY i.i_brand_id ORDER BY rev DESC LIMIT 10")
            q3 = out.num_rows

        # Q4: full-scan aggregate
        with self.timed("q4_full_agg"):
            t = snap.scan(columns=["ss_quantity"]).to_arrow()
            q4 = pc.sum(t.column("ss_quantity")).as_py()

        self.metric("fact_rows", rows, "rows", q1=round(q1, 2), q2=q2,
                    q3=q3, q4=int(q4))
        return self.report


class TpcdsBenchmark(Benchmark):
    """The real TPC-DS harness: loads the 19-table TPC-DS schema as
    Delta tables (`benchmarks/tpcds_data.py`, the dsdgen role of the
    reference's `TPCDSDataLoad.scala:71`) and times every VERBATIM
    query in `benchmarks/tpcds_queries.py` through the sqlengine
    (`TPCDSBenchmark.scala:74` role) on BOTH substrates — the
    TpuEngine device spine (`ops/sqlops.py` kernels) and the
    HostEngine pandas path — plus the independent sqlite oracle as the
    external comparison column. Two timed iterations per engine query
    (cold + warm); correctness is asserted separately in
    tests/test_tpcds.py."""

    name = "tpcds"

    # store_sales rows; dims scale proportionally. "large" ≈ 1.4GB of
    # Delta-backed Parquet across the 19 tables.
    FACT_ROWS = {"smoke": 20_000, "small": 200_000,
                 "medium": 2_000_000, "large": 10_000_000,
                 "full": 25_000_000}

    def run(self):
        from benchmarks.tpcds_data import generate, load_delta
        from benchmarks.tpcds_queries import QUERIES
        from delta_tpu.catalog import Catalog
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.sqlengine import execute_select

        rows = self.FACT_ROWS[self.scale]
        root = os.path.join(self.workdir, f"tpcds_full_{self.scale}")
        shutil.rmtree(root, ignore_errors=True)
        with self.timed("load", rows=rows):
            catalog = load_delta(root, scale=rows)
        host_catalog = Catalog(root, engine=HostEngine())
        size = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(root) for f in fs)
        self.metric("dataset_bytes", size, "bytes", fact_rows=rows)

        oracle = None
        if os.environ.get("TPCDS_BENCH_ORACLE", "1") != "0":
            from tests.tpcds_sqlite_oracle import SqliteOracle

            t0 = time.perf_counter()
            oracle = SqliteOracle(generate(rows))
            n_idx = oracle.create_indexes()
            self.metric("oracle_load_ms",
                        (time.perf_counter() - t0) * 1000, "ms",
                        indexes=n_idx)

        # TPCDS_BENCH_SUBSTRATES=host|device|device,host (default both)
        wanted = [s.strip() for s in os.environ.get(
            "TPCDS_BENCH_SUBSTRATES", "device,host").split(",")
            if s.strip()]
        unknown = set(wanted) - {"device", "host"}
        if unknown or not wanted:
            raise ValueError(
                f"TPCDS_BENCH_SUBSTRATES must name device and/or "
                f"host; got {wanted!r}")
        pairs = [p for p in (("device", catalog), ("host", host_catalog))
                 if p[0] in wanted]
        totals = {s: 0.0 for s, _c in pairs}
        oracle_total, oracle_done, oracle_skipped = 0.0, 0, 0
        saved_flag = os.environ.get("DELTA_TPU_DEVICE_SQL")
        try:
            for name, q in QUERIES.items():
                for substrate, cat in pairs:
                    # pin the substrate: the device column must measure the
                    # device spine even where the link auto-gate would
                    # decline it (that cost is exactly what it reports)
                    os.environ["DELTA_TPU_DEVICE_SQL"] = (
                        "1" if substrate == "device" else "0")
                    for it in range(2):
                        t0 = time.perf_counter()
                        out = execute_select(q, catalog=cat)
                        dt = (time.perf_counter() - t0) * 1000
                        self.report.results.append(QueryResult(
                            name, it, dt, {"rows": out.num_rows,
                                           "substrate": substrate}))
                        print(f"  {name}[{substrate}:{it}]: {dt:,.1f} ms "
                              f"({out.num_rows} rows)", file=sys.stderr)
                        if it == 1:
                            totals[substrate] += dt
                if oracle is not None:
                    t0 = time.perf_counter()
                    try:
                        res = oracle.run_with_timeout(q, seconds=60.0)
                        dt = (time.perf_counter() - t0) * 1000
                        if res is None:
                            oracle_skipped += 1
                            self.report.results.append(QueryResult(
                                name, 0, dt, {"substrate": "oracle",
                                              "error": "timeout"}))
                            print(f"  {name}[oracle]: TIMEOUT",
                                  file=sys.stderr)
                            continue
                        orows = len(res)
                        self.report.results.append(QueryResult(
                            name, 0, dt, {"rows": orows,
                                          "substrate": "oracle"}))
                        oracle_total += dt
                        oracle_done += 1
                        print(f"  {name}[oracle]: {dt:,.1f} ms",
                              file=sys.stderr)
                    except Exception as exc:  # q67 rollup depth
                        oracle_skipped += 1
                        self.report.results.append(QueryResult(
                            name, 0, float("nan"),
                            {"substrate": "oracle",
                             "error": str(exc)[:120]}))
        finally:
            # never leak the substrate pin (a mid-loop
            # failure would force it process-wide)
            if saved_flag is None:
                os.environ.pop("DELTA_TPU_DEVICE_SQL", None)
            else:
                os.environ["DELTA_TPU_DEVICE_SQL"] = saved_flag
        for substrate, total in totals.items():
            self.metric(f"tpcds_warm_total_{substrate}", total, "ms",
                        queries=len(QUERIES))
        if oracle is not None:
            # cold single-run timings over the queries sqlite can run —
            # NOT comparable 1:1 with the warm engine totals; per-query
            # rows carry the honest comparison
            self.metric("tpcds_oracle_total_cold", oracle_total, "ms",
                        queries=oracle_done, skipped=oracle_skipped)
        self.metric("tpcds_warm_total",
                    totals.get("device", totals.get("host", 0.0)),
                    "ms", queries=len(QUERIES))
        return self.report


BENCHMARKS = {
    b.name: b
    for b in (ReplayBenchmark, CheckpointBenchmark, OptimizeBenchmark,
              MergeBenchmark, StreamingBenchmark, TpcdsLiteBenchmark,
              TpcdsBenchmark)
}
