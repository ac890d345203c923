"""Operation: OPTIMIZE ... WHERE ss_sold_date_sk = d ZORDER BY (item,
customer, ticket) on a day's partition that late micro-batches keep
landing in, as the owner of a date-partitioned fact table fed by a
stream schedules it (upstream's documented `OPTIMIZE events WHERE date
>= ... ZORDER BY`; "Z-Ordering is not idempotent": every run
re-clusters all files of the partitions the predicate keeps).

One client, one `Table`, the public entry points:
`table.optimize().where(col(...) == lit(d)).execute_zorder_by(*columns)`
on the library's default engine, from the call to the returned
`OptimizeMetrics`, commit and post-commit hooks included. Before it, in
`prepare` (untimed, inside the window, so it counts against `ops_per_s`
as it does for the table's owner): one late micro-batch lands in the
day's partition through the library's writer
(`delta_tpu.api.write_table`, `late_files` files of `file_rows` rows,
one commit), and the files that commits older than the newest one
removed are deleted from storage, as upstream's VACUUM does on a longer
clock (`TMPDIR` is small and an operation writes the partition anew).

The warm-up: a cold load, the first operation (whose OPTIMIZE
coalesces the sink's micro-batch files and its late batch's,
BASELINE.json `configs[2]`'s work, and compiles the curve's program),
then one more operation of the mix, so the window starts on the steady
shape. A late batch lands before the first operation too: the table is
at version 48 when it is handed over, so every OPTIMIZE is the commit
of an even version and every tenth commit, whose post-commit hook
writes the table's checkpoint, is an OPTIMIZE. The replay's program is
compiled for the byte width of the largest row a remove refers back to
(`ops/replay.py::_fa_pack`), which is 1 where every file removed lies
in a checkpoint's first rows and 2 otherwise: were the late batch the
tenth commit, the OPTIMIZE after it would be of the first kind and all
others of the second, and a window would meet a shape that two
operations of warm-up had not (a finding: PERF.md).

`check` trusts nothing the program returns: it reads `_delta_log` and
the data files by `reference/zorder_oracle.py` (numpy and pyarrow
alone) and holds the operation to the configuration's guarantees. On
every operation: the commit file (guarantee 2: its removes exactly the
day's live files at the version before by the driver's own replay, its
adds the day's and no other's, `dataChange` false on every one,
`operation` OPTIMIZE with `zOrderBy` the columns), the rows by the adds'
`numRecords` and by the new files' footers, and an order-free digest of
three columns of the new files against the same digest of what was
landed, kept from batch to batch. In `full`, on the window's last
operation once the window has closed: the new files equal to the
reference's expected output, row for row, all columns (guarantees 1 and
3), and every new file's statistics equal to what pyarrow computes from
the file (guarantee 4); on that one and on the warm-up's two: a cold
load (`system.drop_caches()`, `system.load()`) that has to hold the
same live set (guarantee 2). The warm-up's first operation, the
coalesce of the sink's files, is held to the reference row for row too,
but after the window: its files stay on storage until then (`kept`),
and the comparison goes on the closing operation's account under
`first_optimize_*`, so the reference's seconds (some twenty at 4,748
files) are no part of `setup_s`.
"""

from __future__ import annotations

import hashlib
import os
import time

import pyarrow.parquet as pq

from chipbench.drivers.scan_under_ingest_ckpt import held_now
from chipbench.reference import zorder_oracle as oracle

KIND = "optimize-zorder"
PARTITION_BY = "ss_sold_date_sk"
SPANS = ("command.optimize", "optimize.plan", "optimize.read",
         "optimize.keys", "optimize.curve", "optimize.gather",
         "optimize.write", "optimize.commit")


def optimize_zorder(table, day_sk: int, columns):
    """The command, as a user of the library gives it."""
    from delta_tpu.expressions import col, lit

    return (table.optimize().where(col(PARTITION_BY) == lit(day_sk))
            .execute_zorder_by(*columns))


def curve_bucket(rows: int) -> int:
    """The padded row count the curve's program is compiled for
    (`ops/zorder.py::curve_keys`)."""
    from delta_tpu.ops.replay import pad_bucket

    return pad_bucket(rows, min_bucket=1024)


def paths_of(actions) -> str:
    """How many paths, and the digest of them in ascending order: what
    two sets of files are compared by (a day lands in 4,740)."""
    return path_set(a["path"] for a in actions)


def path_set(paths) -> str:
    paths = sorted(paths)
    digest = hashlib.sha256("\n".join(paths).encode()).hexdigest()
    return f"{len(paths)} paths, sha256 {digest[:16]}"


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        self.manifest = manifest
        # its own where it has one (the tests' broken systems)
        self.optimize = getattr(system, "optimize_zorder", optimize_zorder)
        self.log_dir = os.path.join(manifest.table_path, "_delta_log")
        self.replay = oracle.Replay(self.log_dir)
        self.rows = manifest.rows_a_date        # of the day's partition
        self.digest = manifest.day_digest       # `key_digest` of them
        self.bucket = curve_bucket(self.rows)
        self.late = list(manifest.late)
        self.table = None
        self.before = None      # the day before's live paths: never touched
        self.optimized = 0      # operations so far, the warm-up's included
        self.vacuumed = -1      # the newest commit whose removes are deleted
        self.first = None       # the first operation's rows, inputs and adds,
        self.kept = set()       # and their paths: compared after the window
        self.warming = False
        self.window_t0 = None
        self.took = None

    def warm_up(self, run_op, schedule) -> None:
        started = time.perf_counter()
        self.table, snapshot = self.system.load(self.manifest.table_path)
        live = self.replay.at(snapshot.version)
        self.before = paths_of(oracle.in_partition(
            live, PARTITION_BY, self.manifest.day_sk - 1))
        loaded = time.perf_counter()
        self.warming = True
        run_op(next(schedule))      # the sink's files into one bin
        first = time.perf_counter()
        run_op(next(schedule))      # the steady shape
        self.warming = False
        made = ", ".join(f"{name} {took:.2f} s"
                         for name, took in self.manifest.took.items())
        print(f"set-up: fixture ({made}); cold load {loaded - started:.2f} "
              f"s; first OPTIMIZE and its checks {first - loaded:.2f} s; one "
              f"more operation and its checks "
              f"{time.perf_counter() - first:.2f} s", flush=True)

    # -- the table's owner --------------------------------------------------

    def vacuum(self) -> None:
        """Delete the files that commits older than the newest one
        removed: the newest commit's are what its check still reads."""
        newest = self.replay.version
        for version in range(self.vacuumed + 1, newest):
            for action in oracle.read_commit(self.log_dir, version):
                if "remove" in action and (
                        action["remove"]["path"] not in self.kept):
                    path = os.path.join(self.manifest.table_path,
                                        action["remove"]["path"])
                    if os.path.exists(path):
                        os.remove(path)
        self.vacuumed = max(self.vacuumed, newest - 1)

    def land_late_batch(self) -> int:
        import delta_tpu.api as dta

        if not self.late:
            raise RuntimeError(
                "the late batches are used up: the configuration needs "
                "more `late_batches` for a system this fast")
        batch = self.late.pop(0)
        if curve_bucket(self.rows + batch.num_rows) != self.bucket:
            raise RuntimeError(
                f"a late batch would take the day past {self.bucket} rows, "
                "the bucket the curve's program was compiled for: the "
                "window would compile inside it")
        version = dta.write_table(self.manifest.table_path, batch,
                                  mode="append",
                                  target_rows_per_file=self.manifest.file_rows)
        self.rows += batch.num_rows
        self.digest = oracle.wrapping_sum(self.digest,
                                          oracle.key_digest(batch))
        return version

    def prepare(self, params):
        if self.window_t0 is None and not self.warming:
            self.window_t0 = time.perf_counter()
        self.vacuum()
        return self.land_late_batch()    # the version it landed as

    def timed(self, prep):
        self.began_unix_ns = time.time_ns()
        began = time.perf_counter()
        metrics = self.optimize(self.table, self.manifest.day_sk,
                                self.manifest.zorder_by)
        self.took = time.perf_counter() - began
        return metrics

    # -- the check ----------------------------------------------------------

    def check(self, prep, answer, full: bool):
        first = not self.optimized
        self.optimized += 1
        # by rows: the window's last, and with it the warm-up's first
        # (stashed below; the reference's seconds stay out of the set-up).
        # The warm-up's second operation is the steady shape again, which
        # the window's last stands for; its cold load is the first from a
        # checkpoint of the table after an OPTIMIZE, so that one stays
        by_rows = full and not self.warming
        day = self.manifest.day_sk
        held, version = prep, prep + 1
        compared = [("version", answer.version, version),
                    ("late_batch_version", held, self.replay.version + 1)]
        inputs = oracle.in_partition(self.replay.at(held), PARTITION_BY, day)
        if not os.path.exists(oracle.commit_path(self.log_dir, version)):
            compared.append(("commit_in_log", False, True))
            return KIND, compared
        commit = oracle.read_commit(self.log_dir, version)
        adds = [a["add"] for a in commit if "add" in a]
        removes = [a["remove"] for a in commit if "remove" in a]
        [info] = [a["commitInfo"] for a in commit if "commitInfo" in a]
        live = self.replay.at(version)
        compared += [
            ("commit_in_log", True, True),
            ("operation", info.get("operation"), "OPTIMIZE"),
            ("zorder_by", zorder_by_of(info), list(self.manifest.zorder_by)),
            ("removed_paths", paths_of(removes), paths_of(inputs)),
            ("removes_data_change", sum(r["dataChange"] for r in removes), 0),
            ("adds_data_change", sum(a["dataChange"] for a in adds), 0),
            ("adds_of_other_partitions", sum(
                a["partitionValues"] != {PARTITION_BY: str(day)}
                for a in adds), 0),
            ("files_removed", answer.num_files_removed, len(inputs)),
            ("files_added", answer.num_files_added, len(adds)),
            ("live_files_of_the_day", paths_of(oracle.in_partition(
                live, PARTITION_BY, day)), paths_of(adds)),
            ("live_files_of_the_day_before", paths_of(oracle.in_partition(
                live, PARTITION_BY, day - 1)), self.before),
            ("live_files_of_other_days", sum(
                a["partitionValues"][PARTITION_BY] not in (str(day),
                                                           str(day - 1))
                for a in live.values()), 0),
            ("rows_by_num_records", sum(
                oracle.stated_rows(a) for a in adds), self.rows),
        ]
        new_paths = [a["path"] for a in adds]
        try:
            compared += [
                ("rows_by_footers",
                 oracle.footer_rows(self.manifest.table_path, new_paths),
                 self.rows),
                ("key_digest", oracle.key_digest(oracle.read_files(
                    self.manifest.table_path, new_paths,
                    oracle.KEY_DIGEST_COLUMNS)), self.digest)]
            if full and first and self.warming:
                self.first = (self.rows, inputs, adds)
                self.kept = {a["path"] for a in inputs + adds}
            if by_rows:
                compared += self.check_rows(self.rows, inputs, adds)
                if self.first:
                    compared += [
                        ("first_optimize_" + name, got, want)
                        for name, got, want in self.check_rows(*self.first)]
                    self.first = None
            if full:
                compared += self.check_cold_load(version, live)
        except OSError as e:
            compared.append(("new_files", f"unreadable: {e}", "whole"))
        self.report(version, inputs, adds, by_rows)
        return KIND, compared

    def check_rows(self, rows, inputs, adds) -> list:
        """Guarantees 1, 3 and 4 against the reference: the `rows` rows
        of `inputs`, their order in `adds`, the statistics."""
        root = self.manifest.table_path
        started = time.perf_counter()
        rows_in = oracle.read_files(root, [a["path"] for a in inputs])
        want = oracle.expected_files(rows_in, self.manifest.zorder_by,
                                     len(adds))
        got = [pq.read_table(os.path.join(root, a["path"])) for a in adds]
        out_of_place = sum(
            not g.equals(w) for g, w in zip(got, want)) + abs(
                len(got) - len(want))
        stats_off = sum(
            oracle.stated_stats(a, g.schema) != oracle.file_stats(g)
            for a, g in zip(adds, got))
        compared = [
            ("rows_in", rows_in.num_rows, rows),
            ("rows_out", sum(g.num_rows for g in got), rows),
            ("row_digest_out", oracle.wrapping_sum(
                *(oracle.row_digest(g) for g in got)),
             oracle.row_digest(rows_in)),
            ("files_not_the_references_row_for_row", out_of_place, 0),
            ("files_whose_stats_are_not_the_files", stats_off, 0)]
        print(f"full check against the reference: {rows_in.num_rows} rows "
              f"of {len(inputs)} files into {len(adds)}, "
              f"{time.perf_counter() - started:.2f} s", flush=True)
        return compared

    def check_cold_load(self, version: int, live: dict) -> list:
        """A process that has never seen the table finds the same."""
        self.system.drop_caches()
        _, cold = self.system.load(self.manifest.table_path)
        num_files, _, paths = self.system.state(cold)
        return [("cold_load_version", cold.version, version),
                ("cold_load_num_files", num_files, len(live)),
                ("cold_load_paths", path_set(paths.to_pylist()),
                 path_set(live))]

    def report(self, version, inputs, adds, full) -> None:
        where = ("in the warm-up" if self.warming else
                 f"{time.perf_counter() - self.window_t0:.3f} s into the "
                 "window")
        print(f"OPTIMIZE at version {version}, checked {where}"
              f"{' in full' if full else ''}: {self.rows} rows, "
              f"{len(inputs)} files ({sum(a['size'] for a in inputs)} bytes) "
              f"into {len(adds)} ({sum(a['size'] for a in adds)} bytes), "
              f"{self.took:.3f} s: {held_now()}", flush=True)
        if full and not self.warming:
            self.report_spans()

    def report_spans(self) -> None:
        """The span table of the closing operation, where the run
        records spans (a traced one)."""
        from delta_tpu import obs

        mine = [s for s in obs.get_finished_spans()
                if s.name in SPANS and s.start_unix_ns >= self.began_unix_ns]
        for s in sorted(mine, key=lambda s: s.start_unix_ns):
            print(f"  span {s.name}: {s.duration_ns / 1e6:.1f} ms {s.attrs}",
                  flush=True)


def zorder_by_of(info: dict):
    """`zOrderBy` of a commitInfo: a list, or the JSON text of one, as
    Delta writers put operation parameters."""
    import json

    said = info.get("operationParameters", {}).get("zOrderBy")
    return json.loads(said) if isinstance(said, str) else said
