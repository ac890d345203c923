"""Operation: one pass over the mix's TPC-DS queries in their fixed
order, as upstream's `TPCDSBenchmark` times an iteration of its list:
each `execute_select(<the text as it was written, LIMIT and all>,
catalog=catalog)` on the library's default engine, read out to Python
rows. A pass and not a query is the operation, because a median over
eight kinds of query would jump between two queries' latencies as the
window's last cycle is cut. Closed loop, one client, no think time.

The catalog and the ten tables' snapshots are opened once, in set-up,
and held; every query reads its data files again, as the engine does
for any caller. The reference (`reference/tpcds_oracle.py`, SQLite over
the generator's Arrow tables) answers every query once, in set-up, with
no `LIMIT`; every operation's every answer is compared with that, row by
row (`tpcds_queries.broken_rows`).

The warm-up runs passes until one has compiled nothing and uploaded no
operand lane, and at least two: the first SQL operator of a process
turns on `jax_enable_x64` (`ops/sqlops.py::_ensure_x64`), which keys
every program anew, so the first pass compiles in one state of it and
the second in the other.
"""

from __future__ import annotations

import collections
import resource
import statistics
import time

from chipbench import tpcds_queries
from chipbench.harness import COMPILE_EVENT
from chipbench.reference import tpcds_oracle

MOST_WARM_UP_PASSES = 6


class Driver:
    def __init__(self, system, manifest):
        self.manifest = manifest
        self.queries = [tpcds_queries.QUERIES[name]
                        for name in manifest.queries]
        # its own where it has one (the tests' broken systems)
        self.open_catalog = getattr(system, "open_catalog",
                                    tpcds_queries.open_catalog)
        self.run_query = getattr(system, "run_query",
                                 tpcds_queries.run_query)
        oracle = tpcds_oracle.Oracle(manifest.tables,
                                     [q.text for q in self.queries])
        started = time.perf_counter()
        self.want = {q.name: oracle.answer(q.text, q.kinds)
                     for q in self.queries}
        print(f"reference: SQLite loaded {oracle.loaded} in "
              f"{oracle.load_s:.2f} s and answered "
              f"{ {n: len(rows) for n, rows in self.want.items()} } rows in "
              f"{time.perf_counter() - started:.2f} s", flush=True)
        manifest.release()
        self.catalog = None
        self.window = None      # per query, its times in the window

    def warm_up(self, run_op, schedule) -> None:
        import jax

        started = time.perf_counter()
        self.catalog = self.open_catalog(self.manifest.root,
                                         self.manifest.table_paths)
        print(f"catalog: ten snapshots opened in "
              f"{time.perf_counter() - started:.2f} s", flush=True)
        compiled = []       # (program, seconds) of every compile

        def on_compile(event, start, end, fun_name="?", **_kw):
            if event == COMPILE_EVENT:
                compiled.append((str(fun_name), end - start))

        # for the warm-up's length only: JAX keeps a listener, and what
        # it can reach, for the life of the process
        jax.monitoring.register_event_time_span_listener(on_compile)
        try:
            self._until_warm(run_op, schedule, compiled)
        finally:
            jax.monitoring.unregister_event_time_span_listener(on_compile)
        self.window = {q.name: [] for q in self.queries}

    def _until_warm(self, run_op, schedule, compiled) -> None:
        from delta_tpu import obs

        misses = obs.counter("sql.operand_cache_misses")
        for n in range(1, MOST_WARM_UP_PASSES + 1):
            before, missed = len(compiled), misses.value
            started = time.perf_counter()
            run_op(next(schedule))
            by_program = collections.defaultdict(list)
            for program, seconds in compiled[before:]:
                by_program[program].append(round(seconds, 2))
            print(f"warm-up pass {n}: {time.perf_counter() - started:.2f} s, "
                  f"{len(compiled) - before} programs compiled or retrieved "
                  f"in {sum(s for _p, s in compiled[before:]):.2f} s, by "
                  f"program (s a shape): {dict(by_program)}; "
                  f"{misses.value - missed} operand lanes uploaded",
                  flush=True)
            if n >= 2 and len(compiled) == before and misses.value == missed:
                return
        raise RuntimeError(
            f"pass {MOST_WARM_UP_PASSES} of the warm-up still compiled or "
            "uploaded: the window would too")

    def prepare(self, params):
        return None

    def timed(self, prep):
        answers = []
        for query in self.queries:
            started = time.perf_counter()
            rows = self.run_query(self.catalog, query)
            answers.append((query, rows, time.perf_counter() - started))
        return answers

    def check(self, prep, answer, full: bool):
        """Every query of every pass in full: the rows counted against
        the reference's (or the LIMIT), and the rows that are not the
        reference's, or not where its ORDER BY puts them, against 0."""
        compared = []
        for query, rows, seconds in answer:
            want = self.want[query.name]
            compared.append((f"{query.name}.rows", len(rows),
                             min(tpcds_queries.LIMIT, len(want))))
            compared.append((f"{query.name}.broken_rows",
                             tpcds_queries.broken_rows(rows, want, query), 0))
            if self.window is not None:
                self.window[query.name].append(seconds)
        if full and self.window is not None:     # the window's last pass
            print("queries of the window (median s, longest s): " + ", ".join(
                f"{name} {statistics.median(took):.3f} {max(took):.3f}"
                for name, took in self.window.items()), flush=True)
            print(f"process RSS at its peak: "
                  f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10}"
                  " MB", flush=True)
        return "pass", compared
