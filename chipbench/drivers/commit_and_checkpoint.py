"""Operation: a writer on upstream's defaults commits the version that
is a multiple of `delta.checkpointInterval`, and its post-commit hook
writes the table's checkpoint before `commit()` returns.

One client, one `Table`, the public entry points:
`table.create_transaction_builder("WRITE").build()`, `add_files`,
`remove_files`, `commit()`, `CommitResult.post_commit_snapshot`. The
commits are the source's next ones (`gen/deltalog_writer.py`: 80 adds
and 20 removes with the source's fields, `modificationTime` and
`deletionTimestamp` the commit's version). The commits between two
checkpoints, nine of them, are made in `prepare` by the same client
through the same calls: inside the window, outside the operation's
latency, so they count against `ops_per_s` as they do for a writer.
Also in `prepare`, untimed: the checkpoint files older than the newest
two are deleted, as upstream's metadata clean-up does on a longer
clock (a window writes 0.7 GB of them and `TMPDIR` is small).

`check` trusts nothing the program returns about its checkpoint (a
hook that fails is swallowed by design, `hooks.checkpoint_hook` is not
critical): it reads the file and `_last_checkpoint` back by
`reference/ckpt_write_oracle.py`, pyarrow alone, and compares them with
the reference's sequential replay. On every operation: the commit's
version, the files in `_delta_log`, the snapshot's count and size, the
file's rows by kind, the sum of `add.size`, and the hint's three
fields. In `full` (the warm-up, and the window's last operation once
the window has closed): the digests of the file's paths, stats strings
and modification times, of the writer's own live paths, and a cold
load (`system.drop_caches()`, `system.load()`) that has to start from
that checkpoint and hold the same state.

The one thing the chip computes in an operation, the stats block of
`checkpoint.aggregate`, reaches no byte of the file by design, so no
reading of the file can see it: where the run records spans (a traced
run; a plain one records none, and compares nothing here), `check`
reads the operation's `checkpoint.aggregate` span and holds what it
says of the block, lane by lane, to the reference's table: a kernel
that returned zeros is not correct.
"""

from __future__ import annotations

import os
import time

import pyarrow as pa

from chipbench.drivers.scan_under_ingest_ckpt import held_now
from chipbench.gen import deltalog, deltalog_writer
from chipbench.gen.deltastream_ckpt import checkpoint_name
from chipbench.reference import ckpt_write_oracle as oracle


def file_actions(commit):
    """The commit's adds and removes as the library's actions, with the
    fields `deltalog.add_line` / `remove_line` write."""
    from delta_tpu.models.actions import AddFile, RemoveFile

    adds = [AddFile(path=deltalog.path_of(i), partitionValues={},
                    size=deltalog.FILE_SIZE, modificationTime=commit.version,
                    dataChange=True, stats=deltalog.stats_of(i))
            for i in range(commit.add_lo, commit.add_hi)]
    removes = [RemoveFile(path=deltalog.path_of(int(i)),
                          deletionTimestamp=commit.version, dataChange=True)
               for i in commit.removed]
    return adds, removes


def crossings() -> tuple:
    """How often `Table.update()` has met a checkpoint past the version
    it held, and how often it then loaded the table anew."""
    from delta_tpu import obs

    return (obs.counter("snapshot.checkpoint_crossings").value,
            obs.counter("snapshot.checkpoint_crossing_reloads").value)


class Driver:
    def __init__(self, system, manifest):
        self.system = system
        self.manifest = manifest
        self.log_dir = os.path.join(manifest.table_path, "_delta_log")
        self.interval = manifest.checkpoint_interval
        self.replay = oracle.Replay(manifest.version, manifest.alive,
                                    deltalog.FILE_SIZE)
        self.table = self.snapshot = None
        self.warming = False
        self.window_t0 = None   # the first operation after the warm-up
        self.ops = []           # of the window: (seconds, checkpoint bytes)

    def warm_up(self, run_op, schedule) -> None:
        """The cold load of the table (the 2.4M-row replay, in set-up)
        and one whole operation: every program the window runs has then
        compiled, the stats block at its bucket and the replay of the
        check's cold load among them."""
        self.table, self.snapshot = self.system.load(
            self.manifest.table_path)
        self.system.state(self.snapshot)     # the state is made on demand
        self.warming = True
        run_op(next(schedule))
        self.warming = False

    # -- the writer ---------------------------------------------------------

    def next_commit(self):
        if not self.manifest.staged:
            raise RuntimeError(
                "the pending commits are used up: the configuration needs "
                "more `pending_commits` for a system this fast")
        return self.manifest.staged.pop(0)

    def commit(self, adds, removes):
        txn = self.table.create_transaction_builder("WRITE").build()
        txn.add_files(adds)
        txn.remove_files(removes)
        result = txn.commit()
        self.snapshot = result.post_commit_snapshot
        return (result.version, self.snapshot.num_files,
                self.snapshot.size_in_bytes)

    def clean_up(self) -> None:
        found = sorted(name for name in os.listdir(self.log_dir)
                       if name.endswith(".checkpoint.parquet"))
        for name in found[:-2]:
            os.remove(os.path.join(self.log_dir, name))

    def prepare(self, params):
        if self.window_t0 is None and not self.warming:
            self.window_t0 = time.perf_counter()
            self.window_unix_ns = time.time_ns()
            self.crossed_before = crossings()
        self.clean_up()
        between = []    # what the commits before the operation's returned
        while (self.replay.version + 1) % self.interval:
            commit = self.next_commit()
            answer = self.commit(*file_actions(commit))
            self.replay.apply(commit)
            between.append((answer, (commit.version, self.replay.num_files(),
                                     self.replay.size_in_bytes())))
        commit = self.next_commit()
        return commit, file_actions(commit), between

    def timed(self, prep):
        commit, actions, _ = prep
        self.began_unix_ns = time.time_ns()
        began = time.perf_counter()
        answer = self.commit(*actions)
        self.ended = time.perf_counter()
        self.took = self.ended - began
        return answer

    # -- the check ----------------------------------------------------------

    def check(self, prep, answer, full: bool):
        commit, _, between = prep
        self.replay.apply(commit)
        want = self.replay
        version = commit.version
        in_log = [os.path.exists(os.path.join(
            self.log_dir, deltalog.commit_name(v)))
            for v in range(version - len(between), version + 1)]
        compared = [
            ("commits_between", [got for got, _ in between],
             [expected for _, expected in between]),
            ("commits_in_log", sum(in_log), len(between) + 1),
            ("version", answer[0], version),
            ("num_files", answer[1], want.num_files()),
            ("size_in_bytes", answer[2], want.size_in_bytes())]
        compared += self.check_aggregate()
        path = os.path.join(self.log_dir, checkpoint_name(version))
        paths = oracle.sha256_lines(
            deltalog_writer.paths_of(want.live_ids())) if full else None
        try:
            compared += self.check_file(path, version, paths)
        except (OSError, pa.ArrowException) as e:
            # nothing can start from it: the cold load is not tried (it
            # would take another way, and compile for it)
            compared += [
                ("checkpoint_file", f"unreadable: {type(e).__name__}: {e}",
                 "whole"),
                ("cold_load_checkpoint_version", None, version)]
        else:
            if full:
                compared += self.check_states(version, paths)
        self.report(path, version, full)
        return "commit+checkpoint", compared

    def check_aggregate(self) -> list:
        """What the operation's `checkpoint.aggregate` span says of the
        stats block, against the reference's table. Nothing where no
        span was recorded. A program older than the lanes' attributes
        (the parent of PR 53) is held to the three it has."""
        from delta_tpu import obs
        from delta_tpu.ops import stats as ckstats

        spans = [s for s in obs.get_finished_spans()
                 if s.name == "checkpoint.aggregate"
                 and s.start_unix_ns >= self.began_unix_ns]
        if not spans:
            return []
        said, want = spans[-1].attrs, self.replay
        # a dispatch that failed is served by the host twin: the block
        # is then right and the chip's kernel unseen, so not correct
        where = ("device" if ckstats.device_stats_enabled(self.table.engine)
                 else "host")
        compared = [
            ("aggregate_stats_mode", said.get("stats_mode"), where),
            ("aggregate_logical_bytes", said.get("logical_bytes"),
             want.size_in_bytes()),
            ("aggregate_dv_cardinality", said.get("dv_cardinality"), 0),
            ("aggregate_distinct_partition_values",
             said.get("distinct_partition_values"),
             1 if want.num_files() else 0)]
        lanes = oracle.lane_aggregates(
            want.live_ids(), want.file_size, self.manifest.adds_per_commit)
        compared += [("aggregate_" + name, said[name], lanes[name])
                     for name in sorted(lanes) if name in said]
        return compared

    def check_file(self, path: str, version: int, paths) -> list:
        """`paths`: in the full check the digest of the replay's sorted
        live paths, else None."""
        want = self.replay
        counts = oracle.read_counts(path)
        hint = oracle.read_hint(self.log_dir)
        compared = [
            ("checkpoint_rows", counts["rows"], want.num_files() + 2),
            ("checkpoint_protocol_rows", counts["protocol"], 1),
            ("checkpoint_metadata_rows", counts["metaData"], 1),
            ("checkpoint_add_rows", counts["add"], want.num_files()),
            ("checkpoint_add_size", counts["add_size"],
             want.size_in_bytes()),
            # the source's stamps expired decades ago: no tombstone stays
            ("checkpoint_remove_rows", counts["remove"], 0),
            ("checkpoint_other_rows",
             counts["txn"] + counts["domainMetadata"], 0),
            ("hint_version", hint.get("version"), version),
            ("hint_size", hint.get("size"), counts["rows"]),
            ("hint_num_add_files", hint.get("numOfAddFiles"),
             counts["add"])]
        if paths is not None:
            ids = want.live_ids()
            adds = oracle.read_adds(path)
            compared += [
                ("checkpoint_paths_sha256",
                 oracle.sha256_lines(adds["path"]), paths),
                ("checkpoint_stats_sha256",
                 oracle.sha256_lines(adds["stats"]),
                 oracle.sha256_lines(deltalog_writer.stats_of(ids))),
                ("checkpoint_modification_times_sha256",
                 oracle.sha256_int64(adds["modificationTime"]),
                 oracle.sha256_int64(ids // self.manifest.adds_per_commit))]
        return compared

    def check_states(self, version: int, paths: str) -> list:
        """The writer's own state, and that of a process that has never
        seen the table, which has to start from the new checkpoint."""
        want = self.replay
        _, _, held = self.system.state(self.snapshot)
        self.system.drop_caches()
        _, cold = self.system.load(self.manifest.table_path)
        num_files, size, cold_paths = self.system.state(cold)
        return [
            ("live_paths_sha256",
             oracle.sha256_lines(held.combine_chunks().sort()), paths),
            ("cold_load_checkpoint_version",
             cold.log_segment.checkpoint_version, version),
            ("cold_load_version", cold.version, version),
            ("cold_load_num_files", num_files, want.num_files()),
            ("cold_load_size_in_bytes", size, want.size_in_bytes()),
            ("cold_load_paths_sha256",
             oracle.sha256_lines(cold_paths.combine_chunks().sort()), paths)]

    def report(self, path: str, version: int, full: bool) -> None:
        size = os.path.getsize(path) if os.path.exists(path) else 0
        where = ("in the warm-up" if self.warming else
                 f"{time.perf_counter() - self.window_t0:.3f} s into the "
                 "window")
        print(f"checkpoint at version {version}, checked {where}: "
              f"{size} bytes, the commit took {self.took:.3f} s: "
              f"{held_now()}", flush=True)
        if self.warming:
            return
        self.ops.append((self.took, size))
        if full:
            self.report_window()

    def report_window(self) -> None:
        """BASELINE.json's second metric, as the harness prints the
        first (`actions/s`) for the cold loads."""
        from delta_tpu import obs

        window_s = self.ended - self.window_t0
        written = sum(size for _, size in self.ops)
        in_commits = sum(took for took, _ in self.ops)
        print(f"commits/s: {self.interval * len(self.ops) / window_s:.3f} "
              f"({len(self.ops)} operations of {self.interval} commits in "
              f"{window_s:.3f} s)", flush=True)
        in_write = sum(s.duration_ns for s in obs.get_finished_spans()
                       if s.name == "checkpoint.write"
                       and s.start_unix_ns >= self.window_unix_ns) / 1e9
        if in_write:
            print(f"checkpoint MB/s: {written / 1e6 / in_write:.3f} "
                  f"({written} bytes of checkpoint files over {in_write:.3f} "
                  "s in the program's `checkpoint.write` spans)", flush=True)
        crossed, reloaded = (now - before for now, before in zip(
            crossings(), self.crossed_before))
        print(f"the writer's update() crossed a checkpoint {crossed} times "
              f"in the window and reloaded the table {reloaded} times",
              flush=True)
        print(f"checkpoint MB/s over the whole commit: "
              f"{written / 1e6 / in_commits:.3f} ({written} bytes over "
              f"{in_commits:.3f} s in the {len(self.ops)} timed commits)",
              flush=True)
