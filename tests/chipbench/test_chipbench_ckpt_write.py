"""The deployment `deltalog-4m-writer-ckpt10` and its cell
`ckpt-write-under-ingest`, at a test's size on the CPU: a writer on
upstream's defaults whose every tenth commit writes the table's
checkpoint from its post-commit hook. The generator's pending commits
against the source's own next commits; the reference's replay and its
reader of a checkpoint, on a file the generator wrote and on files the
program wrote; whole runs, and runs in which the checkpoint is lost,
torn or wrong behind the program's back; the twelve readers on a
recorded run; and the cell's files by name."""

import importlib.util
import json
import os
import time
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from chipbench import control, harness
from chipbench.gen import deltalog, deltalog_writer
from chipbench.reference import ckpt_write_oracle as oracle
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "ckpt_write", "benchmark.json")
TINY_CELL = "tiny-write-under-ingest"
CELL = "ckpt-write-under-ingest"
CONFIG = "deltalog-4m-writer-ckpt10"
MIX = "commit-ckpt10"
OP = "commit+checkpoint"
PARAMS = dict(commits=70, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20)
SEED = 2**31 + 29
MS = 1_000_000
METRICS = {
    "ckpt_write_ms", "ckpt_write_snapshot_ms", "ckpt_write_assemble_ms",
    "ckpt_write_aggregate_ms", "ckpt_write_serialize_ms",
    "ckpt_write_upload_ms", "ckpt_write_commit_ms", "ckpt_write_mb_per_s",
    "ckpt_write_device_route_pct", "ckpt_write_h2d_mb_per_op",
    "ckpt_write_stats_roofline", "ckpt_write_idle_pct"}


def module(kind, name):
    path = os.path.join(ROOT, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"write_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DRIVER = module("drivers", "commit_and_checkpoint")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# ---- the generator: the source's next commits, held and not written ----

def test_the_pending_commits_are_the_sources_next_commits(tmp_path):
    """Ids, paths, stats strings and stamps of every pending commit are
    what `deltalog` itself writes for the versions after the table's,
    line for line, and what the driver hands the library says the same."""
    made = deltalog_writer.generate(
        str(tmp_path / "writer"), dict(PARAMS, pending_commits=25), SEED)
    source = deltalog.generate(
        str(tmp_path / "source"), dict(PARAMS, staged_commits=25), SEED)
    assert made.version == source.version == 69
    assert np.array_equal(made.alive, source.alive)
    assert [c.version for c in made.staged] == list(range(70, 95))
    assert made.checkpoint_interval == 10 and made.adds_per_commit == 80
    for pending in made.staged:
        with open(os.path.join(source.staged_dir,
                               deltalog.commit_name(pending.version))) as f:
            lines = f.read().splitlines()
        v = pending.version
        assert lines == (
            [deltalog.remove_line(int(r), v) for r in pending.removed]
            + [deltalog.add_line(a, v)
               for a in range(pending.add_lo, pending.add_hi)])
        adds, removes = DRIVER.file_actions(pending)
        assert ([{"remove": r.to_dict()} for r in removes]
                + [{"add": a.to_dict()} for a in adds]
                == [json.loads(line) for line in lines])
    # nothing is staged as a file, and nothing can be landed by rename
    assert not os.path.exists(made.staged_dir)
    with pytest.raises(RuntimeError, match="the writer's to make"):
        made.land(1)
    # the table itself is the source's, byte for byte
    for name in os.listdir(os.path.join(source.table_path, "_delta_log")):
        if name.endswith(".json") or name == "_last_checkpoint":
            with open(os.path.join(source.table_path, "_delta_log", name),
                      "rb") as a, open(os.path.join(
                          made.table_path, "_delta_log", name), "rb") as b:
                assert a.read() == b.read(), name


@pytest.mark.parametrize("ids", [[0], [7, 79, 80], [123456, 2399999],
                                 [3_999_999, 4_000_000, 9_999_999]])
def test_many_paths_and_stats_at_once_equal_the_sources_one_by_one(ids):
    ids = np.array(ids)
    assert deltalog_writer.paths_of(ids).to_pylist() == [
        deltalog.path_of(int(i)) for i in ids]
    assert deltalog_writer.stats_of(ids).to_pylist() == [
        deltalog.stats_of(int(i)) for i in ids]


# ---- the reference: the replay as ids ----

def test_the_replay_follows_the_sources_own_bookkeeping(tmp_path):
    source = deltalog.generate(
        str(tmp_path), dict(PARAMS, staged_commits=25), SEED)
    replay = oracle.Replay(source.version, source.alive, deltalog.FILE_SIZE)
    commits = list(source.staged)
    for commit in commits:
        replay.apply(commit)
        source.land(1)
        assert replay.version == source.version
        assert np.array_equal(replay.alive, source.alive)
        assert replay.num_files() == source.num_files()
        assert replay.size_in_bytes() == source.size_in_bytes()
    assert np.array_equal(replay.live_ids(), source.live_ids())


def commit_of(version, add_lo, add_hi, removed):
    return deltalog.StagedCommit(version, add_lo, add_hi,
                                 np.array(removed, np.int64))


@pytest.mark.parametrize("commit,why", [
    (commit_of(12, 8, 10, []), "after 10"),
    (commit_of(11, 8, 10, [5]), "not live"),
    (commit_of(11, 8, 10, [1, 1]), "not live"),
    (commit_of(11, 3, 5, []), "adds a live file"),
], ids=["a-version-skipped", "removes-a-dead-file", "removes-one-twice",
        "adds-a-live-file"])
def test_the_replay_refuses_traffic_that_is_no_table(commit, why):
    alive = np.zeros(16, bool)
    alive[[1, 2, 3]] = True
    replay = oracle.Replay(10, alive, 1)
    with pytest.raises(ValueError, match=why):
        replay.apply(commit)
    assert replay.version == 10 and replay.num_files() == 3


# ---- the reference: a checkpoint read without the program ----

def written_by(writer, tmp_path):
    """(manifest at the checkpoint's version, path of the checkpoint)."""
    if writer == "generator":       # its newest version is a multiple of 10
        made = deltalog.generate(str(tmp_path), dict(PARAMS, commits=71),
                                 SEED)
        version = 70
    else:                           # the program's, through `write_checkpoint`
        from delta_tpu import Table

        made = deltalog.generate(str(tmp_path), PARAMS, SEED)
        Table.for_path(made.table_path).checkpoint()
        version = 69
    assert made.version == version
    return made, os.path.join(made.table_path, "_delta_log",
                              f"{version:020d}.checkpoint.parquet")


@pytest.mark.parametrize("writer", ["generator", "program"])
def test_the_reader_takes_a_file_of_either_writer(writer, tmp_path):
    made, path = written_by(writer, tmp_path)
    n, ids = made.num_files(), made.live_ids()
    assert oracle.read_counts(path) == {
        "rows": n + 2, "protocol": 1, "metaData": 1, "add": n,
        "add_size": n * deltalog.FILE_SIZE, "remove": 0, "txn": 0,
        "domainMetadata": 0}
    adds = oracle.read_adds(path)
    assert adds["path"].to_pylist() == [deltalog.path_of(int(i))
                                        for i in ids]
    assert adds["stats"].to_pylist() == [deltalog.stats_of(int(i))
                                         for i in ids]
    assert adds["modificationTime"].to_pylist() == (ids // 80).tolist()
    assert set(adds["size"].to_pylist()) == {deltalog.FILE_SIZE}
    hint = oracle.read_hint(os.path.dirname(path))
    assert (hint["version"], hint["size"], hint["numOfAddFiles"]) == (
        made.version, n + 2, n)
    assert oracle.sha256_lines(adds["path"]) == made.digest()


def test_the_reader_counts_by_the_footer_or_by_the_column(tmp_path):
    """A file written without statistics gives the same counts: the
    leaf is read where the footer does not say how many are null."""
    made, path = written_by("program", tmp_path)
    bare = str(tmp_path / "bare.parquet")
    pq.write_table(pq.read_table(path), bare, write_statistics=False,
                   row_group_size=1000)
    assert pq.ParquetFile(bare).metadata.num_row_groups > 1
    assert oracle.read_counts(bare) == oracle.read_counts(path)


def test_the_reader_sees_a_tombstone_inside_retention(tmp_path):
    """The source's removes expired decades ago, so the cell's files
    hold none; one stamped now stays, and the reader counts it."""
    from delta_tpu import Table
    from delta_tpu.models.actions import RemoveFile

    made = deltalog.generate(str(tmp_path), PARAMS, SEED)
    table = Table.for_path(made.table_path)
    victim = int(made.live_ids()[0])
    txn = table.create_transaction_builder("WRITE").build()
    txn.remove_files([RemoveFile(path=deltalog.path_of(victim),
                                 deletionTimestamp=int(time.time() * 1000),
                                 dataChange=True)])
    assert txn.commit().version == 70      # the hook writes the checkpoint
    counts = oracle.read_counts(os.path.join(
        made.table_path, "_delta_log", f"{70:020d}.checkpoint.parquet"))
    assert counts["remove"] == 1 and counts["add"] == made.num_files() - 1
    assert counts["rows"] == made.num_files() + 2


# ---- whole runs ----

def run_tiny(seconds=0.25, trace=False, system=None, seed=SEED):
    return harness.run_cell(TINY_CELL, seed, seconds, trace,
                            time.perf_counter(), bench_path=TINY,
                            require_chip=False, system=system)


def compared_in(out, title):
    """name -> (compared, mismatches) of the harness's account."""
    found = {}
    for line in out.splitlines():
        if line.startswith(title + " ") and ": compared " in line:
            name, rest = line[len(title) + 1:].split(": compared ", 1)
            n, rest = rest.split(", mismatches ", 1)
            found[name] = (int(n), int(rest.split(" ", 1)[0]))
    return found


EVERY_OPERATION = {
    "commits_between", "commits_in_log", "version", "num_files",
    "size_in_bytes", "checkpoint_rows", "checkpoint_protocol_rows",
    "checkpoint_metadata_rows", "checkpoint_add_rows", "checkpoint_add_size",
    "checkpoint_remove_rows", "checkpoint_other_rows", "hint_version",
    "hint_size", "hint_num_add_files"}
IN_FULL = {
    "checkpoint_paths_sha256", "checkpoint_stats_sha256",
    "checkpoint_modification_times_sha256", "live_paths_sha256",
    "cold_load_checkpoint_version", "cold_load_version",
    "cold_load_num_files", "cold_load_size_in_bytes",
    "cold_load_paths_sha256"}


def test_a_run_commits_ten_times_an_operation_and_holds_every_guarantee(
        capsys):
    result = run_tiny()
    out = capsys.readouterr().out
    assert result["correct"] and result["failed"] == 0
    n = result["attempted"]
    assert n >= 1
    assert set(result["metrics"]) == {"op_p50_ms", "ops_per_s", "setup_s"}
    warm, window = compared_in(out, "warm-up"), compared_in(out, "window")
    # the warm-up is one whole operation, checked in full
    assert set(warm) == EVERY_OPERATION | IN_FULL
    assert all(row == (1, 0) for row in warm.values())
    # every operation of the window by the footer and the hint, its last
    # in full once the window has closed
    assert set(window) == EVERY_OPERATION | IN_FULL
    assert all(window[name] == (n, 0) for name in EVERY_OPERATION)
    assert all(window[name] == (1, 0) for name in IN_FULL)
    # ten commits an operation, one checkpoint each, the nine between
    # made by the same client (the fixture's first commit is the tenth)
    assert "warm-up commits_in_log: compared 1, mismatches 0 (limit 0); " \
           "last: got 1 want 1" in out
    assert "window commits_in_log: compared %d, mismatches 0 (limit 0); " \
           "last: got 10 want 10" % n in out
    checked = [line for line in out.splitlines()
               if line.startswith("checkpoint at version ")]
    assert [int(line.split()[3].rstrip(",")) for line in checked] == [
        140 + 10 * k for k in range(n + 1)]
    # BASELINE's second metric, as the cold loads print their first
    assert "commits/s: " in out and f"({n} operations of 10 commits" in out
    assert "checkpoint MB/s over the whole commit: " in out
    assert "process RSS " in out
    # its own checkpoint never lies past the version the writer holds
    assert ("update() crossed a checkpoint 0 times in the window and "
            "reloaded the table 0 times") in out


def test_the_driver_cleans_up_all_but_the_newest_two_checkpoints(tmp_path):
    made = deltalog_writer.generate(
        str(tmp_path), dict(PARAMS, pending_commits=60), SEED)
    driver = DRIVER.Driver(DeltaTpu(), made)
    log = os.path.join(made.table_path, "_delta_log")
    driver.table, driver.snapshot = driver.system.load(made.table_path)

    def checkpoints():
        return sorted(int(name[:20]) for name in os.listdir(log)
                      if name.endswith(".checkpoint.parquet"))

    for _ in range(4):
        prep = driver.prepare({})
        answer = driver.timed(prep)
        driver.warming = True       # no window to report on
        kind, compared = driver.check(prep, answer, False)
        assert kind == OP and all(got == want for _, got, want in compared)
    # at most three stand: the newest two of before, and this one
    assert checkpoints() == [80, 90, 100]
    driver.prepare({})
    assert checkpoints() == [90, 100]
    assert len(made.staged) == 60 - 1 - 3 * 10 - 10    # the tenth in hand


def test_the_driver_says_when_the_pending_commits_run_out(tmp_path):
    made = deltalog_writer.generate(
        str(tmp_path), dict(PARAMS, pending_commits=1), SEED)
    driver = DRIVER.Driver(DeltaTpu(), made)
    driver.table, driver.snapshot = driver.system.load(made.table_path)
    driver.warming = True
    prep = driver.prepare({})
    driver.check(prep, driver.timed(prep), False)
    with pytest.raises(RuntimeError, match="pending commits are used up"):
        driver.prepare({})


# ---- a checkpoint that is lost, torn or wrong behind the program's back ----

def rewritten(path, change):
    table = pq.read_table(path)
    os.remove(path)
    pq.write_table(change(table), path, compression="snappy")


def one_stats_string_changed(table):
    add = table.column("add").combine_chunks()
    stats = add.field("stats").to_pylist()
    at = next(i for i, s in enumerate(stats) if s is not None)
    stats[at] = stats[at].replace('"numRecords":1000', '"numRecords":1001')
    fields = [add.field(i) for i in range(add.type.num_fields)]
    fields[add.type.get_field_index("stats")] = pa.array(stats, pa.string())
    changed = pa.StructArray.from_arrays(
        fields, fields=list(add.type), mask=add.is_null())
    return table.set_column(table.schema.get_field_index("add"), "add",
                            changed)


def broken_writer(how):
    """`write_checkpoint` with something done to its file afterwards,
    as a fault behind the program's back would: the hook has returned,
    the commit is acknowledged, nothing was raised."""
    from delta_tpu.log import checkpointer

    real = checkpointer.write_checkpoint

    def write(engine, snapshot, **kwargs):
        log = snapshot._table.log_path
        path = os.path.join(log, f"{snapshot.version:020d}.checkpoint.parquet")
        hint = os.path.join(log, "_last_checkpoint")
        if how == "the-hook-fails":
            raise OSError("no space left on device")  # swallowed upstream
        with open(hint) as f:
            before = f.read()
        info = real(engine, snapshot, **kwargs)
        if how == "deleted":
            os.remove(path)
        elif how == "truncated":
            os.truncate(path, os.path.getsize(path) // 2)
        elif how == "the-hint-stays-behind":
            with open(hint, "w") as f:
                f.write(before)
        elif how == "one-add-short":
            rewritten(path, lambda t: t.slice(0, t.num_rows - 1))
        elif how == "a-stats-string-changed":
            rewritten(path, one_stats_string_changed)
        return info

    return write


class MiscountsBytes(DeltaTpu):
    def state(self, snapshot):
        n, size, paths = super().state(snapshot)
        return n, size - 1, paths


BROKEN = {
    # how: the comparisons that have to read a mismatch
    "the-hook-fails": {"checkpoint_file"},
    "deleted": {"checkpoint_file"},
    "truncated": {"checkpoint_file"},
    "the-hint-stays-behind": {"hint_version", "hint_size",
                              "hint_num_add_files"},
    "one-add-short": {"checkpoint_rows", "checkpoint_add_rows",
                      "checkpoint_add_size"},
    "a-stats-string-changed": {"checkpoint_stats_sha256"},
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_lost_torn_or_wrong_checkpoint_is_not_correct(how, monkeypatch,
                                                        capsys):
    from delta_tpu.log import checkpointer

    monkeypatch.setattr(checkpointer, "write_checkpoint", broken_writer(how))
    result = run_tiny()
    out = capsys.readouterr().out
    assert result["correct"] is False
    warm = compared_in(out, "warm-up")
    wrong = {name for name, (_, mismatches) in warm.items() if mismatches}
    assert BROKEN[how] <= wrong
    # the commit itself was acknowledged and is right: the check has to
    # look at the file to know
    assert not wrong & {"version", "num_files", "size_in_bytes",
                        "commits_in_log", "commits_between"}
    if BROKEN[how] == {"checkpoint_file"}:
        assert "unreadable: " in out and "want whole" in out


@pytest.mark.parametrize("system,wrong", [
    (control.StaleReader, {"cold_load_version", "cold_load_num_files"}),
    (MiscountsBytes, {"cold_load_size_in_bytes"}),
], ids=["a-cold-load-one-commit-short", "a-cold-load-that-miscounts"])
def test_a_cold_reader_that_disagrees_is_not_correct(system, wrong, capsys):
    result = run_tiny(system=system())
    warm = compared_in(capsys.readouterr().out, "warm-up")
    assert result["correct"] is False
    assert wrong <= {name for name, (_, bad) in warm.items() if bad}


# ---- the readers, on a recorded run ----

def reader(name):
    return module("layers", name).read


def span(name, start_ms, dur_ms, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "attrs": attrs}


def operation(start_ms, end_ms):
    return {"kind": OP, "start_unix_ns": start_ms * MS,
            "end_unix_ns": end_ms * MS}


def one_operation(t, write_ms, device=True, error=None):
    """The spans of one tenth commit that begins at `t` ms."""
    mode = {"stats_mode": "device" if device else "host"}
    if error:
        mode["device_error"] = error
    return [
        span("txn.commit", t, write_ms + 30),
        span("hook.snapshot", t + 10, 2, served="update"),
        span("checkpoint.write", t + 20, write_ms, route="classic", parts=1),
        span("checkpoint.assemble", t + 20, 300, adds=100, removes=0),
        span("checkpoint.aggregate", t + 320, 200, **mode),
        span("checkpoint.table", t + 520, 4, rows=102),
        span("checkpoint.serialize", t + 530, write_ms - 700, bytes=60 * 10**6),
        span("checkpoint.upload", t + write_ms - 160, 100, bytes=60 * 10**6),
        span("checkpoint.hint", t + write_ms - 50, 20),
    ]


def block_record(h2d, attrs=True):
    record = {"kernel": "stats.ckpt_block", "h2d_bytes": h2d}
    if attrs:
        record["attrs"] = {"lanes": 4, "n_pad": 2_621_440, "p_pad": 8}
    return record


def recorded(with_new_spans=True, with_attrs=True):
    """Two operations (4,000 and 5,000 ms in `checkpoint.write`) and,
    between them, three plain commits of 100, 120 and 500 ms."""
    spans = (one_operation(0, 4000) + one_operation(10_000, 5000, device=False,
                                                    error="XlaRuntimeError")
             + [span("txn.commit", 6000, 100), span("txn.commit", 6200, 120),
                span("txn.commit", 6400, 500)])
    if not with_new_spans:
        new = {"hook.snapshot", "checkpoint.assemble", "checkpoint.table",
               "checkpoint.hint"}
        spans = [s for s in spans if s["name"] not in new]
    # the block's operations on the chip: 30 ms a launch, two of them
    events = [[("jit_stats_ckpt_block/sort.1", 0, 20 * MS),
               ("jit_stats_ckpt_block/scatter.2", 15 * MS, 30 * MS),
               ("jit_replay_single/x", 40 * MS, 50 * MS),
               ("jit_stats_ckpt_block/sort.1", 100 * MS, 130 * MS)]]
    return types.SimpleNamespace(
        ops=[operation(0, 4030), operation(10_000, 15_030)], spans=spans,
        dispatches=[block_record(95_682_560, with_attrs),
                    block_record(95_682_560, with_attrs),
                    {"kernel": "replay.single", "h2d_bytes": 5}],
        trace=types.SimpleNamespace(events=events, busy_s=0.07,
                                    window_s=16.0),
        device_kind="TPU v5 lite")


LEAST_BYTES = (4 * 2_621_440 * 8 + 4 * 2_621_440 // 8 + 2_621_440 * 4
               + 17 * 8 * 8)
BY_HAND = {
    "ckpt_write_ms": 4500.0,
    "ckpt_write_snapshot_ms": 2.0,
    "ckpt_write_assemble_ms": 304.0,
    "ckpt_write_aggregate_ms": 200.0,
    "ckpt_write_serialize_ms": (3300 + 4300) / 2,
    "ckpt_write_upload_ms": 120.0,
    "ckpt_write_commit_ms": 120.0,          # of 100, 120, 500
    "ckpt_write_mb_per_s": 120.0 / 9.0,     # 120 MB in 9 s
    "ckpt_write_device_route_pct": 50.0,
    "ckpt_write_h2d_mb_per_op": 95.68256,
    # two launches' least time over the 60 ms their operations cover
    "ckpt_write_stats_roofline": 100 * (2 * LEAST_BYTES / 819e9) / 0.060,
    "ckpt_write_idle_pct": 100 * (1 - 0.07 / 16.0),
}


def test_the_twelve_readers_are_the_cells_twelve_metrics():
    assert set(BY_HAND) == METRICS
    assert module("layers", "ckpt_stats_block_bytes").ckpt_stats_block_bytes(
        4, 2_621_440, 8) == LEAST_BYTES == 95_683_648


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_reader_gives_the_hand_computed_value(name):
    assert reader(name)(recorded()) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name,want", [
    # a program without this PR's spans and attributes, under its readers
    ("ckpt_write_snapshot_ms", None),
    ("ckpt_write_assemble_ms", None),
    ("ckpt_write_stats_roofline", None),
    ("ckpt_write_upload_ms", 100.0),        # `checkpoint.upload` alone
    ("ckpt_write_ms", 4500.0),
    ("ckpt_write_aggregate_ms", 200.0),
    ("ckpt_write_h2d_mb_per_op", 95.68256),
])
def test_a_reader_on_the_parents_spans_reads_what_is_there(name, want):
    got = reader(name)(recorded(with_new_spans=False, with_attrs=False))
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("name", sorted(METRICS - {"ckpt_write_idle_pct"}))
def test_a_reader_finds_nothing_in_a_run_without_checkpoints(name):
    empty = types.SimpleNamespace(
        ops=[operation(0, 10)], spans=[], dispatches=[],
        trace=types.SimpleNamespace(events=[[]], busy_s=0.0, window_s=1.0),
        device_kind="TPU v5 lite")
    assert reader(name)(empty) is None


def test_a_traced_run_reads_the_host_side_metrics(capsys):
    """On the CPU the stage stays on the host and nothing is dispatched:
    ten of the twelve read, and the span says where the block ran."""
    result = run_tiny_traced()
    out = capsys.readouterr().out
    assert result["correct"]
    silent = {"ckpt_write_h2d_mb_per_op", "ckpt_write_stats_roofline"}
    assert set(result["metrics"]) == METRICS - silent
    for name in silent:
        assert f"metric {name}: nothing to read in this run" in out
    assert result["metrics"]["ckpt_write_device_route_pct"]["value"] == 0.0
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert values["ckpt_write_ms"] >= (
        values["ckpt_write_assemble_ms"] + values["ckpt_write_serialize_ms"])
    assert 0 < values["ckpt_write_commit_ms"] < values["ckpt_write_ms"]
    assert "checkpoint MB/s: " in out and "`checkpoint.write` spans" in out


# ---- the stats block, which no byte of the file holds ----

AGGREGATE = {
    "aggregate_stats_mode", "aggregate_logical_bytes",
    "aggregate_dv_cardinality", "aggregate_distinct_partition_values",
    "aggregate_lane_min", "aggregate_lane_max", "aggregate_lane_sum",
    "aggregate_lane_nulls"}


def run_tiny_traced():
    from delta_tpu import obs

    try:
        return run_tiny(trace=True)
    finally:
        obs.set_trace_mode(None)
        obs.set_device_obs_mode(None)
        obs.reset_trace_buffer()
        obs.reset_device_obs()


def test_a_traced_run_holds_the_stats_block_to_the_references_table(capsys):
    result = run_tiny_traced()
    out = capsys.readouterr().out
    assert result["correct"]
    n = result["attempted"]
    window = compared_in(out, "window")
    assert AGGREGATE <= set(window)
    assert all(window[name] == (n, 0) for name in AGGREGATE)
    # the warm-up runs before the spans are recorded, a plain run
    # records none: `test_a_run_commits_ten_times...` names all it compares
    assert not AGGREGATE & set(compared_in(out, "warm-up"))
    assert "window aggregate_lane_nulls: compared %d, mismatches 0" % n in out


def test_the_references_lanes_are_the_host_twins_block_of_the_table():
    """The reference's own arithmetic against the program's host twin
    over the lanes `_checkpoint_aggregates` would build of that table."""
    from delta_tpu.ops import stats as ckstats

    ids = np.array([3, 80, 81, 400, 4001])
    want = oracle.lane_aggregates(ids, deltalog.FILE_SIZE, 80)
    n = len(ids)
    lanes = [np.full(n, deltalog.FILE_SIZE), ids // 80, np.zeros(n, int),
             np.zeros(n, int)]
    valids = [np.ones(n, bool), np.ones(n, bool), np.zeros(n, bool),
              np.ones(n, bool)]
    block = ckstats.host_stats_block(lanes, valids, np.zeros(n, np.int32),
                                     1, 1)
    assert want == {"lane_min": block[0:4, 0].tolist(),
                    "lane_max": block[4:8, 0].tolist(),
                    "lane_sum": block[8:12, 0].tolist(),
                    "lane_nulls": block[12:16, 0].tolist()}
    none = oracle.lane_aggregates(ids[:0], deltalog.FILE_SIZE, 80)
    assert none["lane_min"] == [ckstats.IDENT_MIN] * 4
    assert none["lane_max"] == [ckstats.IDENT_MAX] * 4


def changed(row, by):
    def change(block):
        block = block.copy()
        block[row, 0] += by
        return block
    return change


WRONG_BLOCKS = {
    # how: (what is done to the block, the comparisons that must read it)
    "a-kernel-that-returns-zeros": (
        np.zeros_like, AGGREGATE - {"aggregate_stats_mode",
                                    "aggregate_dv_cardinality"}),
    "the-least-stamp-one-off": (changed(1, 1), {"aggregate_lane_min"}),
    "the-greatest-size-one-off": (changed(4, -1), {"aggregate_lane_max"}),
    "a-sum-of-stamps-one-short": (changed(9, -1), {"aggregate_lane_sum"}),
    "the-sizes-sum-one-over": (
        changed(8, 1), {"aggregate_lane_sum", "aggregate_logical_bytes"}),
    "a-null-miscounted": (changed(14, 1), {"aggregate_lane_nulls"}),
    "a-cardinality-where-there-is-none": (
        changed(10, 5), {"aggregate_lane_sum", "aggregate_dv_cardinality"}),
    "two-partition-values": (
        changed(16, 1), {"aggregate_distinct_partition_values"}),
}


@pytest.mark.parametrize("how", sorted(WRONG_BLOCKS))
def test_a_wrong_stats_block_is_not_correct(how, monkeypatch, capsys):
    """The block reaches no byte of the file, so every comparison of
    the file, the hint and the state holds; the span's alone do not."""
    from delta_tpu.ops import stats as ckstats

    change, must = WRONG_BLOCKS[how]
    real = ckstats.host_stats_block
    monkeypatch.setattr(ckstats, "host_stats_block",
                        lambda *a: change(real(*a)))
    result = run_tiny_traced()
    window = compared_in(capsys.readouterr().out, "window")
    assert result["correct"] is False and result["failed"] == result[
        "attempted"]
    assert {name for name, (_, bad) in window.items() if bad} == must


def test_a_block_served_by_the_host_after_a_failed_dispatch_is_not_correct(
        monkeypatch, capsys):
    """The fallback's block is right, and the chip's kernel unseen."""
    from delta_tpu.ops import stats as ckstats

    def fails(*a, **k):
        raise RuntimeError("the device is gone")

    monkeypatch.setenv("DELTA_TPU_DEVICE_CKPT_STATS", "1")
    monkeypatch.setattr(ckstats, "checkpoint_stats_block", fails)
    result = run_tiny_traced()
    window = compared_in(capsys.readouterr().out, "window")
    assert result["correct"] is False
    assert {name for name, (_, bad) in window.items() if bad} == {
        "aggregate_stats_mode"}


# ---- the cell's files ----

def test_the_cells_files_resolve_by_name():
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell.config["name"] == CONFIG and cell.entry["traffic"] == MIX
    assert cell.entry["chips"] == 1
    assert cell.mix["driver"] == "commit_and_checkpoint"
    assert cell.config["generator"]["kind"] == "deltalog_writer"
    assert cell.module("gen", "deltalog_writer").generate
    assert cell.module("drivers", cell.mix["driver"]).Driver
    # a lower bound, and no place in the file: a later PR may add to the
    # cell's metrics, and to the file before or behind its entries
    mine = {m["name"] for m in cell.metrics_of("per_layer")}
    assert METRICS <= mine
    for name in METRICS:
        assert cell.module("layers", name).read
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= {
        m["name"] for m in cell.metrics_of("end_to_end")}
    bench = load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == ["commits"] == list(cell.config["reduced"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if CELL not in m.get("workloads", [])}
    for name in METRICS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["layer"] in layers    # a layer PERF.md has
        assert by_name[name]["moves"] == (
            "ops_per_s" if name in ("ckpt_write_commit_ms",
                                    "ckpt_write_idle_pct") else "op_p50_ms")
    assert by_name["ckpt_write_stats_roofline"]["source"] == "device_trace"
    assert by_name["ckpt_write_h2d_mb_per_op"]["source"] == "program_counter"
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_configuration_is_its_siblings_table_in_its_writers_hands():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        text = f.read()
    config = json.loads(text)
    assert "DELTA_TPU_" not in text and len(config["source"]) <= 200
    sibling = load_json("chipbench", "configs", "deltalog-4m-ckpt10.json")
    assert config["generator"] == dict(
        sibling["generator"], kind="deltalog_writer", pending_commits=1000)
    assert config["environment"] == sibling["environment"]
    assert len(config["guarantees"]) == 3
    for key in ("writer", "checkpoint_format", "tombstones", "checksums",
                "log_cleanup", "bucket", "route", "storage", "allocator"):
        assert config["assumed"][key]
    # what `assumed.bucket` says: the window's rows stay in one bucket
    from delta_tpu.ops.replay import pad_bucket

    live = 2_400_020
    assert pad_bucket(live) == pad_bucket(live + 60 * 1000) == 2_621_440
    assert (2_621_440 - live) // 600 == 369
    mix = load_json("chipbench", "mixes", MIX + ".json")
    assert mix["driver"] == "commit_and_checkpoint" and "draws" not in mix


def test_the_tests_own_cell_is_the_cell_at_a_smaller_table():
    tiny, bench = load_json("tests", "chipbench", "ckpt_write",
                            "benchmark.json"), load_json("BENCHMARK.json")
    [cell] = tiny["workloads"]
    assert cell["traffic"] == MIX and cell["name"] == TINY_CELL
    assert {m["name"] for m in tiny["per_layer"]} == METRICS
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in tiny["per_layer"]:
        assert dict(m, workloads=[CELL]) == by_name[m["name"]]
    config = load_json("tests", "chipbench", "ckpt_write", "configs",
                       "tiny-writer-ckpt10.json")
    real = load_json("chipbench", "configs", CONFIG + ".json")
    assert dict(config["generator"], commits=0, retained_commits=0,
                pending_commits=0) == dict(
        real["generator"], commits=0, retained_commits=0, pending_commits=0)
