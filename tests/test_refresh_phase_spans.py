"""The refresh seen from inside (PR 35): the phase spans under
`update.advance` and `stats.index_build`, and `index.pack_valid` before
`stats.index_upload`, on a streaming table at a test's size on the CPU
(built as `tests/chipbench/test_chipbench_stream.py` builds its
`traced_ops`): where each span sits, what it says, that a plan which
follows no landed commit opens none of them, that `index.compact_table`
(PR 36) opens under the first plan of a version that walks the fallback
ladder and under no refresh whose plans compile to the lanes, that
tracing changes
nothing of the state a refresh returns, that the harness's idle-gap
table names the child and not the parent, and that mode `on` records the
phases from `obs.PHASE_SPAN_ROWS` rows held and not below."""

import json
import os

import numpy as np
import pytest

from chipbench import trace_reduce
from chipbench.gen import deltastream
from chipbench.system import DeltaTpu

PARAMS = dict(commits=64, actions_per_commit=100, remove_fraction=0.2,
              checkpoint_interval=10, retained_commits=20, staged_commits=80)
W = deltastream.batch_width(80)
LO, HI = 11 * W, 14 * W
SLACK_NS = 100_000

ADVANCE = {         # span: the attributes it carries
    "advance.delta_keys": {"rows", "winners"},
    "advance.probe": {"rows", "touched", "candidates", "cleared"},
    "advance.masks": {"rows", "bytes"},
    "advance.table": {"chunks", "merged_rows"},
    "advance.carry": {"commit_infos"},
}
BUILD_APPEND = {
    "index.read_stats": {"rows", "bytes"},
    "index.parse": {"rows"},
    "index.compact_lanes": {"lanes", "rows", "dropped", "bytes"},
    "index.encode": {"rows", "lanes"},
}
BUILD_FULL = {name: BUILD_APPEND[name] for name in (
    "index.read_stats", "index.parse", "index.encode")}
PACK = {"index.pack_valid": {"lanes", "bytes"}}
# the parsed table, made when a plan first walks the ladder
LADDER = {"index.compact_table": {"rows", "columns", "bytes",
                                  "deferred_pieces"}}
NEW = {**ADVANCE, **BUILD_APPEND, **PACK, **LADDER,
       "advance.resident_append": set()}


class Traced:
    """The program's spans of one operation, as dicts."""

    def __init__(self, spans):
        self.spans = spans
        self.by_id = {s["span_id"]: s for s in spans}

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def one(self, name):
        [s] = self.named(name)
        return s

    def parent(self, span):
        return self.by_id[span["parent_id"]]["name"]

    def children(self, name):
        top = self.one(name)
        return sorted((s for s in self.spans
                       if s["parent_id"] == top["span_id"]),
                      key=lambda s: s["start_unix_ns"])


def traced(fn, mode="verbose"):
    """(`fn()`'s result, the spans it finished) with tracing in `mode`:
    `verbose`, because a table of a test's size is under
    `obs.PHASE_SPAN_ROWS`, where the phases are verbose detail."""
    from delta_tpu import obs

    obs.set_trace_mode(mode)
    obs.reset_trace_buffer()
    try:
        result = fn()
        return result, Traced([s.to_dict()
                               for s in obs.get_finished_spans()])
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()


def plan_by_ladder(snapshot):
    """`LO <= x < HI` with the upper bound as a float, which no int lane
    takes: that conjunct walks the Arrow ladder, and keeps the same
    files."""
    from delta_tpu.expressions import col, lit

    pred = (col("x") >= lit(LO)) & (col("x") < lit(HI - 0.5))
    return snapshot.scan(filter=pred).file_paths()


def land_empty_commit(manifest):
    """A commit that carries no file action: a writer's `txn` alone."""
    version = manifest.version + 1
    path = os.path.join(manifest.table_path, "_delta_log",
                        f"{version:020d}.json")
    with open(path, "w") as f:
        f.write(json.dumps({"txn": {"appId": "writer", "version": version,
                                    "lastUpdated": 0}}) + "\n")


@pytest.fixture(scope="module")
def ops(tmp_path_factory):
    """On the kernel's route: the index's first build on a state loaded
    in full, a plan that follows no landed commit, a refresh, the first
    plan of that version that walks the ladder and the one after it, a
    refresh over a commit with no file action, all traced; and beside
    them, on the same table made twice, the same refresh with tracing
    off."""
    from delta_tpu import obs

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DELTA_TPU_DEVICE_SKIP", "force")
        system = DeltaTpu()
        out = {}

        m = deltastream.generate(str(tmp_path_factory.mktemp("on")), PARAMS,
                                 seed=5)
        table, snapshot = system.load(m.table_path)
        want = len(m.scan_expected(LO, HI))
        paths, out["full"] = traced(lambda: system.plan(snapshot, LO, HI))
        assert len(paths) == want
        paths, out["plan"] = traced(lambda: system.plan(snapshot, LO, HI))
        assert len(paths) == want

        def refresh():
            snap = system.refresh(table)
            return snap, system.plan(snap, LO, HI)

        m.land(1)
        (on, paths), out["append"] = traced(refresh)
        assert len(paths) == len(m.scan_expected(LO, HI))
        out["on"] = on
        for kind in ("ladder", "ladder_again"):
            got, out[kind] = traced(lambda: plan_by_ladder(on))
            assert sorted(got) == sorted(paths)

        quiet = deltastream.generate(str(tmp_path_factory.mktemp("off")),
                                     PARAMS, seed=5)
        table_off, snapshot_off = system.load(quiet.table_path)
        system.plan(snapshot_off, LO, HI)
        quiet.land(1)
        assert not obs.trace_enabled()
        obs.reset_trace_buffer()
        out["off"] = system.refresh(table_off)
        system.plan(out["off"], LO, HI)
        out["off_spans"] = obs.get_finished_spans()

        land_empty_commit(m)
        (snap, paths), out["empty"] = traced(refresh)
        assert snap.version == on.version + 1
        assert len(paths) == len(m.scan_expected(LO, HI))
        yield out


CASES = ([("append", name, "update.advance") for name in ADVANCE]
         + [("append", name, "stats.index_build") for name in BUILD_APPEND]
         + [("full", name, "stats.index_build") for name in BUILD_FULL]
         + [("ladder", "index.compact_table", "plan.skip"),
            ("append", "index.pack_valid", "plan.skip"),
            ("full", "index.pack_valid", "plan.skip")])


@pytest.mark.parametrize("kind,name,parent", CASES)
def test_a_phase_appears_once_under_its_parent(ops, kind, name, parent):
    span = ops[kind].one(name)
    assert ops[kind].parent(span) == parent
    assert set(span["attrs"]) == NEW[name]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_plan_after_no_landed_commit_opens_no_phase(ops, name):
    assert ops["plan"].named("scan.plan") and not ops["plan"].named(name)


@pytest.mark.parametrize("kind,parent,names", [
    ("append", "update.advance", list(ADVANCE)),
    ("append", "stats.index_build", list(BUILD_APPEND)),
    ("full", "stats.index_build", list(BUILD_FULL)),
])
def test_the_phases_lie_inside_their_parent_in_order(ops, kind, parent,
                                                     names):
    top = ops[kind].one(parent)
    kids = ops[kind].children(parent)
    assert [s["name"] for s in kids] == names
    # a span's start is the wall clock's and its length the monotonic
    # clock's: the two may part by microseconds
    at = top["start_unix_ns"]
    for s in kids:
        assert s["start_unix_ns"] >= at - SLACK_NS  # none overlaps the last
        at = s["start_unix_ns"] + s["duration_ns"]
    assert at <= top["start_unix_ns"] + top["duration_ns"] + SLACK_NS
    assert ops[kind].one("stats.index_build")["attrs"]["mode"] == kind


def test_the_phases_say_what_happened(ops):
    def attrs(name, kind="append"):
        return ops[kind].one(name)["attrs"]

    advance = attrs("update.advance")
    held, landed = advance["prev_rows"], advance["delta_rows"]
    assert advance["route"] == "host" and landed == 100
    assert attrs("advance.delta_keys") == {"rows": 100, "winners": 100}
    # a commit's 20 removes name 20 rows held, which go
    assert attrs("advance.probe") == {"rows": held, "touched": 100,
                                      "candidates": 20, "cleared": 20}
    assert attrs("advance.masks") == {"rows": held + 100,
                                      "bytes": 2 * (held + 100)}
    assert attrs("advance.table")["merged_rows"] == 0
    assert attrs("advance.table")["chunks"] >= 2
    assert attrs("advance.carry")["commit_infos"] >= 0

    build = attrs("stats.index_build")
    assert (build["rows"], build["dropped"]) == (80, 20)
    read = attrs("index.read_stats")
    assert read["rows"] == 80 and read["bytes"] == build["bytes"] > 0
    assert attrs("index.parse") == {"rows": 80}
    compact = attrs("index.compact_lanes")
    assert (compact["lanes"], compact["dropped"]) == (4, 20)
    assert compact["rows"] == ops["full"].one("index.encode")["attrs"]["rows"]
    assert compact["bytes"] == 4 * 4096 * 9     # int64 lanes, bool plane
    assert attrs("index.encode") == {"rows": 80, "lanes": 4}
    # the full build's table and the refresh's tail, the 20 rows that
    # went still among them
    table = attrs("index.compact_table", "ladder")
    assert table["rows"] == compact["rows"] + 80 and table["columns"] >= 3
    assert table["bytes"] > 0 and table["deferred_pieces"] == 2
    assert attrs("index.pack_valid") == {"lanes": 4, "bytes": 4 * 4096}

    full = attrs("index.read_stats", "full")
    assert full["rows"] == attrs("index.parse", "full")["rows"] == compact["rows"]
    assert attrs("index.encode", "full")["lanes"] == 4
    assert not ops["full"].named("index.compact_lanes")
    assert not ops["full"].named("index.compact_table")


@pytest.mark.parametrize("kind", ["append", "full", "plan", "empty",
                                  "ladder_again"])
def test_the_table_is_made_for_the_first_ladder_plan_alone(ops, kind):
    """No refresh whose plans compile to the lanes, no first build (it
    has the table in hand), no later plan of a version makes the parsed
    table: the first one that walks the ladder did."""
    assert ops[kind].named("plan.skip")
    assert not ops[kind].named("index.compact_table")
    if kind == "ladder_again":
        skip = ops[kind].one("plan.skip")["attrs"]
        assert skip["skip_fallback_conjuncts"] == 1


@pytest.mark.parametrize("kind", ["append", "full"])
def test_the_plane_is_packed_before_the_upload_opens(ops, kind):
    pack = ops[kind].one("index.pack_valid")
    upload = ops[kind].one("stats.index_upload")
    assert ops[kind].parent(upload) == "plan.skip"
    assert pack["start_unix_ns"] + pack["duration_ns"] \
        <= upload["start_unix_ns"] + SLACK_NS
    assert pack["start_unix_ns"] < upload["start_unix_ns"]


def test_an_empty_advance_has_no_child(ops):
    advance = ops["empty"].one("update.advance")
    assert advance["attrs"]["route"] == "empty"
    assert advance["attrs"]["stats_index"] == "carried"
    assert ops["empty"].children("update.advance") == []
    assert not [s for s in ops["empty"].spans
                if s["name"].startswith(("advance.", "index."))]


def test_tracing_off_leaves_no_span_and_the_same_state(ops):
    from delta_tpu.stats.device_index import snapshot_stats_index

    assert ops["off_spans"] == []
    on, off = ops["on"].state, ops["off"].state
    assert ops["on"].version == ops["off"].version
    assert np.array_equal(on.live_mask, off.live_mask)
    assert np.array_equal(on.tombstone_mask, off.tombstone_mask)
    assert on.file_actions.equals(off.file_actions)
    lanes_on, lanes_off = (snapshot_stats_index(s) for s in (on, off))
    assert lanes_on.cols == lanes_off.cols and lanes_on.n == lanes_off.n
    assert np.array_equal(lanes_on.vals, lanes_off.vals)
    assert np.array_equal(lanes_on.valid, lanes_off.valid)


@pytest.mark.parametrize("kind,child,parent", [
    ("append", "advance.probe", "update.advance"),
    ("append", "index.compact_lanes", "stats.index_build"),
    ("append", "index.pack_valid", "plan.skip"),
    ("ladder", "index.compact_table", "plan.skip"),
])
def test_an_idle_moment_goes_to_the_child_not_the_parent(ops, kind, child,
                                                         parent):
    """The harness's own table (`trace_reduce.idle_by_host`) over the
    written-out operation, on a chip that ran nothing: the time a child
    is open is the child's, and what is left of its parent the
    parent's."""
    spans = ops[kind].spans
    host = [(s["name"], s["start_unix_ns"],
             s["start_unix_ns"] + s["duration_ns"]) for s in spans]
    top, kid = ops[kind].one(parent), ops[kind].one(child)
    window = (top["start_unix_ns"], top["start_unix_ns"] + top["duration_ns"])
    idle = dict(trace_reduce.Reduced(window, [[]]).idle_by_host(host))
    assert idle[child] == pytest.approx(kid["duration_ns"] / 1e9, rel=0.02)
    covered = sum(s["duration_ns"] for s in ops[kind].children(parent))
    assert idle.get(parent, 0) <= (top["duration_ns"] - covered) / 1e9 + 1e-4


def test_mode_on_records_the_phases_of_a_large_table_alone(tmp_path):
    """Under `on` (the benchmark's mode) a refresh of a table under
    `obs.PHASE_SPAN_ROWS` rows shows its parents and no phase; from that
    many rows held, every phase."""
    from delta_tpu import obs

    system = DeltaTpu()
    rows = {}
    for name, commits in (("small", 64), ("large", 1200)):
        m = deltastream.generate(str(tmp_path / name),
                                 {**PARAMS, "commits": commits}, seed=3)
        table, snapshot = system.load(m.table_path)
        system.plan(snapshot, LO, HI)
        m.land(1)

        def refresh():
            snap = system.refresh(table)
            return snap, system.plan(snap, LO, HI)

        (snap, _), got = traced(refresh, mode="on")
        rows[name] = got.one("update.advance")["attrs"]["prev_rows"]
        # `index.pack_valid` apart: it is there where the plan took the
        # kernel's route (`ops` forces it for as long as it lives)
        phases = {s["name"] for s in got.spans} & set(NEW) - set(PACK)
        assert got.one("stats.index_build")["attrs"]["mode"] == "append"
        assert phases == (set() if name == "small" else
                          set(ADVANCE) | set(BUILD_APPEND))
        _, got = traced(lambda: plan_by_ladder(snap), mode="on")
        assert len(got.named("index.compact_table")) == (name == "large")
    assert rows["small"] < obs.PHASE_SPAN_ROWS <= rows["large"]


def test_a_merge_of_the_tail_is_counted_once(tmp_path):
    """`advance.table` says how many rows the merge of the small chunks
    at the table's end copied: 0 at every advance but the one in
    `_MAX_SMALL_CHUNKS` + 1 that runs it."""
    from delta_tpu.replay import state as state_mod

    m = deltastream.generate(str(tmp_path), PARAMS, seed=7)
    system = DeltaTpu()
    table, snapshot = system.load(m.table_path)
    system.plan(snapshot, LO, HI)   # no merge while a stats decode pends
    chunks = snapshot.state.file_actions_raw.column("path").num_chunks

    def advances():
        for _ in range(state_mod._MAX_SMALL_CHUNKS + 2):
            m.land(1)
            system.refresh(table)

    _, got = traced(advances)
    merged = [s["attrs"]["merged_rows"] for s in got.named("advance.table")]
    assert len(merged) == state_mod._MAX_SMALL_CHUNKS + 2
    assert sorted(merged)[:-1] == [0] * (len(merged) - 1)
    assert merged[-1] == 0 and max(merged) % 100 == 0 and max(merged) >= 6400
    after = [s["attrs"]["chunks"] for s in got.named("advance.table")]
    assert min(after) >= chunks and after[merged.index(max(merged))] < max(after)


def test_the_resident_route_has_one_phase(tmp_path):
    """A state whose replay keys stayed on the device takes the masks
    from there: one span round `resident.append`, none of the host
    route's."""
    from delta_tpu import Table
    from delta_tpu.engine.tpu import TpuEngine

    m = deltastream.generate(str(tmp_path), PARAMS, seed=9)
    table = Table.for_path(m.table_path, TpuEngine(replay_shards=8))
    snapshot = table.latest_snapshot()
    if snapshot.state.resident is None:
        pytest.skip("this load kept no replay state on the device")
    m.land(1)
    snap, got = traced(table.update)
    assert snap.version == m.version
    advance = got.one("update.advance")
    assert advance["attrs"]["route"] == "resident"
    [kid] = got.children("update.advance")
    assert kid["name"] == "advance.resident_append"
    assert kid["attrs"] == {"rows": 100, "appended": True}
    assert len(snap.state.live_mask) == advance["attrs"]["prev_rows"] + 100
