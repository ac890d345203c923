"""The four readers of the cold load's child spans, on a recorded span
list whose answers are computed by hand, and on a run of a program
that has no such span (the parent of the PR that added them)."""

import importlib.util
import os
import threading
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ME = threading.get_ident()
MS = 1_000_000


def reader(name):
    path = os.path.join(ROOT, "chipbench", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, sid, parent, start_ms, dur_ms, thread=ME):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_unix_ns": start_ms * MS, "duration_ns": dur_ms * MS,
            "thread_id": thread}


# two operations; the first replays on the chip, the second on the host
RECORDED = [
    span("snapshot.load", "l1", None, 0, 1000),
    span("log.read_checkpoint", "c1", "l1", 0, 500),
    span("checkpoint.read_part", "p1", "c1", 0, 300),
    span("checkpoint.read_part", "p2", "c1", 300, 100),
    span("checkpoint.canonicalize", "k1", "c1", 400, 100),
    span("parse.device_window", "w1", "l1", 500, 100),
    span("parse.wait", "w2", "w1", 520, 60),
    span("snapshot.replay", "r1", "l1", 600, 400),
    span("replay.keys", "r2", "r1", 600, 150),
    span("replay.launch", "r3", "r1", 750, 50),
    span("replay.wait", "r4", "r1", 800, 40),
    span("replay.wait", "r5", "r3", 760, 10),     # a grandchild counts
    span("replay.wait", "x1", None, 0, 999, thread=ME + 1),  # a worker's
    span("state.size_in_bytes", "s0", "l1", 990, 5),
    span("state.size_in_bytes", "s1", None, 1000, 20),
    span("state.add_files_table", "s2", None, 1020, 200),
    span("state.splice_stats", "s3", "s2", 1020, 150),
    span("snapshot.load", "l2", None, 2000, 700),
    span("snapshot.replay", "r6", "l2", 2400, 300),
    span("replay.host", "r7", "r6", 2450, 250),
    span("state.add_files_table", "s4", None, 2700, 75),
]
BARE = [span("snapshot.load", "l1", None, 0, 1000),
        span("log.read_checkpoint", "c1", "l1", 0, 500)]


def run(spans):
    return types.SimpleNamespace(spans=spans, ops=[{}, {}])


@pytest.mark.parametrize("name,want", [
    ("state_read_ms", (5 + 20 + 200 + 75) / 2),
    ("ckpt_decode_ms", (300 + 100) / 2),
    # 400 less the waits below it (40 + 10), and the host's 300 whole
    ("replay_host_ms", (400 - 50 + 300) / 2),
    # this thread's waits: parse 60, replay 40 + 10; not the worker's
    ("device_wait_ms", (60 + 40 + 10) / 2),
])
def test_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(run(RECORDED)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["state_read_ms", "ckpt_decode_ms",
                                  "replay_host_ms", "device_wait_ms"])
@pytest.mark.parametrize("spans", [BARE, []], ids=["parent", "empty"])
def test_reader_finds_nothing_without_its_spans(name, spans):
    assert reader(name)(run(spans)) is None


def test_overlapping_waits_are_not_subtracted_twice():
    spans = [span("snapshot.replay", "r1", None, 0, 100),
             span("replay.wait", "a", "r1", 10, 30),
             span("replay.wait", "b", "r1", 20, 30)]
    assert reader("replay_host_ms")(run(spans)) == (100 - 40) / 2


def test_a_wait_under_another_replay_is_left_alone():
    spans = [span("snapshot.replay", "r1", None, 0, 100),
             span("snapshot.replay", "r2", None, 200, 100),
             span("replay.wait", "a", "r2", 210, 60)]
    assert reader("replay_host_ms")(run(spans)) == (100 + 40) / 2
