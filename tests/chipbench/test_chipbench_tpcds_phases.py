"""The seven readers PR 51 put over the phase spans of a `tpcds-q-mix`
pass (`scan.read`, `scan.read_run`, `scan.assemble`, `sql.frame`,
`join.nulls`, `join.encode`, `join.expand`, `join.gather`): each on
built spans against a value worked out by hand, `None` on a program
without its span, and their entries in `BENCHMARK.json`."""

import importlib.util
import json
import os
import types

import pytest

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "tpcds-q-mix"
PHASE_METRICS = ["sql_scan_read_ms", "sql_scan_frame_ms",
                 "sql_scan_files_per_op", "sql_scan_task_cpu_pct",
                 "sql_join_encode_ms", "sql_join_expand_ms",
                 "sql_join_gather_ms"]
UNITS = {"sql_scan_files_per_op": ("files/op", "lower"),
         "sql_scan_task_cpu_pct": ("%", "higher")}
MS = 1_000_000
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def reader(name):
    path = os.path.join(ROOT, "chipbench", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("phase_layer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, start_ms, ms, **attrs):
    return {"name": name, "span_id": f"{name}@{start_ms}", "parent_id": None,
            "start_unix_ns": start_ms * MS, "duration_ns": ms * MS,
            "thread_id": 1, "attrs": attrs}


# two passes, [0, 1000) and [1000, 2200) ms, and a warm-up query before
# them whose spans no pass holds
OPS = [{"kind": "pass", "start_unix_ns": 0, "end_unix_ns": 1000 * MS},
       {"kind": "warm-up", "start_unix_ns": -900 * MS, "end_unix_ns": 0},
       {"kind": "pass", "start_unix_ns": 1000 * MS,
        "end_unix_ns": 2200 * MS}]
RECORDED = [
    span("scan.read", -500, 77, files=1824),
    span("scan.read_run", -480, 50, cpu_ms=50.0, files=35),
    span("scan.assemble", -400, 33), span("sql.frame", -300, 44),
    span("join.nulls", -200, 5), span("join.encode", -190, 6),
    span("join.expand", -150, 7), span("join.gather", -100, 8),
    # the first pass: a dealt read of two tasks and an inline one
    span("scan.read", 0, 200, files=1824, tasks=2, inline=False),
    span("scan.read_run", 5, 100, cpu_ms=20.0, files=912),
    span("scan.read_run", 5, 300, cpu_ms=180.0, files=912),
    span("scan.assemble", 200, 30), span("sql.frame", 230, 70),
    span("scan.read", 300, 10, files=1, tasks=0, inline=True),
    span("scan.assemble", 310, 1), span("sql.frame", 311, 2),
    span("join.nulls", 400, 20), span("join.encode", 420, 30),
    span("sql.wait", 450, 100), span("join.expand", 550, 40),
    span("join.gather", 590, 110),
    # the second: one read of fewer files, two joins
    span("scan.read", 1000, 150, files=60, tasks=2, inline=False),
    span("scan.read_run", 1005, 100, cpu_ms=100.0, files=60),
    span("scan.assemble", 1150, 20), span("sql.frame", 1170, 50),
    span("join.nulls", 1300, 10), span("join.encode", 1310, 20),
    span("join.expand", 1400, 25), span("join.gather", 1430, 90),
    span("join.nulls", 1600, 4), span("join.encode", 1610, 6),
    span("join.expand", 1700, 15), span("join.gather", 1720, 30),
]


def recorded(spans=RECORDED):
    return types.SimpleNamespace(ops=OPS, spans=list(spans))


def without(*names):
    return [s for s in RECORDED if s["name"] not in names]


@pytest.mark.parametrize("name, want", [
    # a pass's sum, then the median of the two passes
    ("sql_scan_read_ms", ((200 + 10) + 150) / 2),
    ("sql_scan_frame_ms", ((30 + 70 + 1 + 2) + (20 + 50)) / 2),
    ("sql_scan_files_per_op", ((1824 + 1) + 60) / 2),
    # over the window's tasks, CPU over duration: the long task weighs
    # three times the short one, the warm-up's as any other of the window
    ("sql_scan_task_cpu_pct",
     100 * (50 + 20 + 180 + 100) / (50 + 100 + 300 + 100)),
    ("sql_join_encode_ms", ((20 + 30) + (10 + 20 + 4 + 6)) / 2),
    ("sql_join_expand_ms", (40 + (25 + 15)) / 2),
    ("sql_join_gather_ms", (110 + (90 + 30)) / 2),
])
def test_a_phase_reader_gives_the_hand_computed_value(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


def test_the_task_share_weighs_a_task_by_how_long_it_took():
    tasks = [span("scan.read_run", 10, 100, cpu_ms=10.0),
             span("scan.read_run", 10, 900, cpu_ms=900.0)]
    share = reader("sql_scan_task_cpu_pct")(recorded(tasks))
    assert share == pytest.approx(91.0)      # not the mean of 10 and 100
    assert 0 < share <= 100


@pytest.mark.parametrize("name, spans", [
    # the parent: `scan.read` and its `files`, and no span under it
    ("sql_scan_frame_ms", without("scan.assemble", "sql.frame")),
    ("sql_scan_task_cpu_pct", without("scan.read_run")),
    ("sql_join_encode_ms", without("join.nulls", "join.encode")),
    ("sql_join_expand_ms", without("join.expand")),
    ("sql_join_gather_ms", without("join.gather")),
    # a program before PR 50: no `scan.read` either
    ("sql_scan_read_ms", without("scan.read")),
    ("sql_scan_files_per_op", without("scan.read")),
    ("sql_scan_files_per_op", [span("scan.read", 10, 5)]),   # no `files`
    ("sql_scan_task_cpu_pct", [span("scan.read_run", 10, 5, files=3)]),
    # the spans there, and every one outside the passes
    ("sql_scan_read_ms", RECORDED[:8]),
    ("sql_join_gather_ms", RECORDED[:8]),
] + [(name, []) for name in PHASE_METRICS])
def test_a_phase_reader_finds_nothing_on_a_program_without_its_spans(
        name, spans):
    assert reader(name)(recorded(spans)) is None


def test_on_the_parent_the_two_readers_of_scan_read_still_read():
    parent = recorded(without("scan.read_run", "scan.assemble", "sql.frame",
                              "join.nulls", "join.encode", "join.expand",
                              "join.gather"))
    found = {name: reader(name)(parent) for name in PHASE_METRICS}
    assert found.pop("sql_scan_read_ms") == pytest.approx(180)
    assert found.pop("sql_scan_files_per_op") == pytest.approx(942.5)
    assert set(found.values()) == {None}


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_a_phase_entry_resolves_for_the_cell_and_for_no_other(name):
    cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    mine = {m["name"]: m for m in cell.metrics_of("per_layer")}
    unit, better = UNITS.get(name, ("ms", "lower"))
    assert mine[name] == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span", "layer": "host pipeline",
        "moves": "op_p50_ms", "workloads": [CELL]}
    assert cell.module("layers", name).read
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            theirs = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"),
                                  other["name"]).metrics_of("per_layer")
            assert name not in {m["name"] for m in theirs}


def test_the_phase_entries_stand_after_every_entry_the_file_had():
    """After them, not last: a later PR appends its own behind these."""
    names = [m["name"] for m in BENCH["per_layer"]]
    had = names.index("sql_idle_pct")       # the last entry of PR 50's file
    assert had == 65 and len(names) == len(set(names))
    assert [n for n in names[had + 1:] if n in PHASE_METRICS] == PHASE_METRICS
    assert not set(names[:had + 1]) & set(PHASE_METRICS)
