"""End-to-end arithmetic and the span arithmetic of the layer readers."""

import statistics

import pytest

from chipbench import metrics, spans


@pytest.mark.parametrize("latencies,want", [
    ([30.0], 30.0), ([10.0, 30.0], 20.0), ([5.0, 1.0, 9.0], 5.0),
    ([20.0] * 7 + [5000.0], 20.0)])      # a slow refresh in eight
def test_op_p50_is_the_median_of_every_operation(latencies, want):
    found = metrics.end_to_end(latencies, len(latencies), 10.0, 3.0)
    assert found["op_p50_ms"] == (want, "ms")
    assert found["setup_s"] == (3.0, "s")
    assert set(found) == {"op_p50_ms", "ops_per_s", "setup_s"}


def test_ops_per_s_counts_correct_operations_over_the_whole_window():
    found = metrics.end_to_end([10.0] * 8, n_correct=6, window_s=4.0,
                               setup_s=1.0)
    assert found["ops_per_s"] == (1.5, "ops/s")


def span(name, sid, parent, start, dur):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_unix_ns": start, "duration_ns": dur}


SPANS = [span("log.columnarize", "a", None, 0, 100),
         span("pipeline.parse_window", "b", "a", 10, 30),
         span("native.scan", "c", "b", 15, 10),
         span("log.read", "d", "a", 30, 30),       # overlaps b by 10
         span("late", "e", "a", 90, 50),           # runs past its parent
         span("scan.plan", "f", None, 200, 40)]


@pytest.mark.parametrize("sid,want", [("a", 100 - (50 + 10)), ("b", 20),
                                      ("c", 10), ("f", 40)])
def test_self_time_is_duration_less_what_children_cover(sid, want):
    s = next(s for s in SPANS if s["span_id"] == sid)
    assert spans.self_time_ns(s, SPANS) == want


def test_span_helpers():
    assert spans.median_ms(spans.named(SPANS, "scan.plan")) == 40 / 1e6
    assert spans.median_ms([]) is None
    assert [s["span_id"] for s in spans.inside(SPANS, 10, 31)] == ["b", "c", "d"]
    assert spans.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert spans.merge([(30, 40), (0, 10), (10, 12)]) == [[0, 12], [30, 40]]
