"""The reduction from a profiler trace to busy time, the operations
that took most time and the idle time by what the host did: on planes
written by hand, where every number can be checked, and on a small
trace recorded on a TPU v5e (`recorded_tiny.xplane.pb`)."""

import json
import os

import pytest

from chipbench import roofline, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

# nanoseconds; the window is [1000, 2000)
PLANES = [
    ("/host:CPU", [("python3", [("chipbench.window", 1000, 1000),
                                ("chipbench.op", 1000, 400)])]),
    ("/device:TPU:0", [
        ("XLA Modules", [("jit_kernel(1)", 900, 500)]),
        ("XLA Ops", [("fusion.1", 900, 200),      # 100 before the window
                     ("fusion.1", 1200, 100),
                     ("sort.2", 1250, 150),       # overlaps fusion.1 by 50
                     ("copy.3", 1900, 300)]),     # 200 past the window
    ]),
    ("/device:TPU:1", [("XLA Ops", [("fusion.1", 1500, 100)])]),
    ("/device:TPU:0 SparseCore", [("XLA Ops", [("x", 1000, 1000)])]),
]


def test_busy_time_is_the_union_clipped_to_the_window():
    r = trace_reduce.reduce_planes(PLANES)
    assert r.window == (1000, 2000) and r.window_s == 1e-6
    # chip 0: [1000,1100) + [1200,1400) + [1900,2000) = 400; chip 1: 100
    assert [b.length for b in r.busy] == [400, 100]
    assert r.busy_s == pytest.approx(250e-9)


def test_device_ops_are_ranked_by_their_time_on_chip_0():
    r = trace_reduce.reduce_planes(PLANES)
    assert r.device_ops() == [["jit_kernel/fusion.1", 200e-9], ["jit_kernel/sort.2", 150e-9],
                              ["?/copy.3", 100e-9]]
    assert r.device_seconds([(1150, 1260)]) == pytest.approx(250e-9)


def test_idle_time_goes_to_the_innermost_host_span():
    r = trace_reduce.reduce_planes(PLANES)
    host = [("snapshot.load", 1000, 1500), ("log.columnarize", 1050, 1300),
            ("scan.plan", 1600, 1700)]
    idle = dict(r.idle_by_host(host))
    # idle on chip 0: [1100,1200) and [1400,1900)
    assert idle == {
        "log.columnarize": pytest.approx(100e-9),
        "snapshot.load": pytest.approx(100e-9),           # [1400,1500)
        "scan.plan": pytest.approx(100e-9),
        trace_reduce.BETWEEN: pytest.approx(300e-9)}
    assert sum(idle.values()) == pytest.approx(r.window_s - 400e-9)


def test_a_trace_without_the_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(PLANES[1:])


def test_a_window_with_no_device_plane_is_all_idle():
    r = trace_reduce.reduce_planes(PLANES[:1])
    assert r.busy_s == 0.0 and r.device_ops() == []
    assert dict(r.idle_by_host([])) == {
        trace_reduce.BETWEEN: pytest.approx(1e-6)}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "recorded_tiny.xplane.pb")
    with open(os.path.join(HERE, "recorded_tiny.json")) as f:
        return trace_reduce.read_xplane(path), json.load(f)


def test_the_recorded_trace_reduces_to_what_was_recorded(recorded):
    planes, notes = recorded
    r = trace_reduce.reduce_planes(planes)
    w0, w1 = notes["window_unix_ns"]
    assert r.window_s == pytest.approx((w1 - w0) / 1e9, rel=0.02)
    assert len(r.events) == 1 and r.events[0]
    assert 0 < r.busy_s < r.window_s
    # three operations, each a matrix product and a sort, 20 ms apart
    names = [n for n, _ in r.device_ops()]
    assert any("sort" in n for n in names)
    offset = r.window[0] - w0
    ops = [(a + offset, b + offset) for a, b in notes["ops_unix_ns"]]
    # the device's timeline leads the host's by 0.9 ms in this trace
    early = [(a - 2_000_000, b) for a, b in ops]
    assert r.device_seconds(early) == pytest.approx(r.busy_s, rel=0.05)
    idle = dict(r.idle_by_host([("op", a, b) for a, b in ops]))
    assert idle[trace_reduce.BETWEEN] >= 3 * 0.019
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s,
                                               rel=1e-6)


def test_peaks_are_known_for_the_v5e_and_for_nothing_else():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")


def test_parse_roofline_bytes_follow_the_kernels_shapes():
    # 52,428,800-byte lane (+32 of tail), 262,144 padded lines
    nbytes = roofline.parse_window_bytes(52_428_800, 262_144)
    assert nbytes == 52_428_832 + 262_144 * (24 + 24 + 14)
    assert roofline.least_seconds(nbytes, "TPU v5 lite") == nbytes / 819e9
