"""delta-bench-trend: noise-banded regression verdicts over BENCH_r*
artifacts, metric-direction heuristics, conditions backfill, and the
heterogeneous artifact formats (tail JSON lines vs metrics list)."""

import json

import pytest

from delta_tpu.obs import bench_trend
from delta_tpu.obs.device import CONDITIONS_UNKNOWN


def _write_runs(tmp_path, series, metric="load_actions_per_sec",
                conditions="cond-a"):
    """Write BENCH_r01..rNN artifacts in the modern (metrics-list)
    shape; `series` is [(value, conditions?)...] — a bare number uses
    the default conditions."""
    paths = []
    for i, point in enumerate(series, start=1):
        value, cond = point if isinstance(point, tuple) else (point,
                                                              conditions)
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps({
            "n": i,
            "conditions": cond,
            "metrics": [{"metric": metric, "value": value, "unit": "x"}],
        }, indent=1))
        paths.append(str(p))
    return paths


def _verdict(tmp_path, series, metric="load_actions_per_sec", **kw):
    runs = bench_trend.load_bench_runs(
        _write_runs(tmp_path, series, metric=metric))
    [v] = bench_trend.trend_verdicts(runs, **kw)
    return v


# ----------------------------------------------------- verdicts -------------

def test_synthetic_regression_is_flagged(tmp_path):
    # higher-is-better throughput drops 40% against a tight history
    v = _verdict(tmp_path, [100.0, 102.0, 98.0, 101.0, 60.0])
    assert v["verdict"] == "regressed"
    assert v["comparable_points"] == 4
    assert v["delta_pct"] < -30


def test_noise_within_band_is_stable(tmp_path):
    v = _verdict(tmp_path, [100.0, 102.0, 98.0, 101.0, 104.0])
    assert v["verdict"] == "stable"


def test_improvement_outside_band(tmp_path):
    v = _verdict(tmp_path, [100.0, 102.0, 98.0, 101.0, 150.0])
    assert v["verdict"] == "improved"


def test_band_widens_with_noisy_history(tmp_path):
    """A history that itself swings 30% must not flag a 20% move: the
    band is 2x the MAD, floored at min_band_pct — never tighter."""
    v = _verdict(tmp_path, [100.0, 140.0, 70.0, 125.0, 80.0])
    assert v["band_pct"] > 30
    assert v["verdict"] == "stable"


def test_lower_is_better_direction(tmp_path):
    up = _verdict(tmp_path, [10.0, 11.0, 9.0, 10.0, 20.0],
                  metric="trace_overhead_pct")
    assert up["verdict"] == "regressed"  # overhead going UP regresses
    down = _verdict(tmp_path, [10.0, 11.0, 9.0, 10.0, 5.0],
                    metric="trace_overhead_pct")
    assert down["verdict"] == "improved"


def test_insufficient_history(tmp_path):
    v = _verdict(tmp_path, [100.0, 101.0, 99.0])  # 2 comparable points
    assert v["verdict"] == "insufficient-history"
    assert "delta_pct" not in v


def test_unknown_direction_refuses_verdict(tmp_path):
    v = _verdict(tmp_path, [1.0, 1.0, 1.0, 9.0], metric="mystery_number")
    assert v["verdict"] == "unknown-direction"


def test_different_fingerprints_never_compare(tmp_path):
    """A TPU capture is not a baseline for a CPU capture: history
    points under other conditions drop out of the comparison."""
    series = [(100.0, "cpu"), (101.0, "cpu"), (99.0, "cpu"),
              (100.0, "cpu"), (500.0, "tpu")]
    v = _verdict(tmp_path, series)
    assert v["fingerprint"] == "tpu"
    assert v["comparable_points"] == 0
    assert v["verdict"] == "insufficient-history"


def test_zero_median_history(tmp_path):
    flat = _verdict(tmp_path, [0.0, 0.0, 0.0, 0.0],
                    metric="analyzer_findings_total")
    assert flat["verdict"] == "stable"
    spike = _verdict(tmp_path, [0.0, 0.0, 0.0, 3.0],
                     metric="analyzer_findings_total")
    assert spike["verdict"] == "regressed"


# ------------------------------------------------ direction rules -----------

@pytest.mark.parametrize("name,expected", [
    ("e2e_snapshot_load_actions_per_sec", +1),
    ("device_json_parse_gbps", +1),
    ("replay_kernel_speedup_large", +1),
    ("incremental_checkpoint_reuse_pct", +1),     # explicit: a hit rate
    ("trace_overhead_pct", -1),
    ("device_obs_overhead_pct", -1),
    ("cold_first_commit_seconds", -1),
    ("serve_p99_ms_chaos", -1),
    ("analyzer_findings_total", -1),
    ("mystery_number", 0),
])
def test_metric_direction(name, expected):
    assert bench_trend.metric_direction(name) == expected


# ------------------------------------------- artifact heterogeneity ---------

def test_extract_metrics_precedence_and_tail_lines(tmp_path):
    """Legacy artifacts embed metric JSON lines in the captured tail;
    the parsed record and the modern metrics list override them."""
    p = tmp_path / "BENCH_r03.json"
    p.write_text(json.dumps({
        "n": 3,
        "tail": 'noise line\n{"metric": "a_per_sec", "value": 1}\n'
                '{"metric": "b_per_sec", "value": 5}\nnot json {"metric"',
        "parsed": {"metric": "a_per_sec", "value": 2},
        "metrics": [{"metric": "a_per_sec", "value": 3}],
    }))
    [run] = bench_trend.load_bench_runs([str(p)])
    assert run["n"] == 3
    assert run["metrics"] == {"a_per_sec": 3.0, "b_per_sec": 5.0}
    # no conditions key -> the pre-schema sentinel group
    assert run["fingerprint"] == CONDITIONS_UNKNOWN


def test_load_skips_unreadable(tmp_path):
    good = _write_runs(tmp_path, [1.0])
    bad = tmp_path / "BENCH_r09.json"
    bad.write_text("{truncated")
    runs = bench_trend.load_bench_runs(good + [str(bad),
                                               str(tmp_path / "nope.json")])
    assert len(runs) == 1


# ------------------------------------------------------ backfill ------------

def test_backfill_stamps_and_is_idempotent(tmp_path):
    legacy = tmp_path / "BENCH_r01.json"
    legacy.write_text(json.dumps({"n": 1, "parsed": {"metric": "m_per_sec",
                                                     "value": 1}}, indent=2)
                      + "\n")
    modern = tmp_path / "BENCH_r02.json"
    modern.write_text(json.dumps({
        "n": 2, "conditions": {"schema": "v1"},
        "metrics": [{"metric": "m_per_sec", "value": 2}]}, indent=1))
    paths = [str(legacy), str(modern)]

    assert bench_trend.backfill_conditions(paths) == 1
    stamped = json.loads(legacy.read_text())
    assert stamped["conditions"] == CONDITIONS_UNKNOWN
    # detected indent preserved (artifact was written with indent=2)
    assert '\n  "n"' in legacy.read_text()
    # artifacts that already carry conditions are untouched
    assert json.loads(modern.read_text())["conditions"] == {"schema": "v1"}

    before = legacy.read_text()
    assert bench_trend.backfill_conditions(paths) == 0  # second run: no-op
    assert legacy.read_text() == before


# ----------------------------------------------------------- CLI ------------

def test_cli_text_json_and_fail_on_regress(tmp_path, capsys):
    _write_runs(tmp_path, [100.0, 101.0, 99.0, 100.0, 50.0])
    root = ["--root", str(tmp_path)]

    assert bench_trend.main(root) == 0
    out = capsys.readouterr().out
    assert "load_actions_per_sec" in out and "regressed" in out

    assert bench_trend.main(root + ["--fail-on-regress"]) == 1
    capsys.readouterr()

    assert bench_trend.main(root + ["--json"]) == 0
    [v] = json.loads(capsys.readouterr().out)
    assert v["verdict"] == "regressed" and v["latest_run"] == 5

    assert bench_trend.main(["--root", str(tmp_path / "empty")]) == 2


def test_cli_backfill_and_metric_filter(tmp_path, capsys):
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"n": 1, "metrics": [
        {"metric": "a_per_sec", "value": 1},
        {"metric": "b_per_sec", "value": 2}]}))
    assert bench_trend.main(["--root", str(tmp_path), "--backfill"]) == 0
    assert "backfilled 1 of 1" in capsys.readouterr().out
    assert json.loads(p.read_text())["conditions"] == CONDITIONS_UNKNOWN

    assert bench_trend.main(["--root", str(tmp_path),
                             "--metric", "a_per_sec"]) == 0
    out = capsys.readouterr().out
    assert "a_per_sec" in out and "b_per_sec" not in out
