"""Runs of whole cells through the harness, on the CPU at a test's
size: everything but the look for a chip. Two cells of
`tests/chipbench/extra/` (a reader that plans scans while a writer's
commits land) exist only as files there: a driver, two mixes and three
layer metrics that `chipbench/` does not have. So these runs also show
that a later cell is new files and one entry, with no file edited."""

import json
import os
import time

import pytest

from chipbench import control, harness
from chipbench.system import DeltaTpu

HERE = os.path.dirname(os.path.abspath(__file__))
EXTRA = os.path.join(HERE, "extra", "benchmark.json")
with open(EXTRA) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


def run(workload, trace=False, system=None, seed=2**31 + 17, seconds=0.3):
    return harness.run_cell(workload, seed, seconds, trace,
                            time.perf_counter(), bench_path=EXTRA,
                            require_chip=False, system=system)


@pytest.mark.parametrize("workload", CELLS)
def test_a_run_reports_its_end_to_end_metrics(workload, capsys):
    result = run(workload)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert {"op_p50_ms", "ops_per_s", "setup_s"} <= set(result["metrics"])
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    out = capsys.readouterr().out
    assert "mismatches 0 (limit 0)" in out and "fixture:" in out


EXPECTED_LAYERS = {
    "tiny-json-cold-load": {"snapshot_load_ms", "host_parse_ms"},
    "tiny-ckpt-cold-load": {"snapshot_load_ms", "host_parse_ms"},
    "tiny-ckpt-query-under-ingest": {"scan_plan_ms", "refresh_ms"},
    "tiny-ckpt-wide-queries": {"scan_plan_ms", "refresh_ms",
                               "planned_files_per_op"},
}


@pytest.mark.parametrize("workload", CELLS)
def test_a_traced_run_reports_its_per_layer_metrics(workload):
    result = run(workload, trace=True)
    assert result["correct"]
    always = {"device_route_pct", "h2d_mb_per_op", "device_idle_pct"}
    assert set(result["metrics"]) == always | EXPECTED_LAYERS[workload]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    gaps = dict(result["breakdown"]["idle_gaps"])
    assert len(gaps) <= 10 and gaps
    # on the CPU there is no device plane: all of the window is idle,
    # and the program's spans account for it
    assert sum(gaps.values()) == pytest.approx(
        result["device"]["window_s"], rel=0.05)
    assert any(not name.startswith(("chipbench", "(")) for name in gaps)


class LossySkipping(DeltaTpu):
    """Skipping that compares the file's maximum with `>` where the
    range needs `>=`: a file that ends where the range begins is lost."""

    def plan(self, snapshot, lo, hi):
        from delta_tpu.expressions import col, lit

        pred = (col("x") > lit(lo)) & (col("x") < lit(hi))
        return snapshot.scan(filter=pred).file_paths()


class DropsAPath(DeltaTpu):
    def plan(self, snapshot, lo, hi):
        return super().plan(snapshot, lo, hi)[1:]


class RenamesAPath(DeltaTpu):
    def state(self, snapshot):
        n, size, paths = super().state(snapshot)
        first = paths.to_pylist()
        first[0] = "part-9999999999.parquet"
        import pyarrow as pa

        return n, size, pa.chunked_array([first])


class MiscountsBytes(DeltaTpu):
    def state(self, snapshot):
        n, size, paths = super().state(snapshot)
        return n, size - 1, paths


class NeverRefreshes(DeltaTpu):
    def refresh(self, table):
        return table._cached_snapshot


BROKEN = [
    ("tiny-json-cold-load", control.StaleReader),
    ("tiny-ckpt-cold-load", control.StaleReader),
    ("tiny-ckpt-query-under-ingest", control.StaleReader),
    ("tiny-ckpt-query-under-ingest", LossySkipping),
    ("tiny-ckpt-query-under-ingest", DropsAPath),
    ("tiny-ckpt-query-under-ingest", NeverRefreshes),
    ("tiny-json-cold-load", RenamesAPath),
    ("tiny-ckpt-cold-load", MiscountsBytes),
]


@pytest.mark.parametrize("workload,system", BROKEN,
                         ids=[f"{w}-{s.__name__}" for w, s in BROKEN])
def test_a_broken_guarantee_or_an_altered_answer_is_not_correct(
        workload, system, capsys):
    result = run(workload, system=system())
    assert result["correct"] is False
    assert "first mismatch: got" in capsys.readouterr().out


def test_the_sound_system_passes_where_the_controls_fail():
    assert run("tiny-ckpt-query-under-ingest", system=DeltaTpu())["correct"]


def test_without_the_chip_there_is_no_run_and_no_result(capsys):
    with pytest.raises(SystemExit) as stop:
        harness.main(["--workload", "json-cold-load", "--seed", "1",
                      "--seconds", "1", "--trace", "0"], time.perf_counter())
    assert stop.value.code not in (0, None)
    assert "there is no fallback" in str(stop.value.code)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("environment,refused", [
    ({"MIMALLOC_PURGE_DELAY": "-1"}, False),
    ({"DELTA_TPU_DEVICE_PARSE": "force"}, True),
    ({"CHIPBENCH_TEST_A": "1", "DELTA_TPU_THREADS": "4"}, True)])
def test_a_configuration_sets_no_variable_of_the_program(
        environment, refused, monkeypatch):
    for name in environment:
        monkeypatch.delenv(name, raising=False)
    if refused:
        with pytest.raises(SystemExit, match="may not set DELTA_TPU_"):
            harness.deploy(environment)
        assert not any(n.startswith("DELTA_TPU_") for n in os.environ
                       if n in environment)
    else:
        harness.deploy(environment)
        assert all(os.environ[n] == v for n, v in environment.items())


def test_an_unknown_cell_is_an_error():
    with pytest.raises(SystemExit):
        run("no-such-cell")


def test_a_compilation_inside_the_window_voids_the_run():
    import jax
    import jax.numpy as jnp

    class CompilesLate(DeltaTpu):
        calls = 0

        def load(self, path):
            CompilesLate.calls += 1
            if CompilesLate.calls == 2:     # 1 is the warm-up
                jax.jit(lambda x: x * 3 + CompilesLate.calls)(
                    jnp.ones(7)).block_until_ready()
            return super().load(path)

    with pytest.raises(SystemExit, match="compiled inside the window"):
        run("tiny-json-cold-load", system=CompilesLate())
