"""`BENCHMARK.json` against the rules of its contract that a test can
hold it to, and against the files its names lead to."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(name="BENCHMARK.json"):
    with open(os.path.join(ROOT, name)) as f:
        return json.load(f)


BENCH = load()
FILES = ["BENCHMARK.json", "tests/chipbench/extra/benchmark.json"]


def entries(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            yield group, e


@pytest.mark.parametrize("file", FILES)
def test_names_units_and_lines_are_within_the_allowed_characters(file):
    bench = load(file)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group, e in entries(bench):
        assert NAME.match(e["name"]), e["name"]
        for key in ("config", "traffic", "moves"):
            assert key not in e or NAME.match(e[key]), (e["name"], key)
        for key in ("why", "layer", "source"):
            if key in e and group in ("configs", "workloads", "per_layer"):
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["name"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metric_names = [e["name"] for g in ("end_to_end", "per_layer")
                    for e in bench[g]]
    assert len(metric_names) == len(set(metric_names))


def test_the_entries_have_just_the_keys_of_the_contract():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, e in entries(BENCH):
        assert set(e) - {"workloads"} == keys[group], e["name"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_bounds_and_references_hold_together():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        mine = set(m.get("workloads", cells))
        assert mine <= cells
        assert mine <= set(e2e[m["moves"]].get("workloads", cells)), m["name"]
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("file", FILES)
def test_every_name_leads_to_a_file_of_its_own(file):
    bench = load(file)

    def found(kind, filename):
        return any(os.path.exists(os.path.join(ROOT, p, kind, filename))
                   for p in bench["paths"])

    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        config = load(c["file"])
        assert config["name"] == c["name"]
        assert found("gen", config["generator"]["kind"] + ".py")
    for w in bench["workloads"]:
        assert found("mixes", w["traffic"] + ".json")
        mix = next(load(os.path.join(p, "mixes", w["traffic"] + ".json"))
                   for p in bench["paths"]
                   if os.path.exists(os.path.join(
                       ROOT, p, "mixes", w["traffic"] + ".json")))
        assert found("drivers", mix["driver"] + ".py")
    for m in bench["per_layer"]:
        assert found("layers", m["name"] + ".py"), m["name"]


def test_a_configuration_states_its_source_cuts_and_guarantees():
    for c in BENCH["configs"]:
        config = load(c["file"])
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["guarantees"] and config["assumed"]
        # no shape of the source is cut: only the number of commits
        assert config["generator"]["actions_per_commit"] == 100
        assert config["generator"]["remove_fraction"] == 0.2
        assert not any(name.startswith("DELTA_TPU_")
                       for name in config.get("environment", {}))
