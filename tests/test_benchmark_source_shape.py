"""The log generator `BENCHMARK.json` cites and the copies that stand in
for it write the same log.

`deltalog-500k` names `benchmarks/workloads.py::synth_delta_log` as the
form of its source; the benchmark runs `chipbench/gen/deltalog.py`,
whose docstring says "same action form, byte for byte"; and
`chip_smoke.py` writes its log with the first and parks the tail in a
staging directory. Held here: every line the cited generator writes is
the line `chipbench.gen.deltalog` forms for the same file id and
version, and `chip_smoke`'s table plus staged tail is the generator's
whole log, before and after the tail lands.
"""

import json
import os

import pytest

import chip_smoke
from benchmarks.workloads import synth_delta_log
from chipbench.gen import deltalog
from chipbench.reference.oracle import read_table_state

COMMITS = 3
FILES_PER_COMMIT = 10


@pytest.fixture(scope="module")
def cited_log(tmp_path_factory):
    """[(version, line)] of a 3-commit log by the cited generator."""
    path = str(tmp_path_factory.mktemp("cited"))
    synth_delta_log(path, COMMITS, FILES_PER_COMMIT, seed=7)
    log = os.path.join(path, "_delta_log")
    assert sorted(os.listdir(log)) == [
        deltalog.commit_name(v) for v in range(COMMITS)]
    lines = []
    for v in range(COMMITS):
        with open(os.path.join(log, deltalog.commit_name(v))) as f:
            body = f.read()
        assert body.endswith("\n")
        lines += [(v, line) for line in body[:-1].split("\n")]
    return lines


def _fid(action: dict) -> int:
    name = action["path"]
    assert name.startswith("part-") and name.endswith(".parquet")
    return int(name[len("part-"):-len(".parquet")])


def _expected(kind: str, version: int, line: str) -> str:
    if kind == "protocol":
        return deltalog.PROTOCOL
    if kind == "metaData":
        return deltalog.METADATA
    form = deltalog.add_line if kind == "add" else deltalog.remove_line
    return form(_fid(json.loads(line)[kind]), version)


@pytest.mark.parametrize("kind,count", [
    ("protocol", 1), ("metaData", 1),
    # 20% removes: 8 adds a commit, 2 removes once there is a file
    ("add", 8 * COMMITS), ("remove", 2 * (COMMITS - 1))])
def test_cited_generator_writes_the_benchmarks_lines(cited_log, kind, count):
    of_kind = [(v, line) for v, line in cited_log
               if next(iter(json.loads(line))) == kind]
    assert len(of_kind) == count
    for version, line in of_kind:
        assert line == _expected(kind, version, line)


# -- chip_smoke's log: the table and the staged tail are the whole log --

SMOKE_COMMITS = 5
ALL_COMMITS = SMOKE_COMMITS + chip_smoke.APPEND_COMMITS


def _live_after(commits: int) -> int:
    """80 adds a commit, 20 removes a commit but the first."""
    removes = chip_smoke.FILES_PER_COMMIT // 5
    return (chip_smoke.FILES_PER_COMMIT - removes) * commits \
        - removes * (commits - 1)


def _live(path: str, version=None):
    return sorted(p for p, _ in read_table_state(path, version).live)


def _commits(directory: str):
    return sorted(int(n[:20]) for n in os.listdir(directory)
                  if n.endswith(".json") and n[:20].isdigit())


@pytest.mark.parametrize("tail", ["staged", "landed"])
def test_chip_smoke_log_is_the_generators(tmp_path, tail):
    whole = str(tmp_path / "whole")
    synth_delta_log(whole, ALL_COMMITS, chip_smoke.FILES_PER_COMMIT, seed=3)
    path, staged = chip_smoke.make_log(str(tmp_path), SMOKE_COMMITS, seed=3)
    log = os.path.join(path, "_delta_log")

    def held_back():
        assert _commits(log) == list(range(SMOKE_COMMITS))
        assert _commits(staged) == list(range(SMOKE_COMMITS, ALL_COMMITS))
        assert all(n.endswith(".json") for n in os.listdir(log))
        live = _live(path)
        assert live == _live(whole, SMOKE_COMMITS - 1)
        assert len(live) == _live_after(SMOKE_COMMITS)

    held_back()
    if tail == "staged":
        return
    chip_smoke.append_staged(path, staged)
    assert _commits(log) == list(range(ALL_COMMITS))
    assert os.listdir(staged) == []
    live = _live(path)
    assert live == _live(whole)
    assert len(live) == _live_after(ALL_COMMITS)
    # what the smoke's later steps leave in the log goes with a reset
    for stray in ("_last_checkpoint", f"{4:020d}.crc"):
        with open(os.path.join(log, stray), "w") as f:
            f.write("{}")
    os.makedirs(os.path.join(log, "_sidecars"))
    chip_smoke.reset_log(path, staged, SMOKE_COMMITS)
    held_back()
