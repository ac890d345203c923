"""The generator, its manifest and the traffic: the manifest is held to
the plain reference (`chipbench/reference/oracle.py`) and to both
engines at a small size, checkpoint and `_last_checkpoint` included."""

import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import traffic
from chipbench.gen import deltalog
from chipbench.reference import oracle

JSON_ONLY = dict(commits=40, actions_per_commit=100, remove_fraction=0.2,
                 staged_commits=12)
CKPT10 = dict(JSON_ONLY, commits=64, checkpoint_interval=10,
              retained_commits=20)
CONFIGS = {"json-only": JSON_ONLY, "ckpt10": CKPT10}


def sha(paths) -> str:
    return hashlib.sha256("\n".join(sorted(paths)).encode()).hexdigest()


def state_by_oracle(path):
    state = oracle.read_table_state(path)
    live = state.live
    return (len(live), sum(a["size"] for a in live.values()),
            sha(p for p, _ in live))


def state_by_engine(engine_name):
    def read(path):
        from delta_tpu import Table
        from delta_tpu.engine.host import HostEngine
        from delta_tpu.engine.tpu import TpuEngine
        from delta_tpu.replay.columnar import clear_parse_cache

        clear_parse_cache()
        engine = {"host": HostEngine, "tpu": TpuEngine}[engine_name]()
        snap = Table.for_path(path, engine).latest_snapshot()
        paths = snap.state.add_files_table.column("path").to_pylist()
        return snap.num_files, snap.size_in_bytes, sha(paths)
    return read


READERS = {"oracle": state_by_oracle, "HostEngine": state_by_engine("host"),
           "TpuEngine": state_by_engine("tpu")}


@pytest.mark.parametrize("reader", sorted(READERS))
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_manifest_is_what_every_reader_finds(tmp_path, config, reader):
    m = deltalog.generate(str(tmp_path), CONFIGS[config], seed=2**31 + 11)
    for landed in (0, 5, 7):   # 7 more: past the next multiple of 10
        m.land(landed)
        assert READERS[reader](m.table_path) == (
            m.num_files(), m.size_in_bytes(), m.digest()), landed


def test_checkpointed_table_is_laid_out_as_the_configuration_says(tmp_path):
    m = deltalog.generate(str(tmp_path), CKPT10, seed=3)
    log = os.path.join(m.table_path, "_delta_log")
    names = sorted(os.listdir(log))
    assert m.checkpoint_version == 60 and m.version == 63
    assert f"{60:020d}.checkpoint.parquet" in names
    commits = [int(n[:20]) for n in names if n.endswith(".json")]
    assert commits == list(range(40, 64))       # 20 retained + 60..63
    hint = json.load(open(os.path.join(log, "_last_checkpoint")))
    assert hint["version"] == 60 and hint["size"] == hint["numOfAddFiles"] + 2
    assert len(m.staged) == 12 and len(os.listdir(m.staged_dir)) == 12
    # a cold load reads the checkpoint and the three commits after it
    assert m.load_actions == hint["size"] + 3 * 100


def test_checkpoint_rows_come_as_a_writer_emits_them(tmp_path):
    import pyarrow.parquet as pq

    m = deltalog.generate(str(tmp_path), CKPT10, seed=3)
    rows = pq.read_table(os.path.join(
        m.table_path, "_delta_log", f"{60:020d}.checkpoint.parquet"))
    assert rows.column_names == ["protocol", "metaData", "add"]  # no remove
    paths = [a["path"] for a in rows.column("add").to_pylist()[2:]]
    assert len(paths) == len(set(paths)) > 1000
    assert paths != sorted(paths)
    # neither by path nor by age: neighbours are far apart
    ids = np.array([int(p[5:15]) for p in paths])
    assert np.median(np.abs(np.diff(ids))) > len(ids) / 10


def test_a_reader_starts_from_the_checkpoint(tmp_path):
    from delta_tpu import Table

    m = deltalog.generate(str(tmp_path), CKPT10, seed=3)
    snap = Table.for_path(m.table_path).latest_snapshot()
    assert snap.log_segment.checkpoint_version == 60
    assert snap.version == 63


@pytest.mark.parametrize("fid,version", [(0, 0), (79, 0), (123456, 1543)])
def test_action_lines_are_what_json_dumps_writes(fid, version):
    dumps = lambda o: json.dumps(o, separators=(",", ":"))  # noqa: E731
    stats = dumps({"numRecords": 1000, "minValues": {"x": (fid + 1) * 1000},
                   "maxValues": {"x": (fid + 2) * 1000},
                   "nullCount": {"x": 0}})
    path = f"part-{fid:010d}.parquet"
    assert deltalog.add_line(fid, version) == dumps({"add": {
        "path": path, "partitionValues": {}, "size": 1 << 20,
        "modificationTime": version, "dataChange": True, "stats": stats}})
    assert deltalog.remove_line(fid, version) == dumps({"remove": {
        "path": path, "deletionTimestamp": version, "dataChange": True}})


def tree(root) -> dict:
    out = {}
    for base, _, files in os.walk(root):
        for name in files:
            with open(os.path.join(base, name), "rb") as f:
                out[os.path.relpath(os.path.join(base, name), root)] = (
                    hashlib.sha256(f.read()).hexdigest())
    return out


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_seed_decides_the_fixture(tmp_path, config):
    a = deltalog.generate(str(tmp_path / "a"), CONFIGS[config], seed=7)
    b = deltalog.generate(str(tmp_path / "b"), CONFIGS[config], seed=7)
    c = deltalog.generate(str(tmp_path / "c"), CONFIGS[config], seed=8)
    assert tree(tmp_path / "a") == tree(tmp_path / "b")
    assert a.digest() == b.digest() != c.digest()
    assert a.num_files() == c.num_files()   # the seed changes no size


@pytest.mark.parametrize("lo,hi", [(0, 1), (1000, 1001), (5000, 9000),
                                   (5001, 8999), (4999, 9001),
                                   (10**9, 10**9 + 5), (7000, 7000)])
def test_scan_expected_is_the_filter_on_min_and_max(tmp_path, lo, hi):
    m = deltalog.generate(str(tmp_path), JSON_ONLY, seed=5)
    ids = m.live_ids()
    mins, maxs = (ids + 1) * 1000, (ids + 2) * 1000
    want = ids[(maxs >= lo) & (mins < hi)] if hi > lo else ids[:0]
    assert m.scan_expected(lo, hi).tolist() == want.tolist()


MIX = {"block": 8, "draws": {
    "commits": {"kind": "every", "period": 4, "values": [3, 9],
                "otherwise": 0},
    "position": {"kind": "uniform"},
    "width_share": {"kind": "log_grid", "lo": 0.001, "hi": 0.05,
                    "where": "commits", "equals": 0, "otherwise": 0.007}}}


def take(mix, seed, n):
    it = traffic.schedule(mix, seed)
    return [next(it) for _ in range(n)]


def test_the_seed_permutes_the_traffic_and_changes_no_amount():
    a, b, c = take(MIX, 2**31 + 5, 24), take(MIX, 2**31 + 5, 24), take(MIX, 6, 24)
    assert a == b != c
    for name in MIX["draws"]:
        for block in range(3):
            rows = slice(8 * block, 8 * block + 8)
            assert sorted(p[name] for p in a[rows]) == sorted(
                p[name] for p in c[rows]), name
    assert [p["commits"] > 0 for p in a[:8]] == [False] * 3 + [True] + [
        False] * 3 + [True]
    # the grid is spread over the plans alone: a refresh takes no point
    assert [p["width_share"] for p in a[:8] if p["commits"]] == [0.007] * 2
    widths = sorted(p["width_share"] for p in a[:8] if not p["commits"])
    assert widths[0] == pytest.approx(0.001) and widths[-1] == pytest.approx(0.05)
    assert np.allclose(np.diff(np.log(widths)), np.log(50) / 5)
    assert sorted(p["position"] for p in a[:8]) == pytest.approx(
        [(i + 0.5) / 8 for i in range(8)])


@pytest.mark.parametrize("draw", [
    {"kind": "every", "period": 3, "values": [1, 2], "otherwise": 0},
    {"kind": "zipf"}])
def test_a_draw_the_generator_cannot_fill_is_an_error(draw):
    with pytest.raises(ValueError):
        take({"block": 8, "draws": {"d": draw}}, 1, 1)
