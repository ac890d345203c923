"""Device checkpoint-page decoder (`log/page_decode.py`) vs the Arrow
reader as oracle: page-level parity on synthetic parquet (nulls,
multiple row groups, dictionary + plain fallbacks, every bit width
through `shift_extract`), real checkpoint files incl. the
golden fixtures, and the hybrid grafted read equaling a plain Arrow
read. The reference hand-rolls this decode in
`kernel-defaults/.../internal/parquet/ParquetFileReader.java`."""

import glob
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

import delta_tpu.api as dta
from delta_tpu.log.page_decode import (
    DecodeUnsupported,
    read_checkpoint_column,
    read_checkpoint_part_hybrid,
)
from delta_tpu.table import Table


# ---- page-level parity on synthetic parquet --------------------------

def _roundtrip(table, tmp_path, **write_kw):
    p = str(tmp_path / "t.parquet")
    pq.write_table(table, p, **write_kw)
    return p


def _column_parity(path, col):
    ref = pq.read_table(path)
    parts = col.split(".")
    a = ref.column(parts[0])
    for sub in parts[1:]:
        a = pc.struct_field(a, sub)
    vals, valid = read_checkpoint_column(path, col)
    exp = a.to_pylist()
    got = [None if not v else
           (bool(x) if vals.dtype == bool else
            float(x) if vals.dtype == np.float64 else int(x))
           for x, v in zip(vals.tolist(), valid.tolist())]
    assert got == [None if e is None else
                   (bool(e) if isinstance(e, bool) else
                    float(e) if isinstance(e, float) else int(e))
                   for e in exp], col


@pytest.mark.parametrize("codec", ["snappy", "none"])
def test_flat_int64_with_nulls(tmp_path, codec):
    rng = np.random.default_rng(1)
    n = 5_000
    vals = rng.integers(0, 50, n)  # small domain -> dictionary
    mask = rng.random(n) < 0.1
    t = pa.table({"x": pa.array(
        [None if m else int(v) for v, m in zip(vals, mask)],
        pa.int64())})
    p = _roundtrip(t, tmp_path, compression=codec)
    _column_parity(p, "x")


@pytest.mark.parametrize("w", [1, 2, 3, 4, 5, 7, 8, 11, 16])
def test_dictionary_index_widths(tmp_path, monkeypatch, w):
    """A dictionary of 2**w entries has w-bit indices: every width the
    hybrid streams of a checkpoint can carry goes through the plan and
    `shift_extract`, against Arrow's reading of the same file."""
    from delta_tpu.log import page_decode

    widths = []
    plan_hybrid = page_decode._plan_hybrid

    def spy(st, base_off, data, pos, width, n, **kw):
        widths.append(width)
        return plan_hybrid(st, base_off, data, pos, width, n, **kw)

    monkeypatch.setattr(page_decode, "_plan_hybrid", spy)
    rng = np.random.default_rng(w)
    domain = rng.permutation(1 << 20)[:1 << w] * 1_000_003
    n = max(4 << w, 4_096)
    idx = np.concatenate([np.arange(1 << w), rng.integers(0, 1 << w,
                                                          n - (1 << w))])
    mask = rng.random(n) < 0.05
    t = pa.table({"x": pa.array(
        [None if m else int(v) for v, m in zip(domain[idx], mask)],
        pa.int64())})
    p = _roundtrip(t, tmp_path, compression="none")
    _column_parity(p, "x")
    assert w in widths, widths  # the index stream really had that width


def test_plain_fallback_high_cardinality(tmp_path):
    # a huge domain overflows the dictionary -> PLAIN data pages
    rng = np.random.default_rng(2)
    n = 200_000
    t = pa.table({"x": pa.array(rng.integers(0, 1 << 60, n),
                                pa.int64())})
    p = _roundtrip(t, tmp_path, dictionary_pagesize_limit=1024,
                   data_page_size=64 << 10)
    _column_parity(p, "x")


def test_boolean_and_double_and_multiple_row_groups(tmp_path):
    rng = np.random.default_rng(3)
    n = 30_000
    t = pa.table({
        "b": pa.array([None if x < 0.05 else bool(x < 0.5)
                       for x in rng.random(n)], pa.bool_()),
        "d": pa.array(np.round(rng.random(n) * 100, 2), pa.float64()),
    })
    p = _roundtrip(t, tmp_path, row_group_size=7_000)
    _column_parity(p, "b")
    _column_parity(p, "d")


def test_nested_struct_levels(tmp_path):
    rng = np.random.default_rng(4)
    rows = []
    for i in range(4_000):
        r = rng.random()
        if r < 0.1:
            rows.append(None)  # struct null (def 0)
        elif r < 0.2:
            rows.append({"size": None, "flag": None})  # field null (1)
        else:
            rows.append({"size": int(rng.integers(0, 99)),
                         "flag": bool(rng.random() < 0.5)})
    t = pa.table({"add": pa.array(
        rows, pa.struct([("size", pa.int64()), ("flag", pa.bool_())]))})
    p = _roundtrip(t, tmp_path)
    _column_parity(p, "add.size")
    _column_parity(p, "add.flag")


def test_unsupported_shapes_raise(tmp_path):
    t = pa.table({"s": pa.array(["a", "b"]),
                  "l": pa.array([[1, 2], [3]], pa.list_(pa.int64()))})
    p = _roundtrip(t, tmp_path)
    with pytest.raises(DecodeUnsupported):
        read_checkpoint_column(p, "s")  # BYTE_ARRAY out of scope
    with pytest.raises(DecodeUnsupported):
        read_checkpoint_column(p, "l.list.element")  # repeated


# ---- real checkpoints ------------------------------------------------

@pytest.fixture
def checkpoint_path(tmp_table_path):
    rng = np.random.default_rng(5)
    for i in range(15):
        dta.write_table(
            tmp_table_path,
            pa.table({"id": pa.array(rng.integers(0, 1000, 200))}),
            mode="append" if i else "error")
    t = Table.for_path(tmp_table_path)
    t.checkpoint()
    return glob.glob(
        tmp_table_path + "/_delta_log/*.checkpoint.parquet")[0]


def test_real_checkpoint_columns(checkpoint_path):
    for col in ("add.size", "add.modificationTime", "add.dataChange"):
        _column_parity(checkpoint_path, col)


def test_golden_checkpoints():
    fixtures = glob.glob(os.path.join(
        os.path.dirname(__file__), "golden_fixtures", "**",
        "*.checkpoint.parquet"), recursive=True)
    checked = 0
    for path in fixtures:
        leaves = {pq.ParquetFile(path).metadata.schema.column(i).path
                  for i in range(
                      len(pq.ParquetFile(path).metadata.schema))}
        for col in ("add.size", "add.modificationTime",
                    "add.dataChange"):
            if col in leaves:
                _column_parity(path, col)
                checked += 1
    assert checked > 0, "no golden checkpoints found"


def test_hybrid_graft_equals_arrow_read(checkpoint_path):
    ref = pq.read_table(checkpoint_path)
    got = read_checkpoint_part_hybrid(checkpoint_path)
    assert got is not None
    assert set(got.column_names) == set(ref.column_names)
    for name in ref.column_names:
        assert got.column(name).combine_chunks().equals(
            ref.column(name).combine_chunks()), name


def test_zstd_column_parity(tmp_path):
    rng = np.random.default_rng(7)
    n = 8_000
    vals = rng.integers(0, 40, n)
    mask = rng.random(n) < 0.15
    t = pa.table({"x": pa.array(
        [None if m else int(v) for v, m in zip(vals, mask)],
        pa.int64())})
    p = _roundtrip(t, tmp_path, compression="zstd")
    _column_parity(p, "x")


def test_multi_page_column_parity(tmp_path):
    # tiny data pages force many pages per column chunk; the plan packs
    # every page of the chunk into the one lane
    rng = np.random.default_rng(8)
    n = 50_000
    t = pa.table({"x": pa.array(rng.integers(0, 30, n), pa.int64())})
    p = _roundtrip(t, tmp_path, data_page_size=1 << 10)
    assert pq.ParquetFile(p).metadata.row_group(0).column(0) \
        .data_page_offset  # sanity: file really has data pages
    _column_parity(p, "x")


def test_unknown_codec_raises_decode_unsupported():
    from delta_tpu.log.page_decode import PageInfo, _decompress

    page = PageInfo(type=0, uncompressed_size=1, compressed_size=1,
                    num_values=1, encoding=0, payload_start=0)
    with pytest.raises(DecodeUnsupported):
        _decompress(b"\x00", page, "GZIP")
    with pytest.raises(DecodeUnsupported):
        _decompress(b"\x00", page, "LZ4_RAW")


# ---- whole-part device decode + routed snapshot loads ----------------

from delta_tpu import obs as _obs
from delta_tpu.log.page_decode import read_checkpoint_part_device
from delta_tpu.obs.registry import metrics_snapshot, registry


@pytest.fixture
def device_obs():
    """Flip global device-obs mode for one test and restore it."""
    def _set(mode):
        _obs.set_device_obs_mode(mode)
        _obs.reset_device_obs()
        registry().reset()
    yield _set
    _obs.set_device_obs_mode(None)
    _obs.reset_device_obs()


def _counter(name):
    return metrics_snapshot()["counters"].get(name, 0)


def test_strict_mode_real_part_single_dispatch(checkpoint_path,
                                               device_obs):
    # strict mode raises on any budget violation; a real checkpoint
    # part must decode in EXACTLY one device dispatch, clean
    device_obs("strict")
    ref = pq.read_table(checkpoint_path)
    out = read_checkpoint_part_device(checkpoint_path)
    assert out is not None
    tbl, keys = out
    for name in ref.column_names:
        assert tbl.column(name).combine_chunks().equals(
            ref.column(name).combine_chunks()), name
    recs = [r for r in _obs.get_dispatch_records()
            if r["kernel"] == "page_decode.part"]
    assert len(recs) == 1
    assert recs[0]["violations"] == []
    assert _counter("device.budget_violations") == 0
    assert keys is not None and keys.n_bad == 0
    n_add_ref = len(ref.column("add").combine_chunks().drop_null())
    assert keys.n_add == n_add_ref


def test_empty_part_device_read_no_dispatch(tmp_path, device_obs):
    device_obs("on")
    t = pa.table({"add": pa.array(
        [], pa.struct([("path", pa.string()), ("size", pa.int64())]))})
    p = _roundtrip(t, tmp_path)
    out = read_checkpoint_part_device(p)
    assert out is not None
    tbl, keys = out
    assert tbl.num_rows == 0
    assert keys.n_add == keys.n_rem == keys.n_bad == 0
    assert _obs.get_dispatch_records() == []  # zero dispatches


def _build_checkpoint_table(path, seed=6, writes=13, tail_commits=1):
    rng = np.random.default_rng(seed)
    for i in range(writes):
        dta.write_table(
            path,
            pa.table({"id": pa.array(rng.integers(0, 100, 300))}),
            mode="append" if i else "error")
    Table.for_path(path).checkpoint()
    for _ in range(tail_commits):
        dta.write_table(path, pa.table(
            {"id": pa.array([1, 2])}), mode="append")


def _snapshot_parity(a, b):
    assert a.num_files == b.num_files
    at, bt = a.state.add_files_table, b.state.add_files_table
    assert sorted(at.column("path").to_pylist()) == \
        sorted(bt.column("path").to_pylist())
    assert sorted(at.column("size").to_pylist()) == \
        sorted(bt.column("size").to_pylist())


def test_snapshot_load_forced_device_route(tmp_table_path, monkeypatch,
                                           device_obs):
    _build_checkpoint_table(tmp_table_path)
    from delta_tpu.engine.tpu import TpuEngine

    base = Table.for_path(tmp_table_path,
                          TpuEngine()).latest_snapshot()
    _ = base.num_files, base.state.add_files_table  # materialize now
    monkeypatch.setenv("DELTA_TPU_DEVICE_DECODE", "force")
    device_obs("on")
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    _snapshot_parity(snap, base)
    # non-vacuity: the device route really ran, nothing fell back
    assert _counter("decode.device_parts") > 0
    assert _counter("decode.device_fallbacks") == 0


def test_snapshot_load_route_off(tmp_table_path, monkeypatch,
                                 device_obs):
    _build_checkpoint_table(tmp_table_path, seed=9)
    from delta_tpu.engine.tpu import TpuEngine

    monkeypatch.setenv("DELTA_TPU_DEVICE_DECODE", "off")
    device_obs("on")
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    assert snap.num_files == 14  # 13 appends + 1 tail commit
    assert _counter("decode.device_parts") == 0
    assert not [r for r in _obs.get_dispatch_records()
                if r["kernel"].startswith("page_decode.")]


def test_unsupported_codec_falls_back_whole_part(tmp_table_path,
                                                 monkeypatch,
                                                 device_obs):
    _build_checkpoint_table(tmp_table_path, seed=10)
    # rewrite the checkpoint with a codec the device decoder refuses:
    # the forced route must fall back whole-part to Arrow and still
    # produce a correct snapshot
    ckpt = glob.glob(
        tmp_table_path + "/_delta_log/*.checkpoint.parquet")[0]
    pq.write_table(pq.read_table(ckpt), ckpt, compression="gzip")
    from delta_tpu.engine.tpu import TpuEngine

    base = Table.for_path(tmp_table_path,
                          TpuEngine()).latest_snapshot()
    _ = base.num_files, base.state.add_files_table  # materialize now
    monkeypatch.setenv("DELTA_TPU_DEVICE_DECODE", "force")
    device_obs("on")
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    _snapshot_parity(snap, base)
    assert _counter("decode.device_fallbacks") == 1
    assert _counter("decode.device_parts") == 0


def test_checkpoint_only_load_uses_device_handoff(tmp_table_path,
                                                  monkeypatch,
                                                  device_obs):
    # a load served purely from the checkpoint hands the decoded key
    # codes straight to the replay reducer on device: the handoff
    # dispatch replaces the replay upload dispatch entirely
    _build_checkpoint_table(tmp_table_path, seed=11, tail_commits=0)
    from delta_tpu.engine.tpu import TpuEngine

    base = Table.for_path(tmp_table_path,
                          TpuEngine()).latest_snapshot()
    _ = base.num_files, base.state.add_files_table  # materialize now
    monkeypatch.setenv("DELTA_TPU_DEVICE_DECODE", "force")
    device_obs("strict")
    snap = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    _snapshot_parity(snap, base)
    names = [r["kernel"] for r in _obs.get_dispatch_records()]
    assert "page_decode.handoff" in names
    assert not any(n.startswith("replay.single") for n in names)
    assert _counter("decode.handoff_launches") == 1
    assert _counter("device.budget_violations") == 0
