"""Blockwise (>HBM) replay: bounded-memory streaming equals the one-shot
kernel and the sequential reference."""

import numpy as np
import pytest

from delta_tpu.ops.replay import python_replay_reference, replay_select
from delta_tpu.ops.replay_blockwise import replay_select_blockwise
from delta_tpu.utils.synth import fa_history


@pytest.mark.parametrize("n,block", [
    (10_000, 2048),      # many small blocks
    (300_000, 65_536),   # several large blocks
    (5_000, 1 << 22),    # single block (degenerate)
])
def test_blockwise_matches_reference(n, block):
    pk, dk, ver, order, add, _ = fa_history(n, seed=n, dv_frac=0.02)
    live_b, tomb_b = replay_select_blockwise(
        [pk, dk], ver, order, add, block_rows=block)
    live_h, tomb_h = python_replay_reference(
        list(zip(pk.tolist(), dk.tolist())), ver, order, add)
    np.testing.assert_array_equal(live_b, live_h)
    np.testing.assert_array_equal(tomb_b, tomb_h)


def test_blockwise_matches_one_shot_kernel():
    pk, dk, ver, order, add, _ = fa_history(200_000, seed=3, dv_frac=0.01)
    live_b, tomb_b = replay_select_blockwise(
        [pk, dk], ver, order, add, block_rows=32_768)
    live_1, tomb_1 = replay_select([pk, dk], ver, order, add)
    np.testing.assert_array_equal(live_b, live_1)
    np.testing.assert_array_equal(tomb_b, tomb_1)


def test_blockwise_out_of_order_rows():
    rng = np.random.default_rng(5)
    n = 50_000
    pk = rng.integers(0, 9000, n).astype(np.uint32)
    dk = rng.integers(0, 2, n).astype(np.uint32)
    ver = rng.integers(0, 512, n).astype(np.int32)   # NOT sorted
    order = rng.integers(0, 64, n).astype(np.int32)
    add = rng.random(n) < 0.6
    live_b, tomb_b = replay_select_blockwise(
        [pk, dk], ver, order, add, block_rows=8192)
    live_h, tomb_h = python_replay_reference(
        list(zip(pk.tolist(), dk.tolist())), ver, order, add)
    np.testing.assert_array_equal(live_b, live_h)
    np.testing.assert_array_equal(tomb_b, tomb_h)


def test_blockwise_device_footprint_is_bounded():
    """The device never holds more than one block + the key bitset: the
    jitted block kernel's operand shapes depend on block_rows, not n."""
    from delta_tpu.ops.replay import pad_bucket

    n, block = 300_000, 16_384
    m = pad_bucket(block)
    assert m * 4 + m // 8 < n  # block footprint well under total rows
    pk, dk, ver, order, add, _ = fa_history(n, seed=9)
    live_b, _ = replay_select_blockwise(
        [pk, dk], ver, order, add, block_rows=block)
    assert live_b.sum() > 0


def test_product_load_routes_blockwise_above_threshold(
        tmp_table_path, monkeypatch):
    """A snapshot load whose action count crosses BLOCKWISE_MIN_ROWS
    reconstructs through the streaming path, with identical results."""
    import pyarrow as pa

    import delta_tpu.api as dta
    import delta_tpu.parallel.gate as gate_mod
    from delta_tpu.engine.tpu import TpuEngine
    from delta_tpu.table import Table

    dta.write_table(tmp_table_path, pa.table(
        {"id": pa.array(np.arange(1000, dtype=np.int64))}),
        target_rows_per_file=100)
    for i in range(3):
        dta.write_table(tmp_table_path, pa.table(
            {"id": pa.array([i], pa.int64())}), mode="append")

    normal = Table.for_path(tmp_table_path, TpuEngine()).latest_snapshot()
    monkeypatch.setattr(gate_mod, "BLOCKWISE_MIN_ROWS", 1)
    blockwise = Table.for_path(
        tmp_table_path, TpuEngine()).latest_snapshot()
    a = sorted(normal.state.add_files_table.column("path").to_pylist())
    b = sorted(blockwise.state.add_files_table.column("path").to_pylist())
    assert a == b
    assert normal.state.size_in_bytes == blockwise.state.size_in_bytes
