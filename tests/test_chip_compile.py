"""Ask the v5e compiler, not the chip: the device kernels of the product
path compile for a *described* TPU v5e at the shapes `chip_smoke.py`
produces (on-chip-measurement guide, section 2).

Nothing runs — a passing compile says the chip's compiler accepts the
program (tiling, VMEM, HBM, Mosaic legality), not that its result or its
speed is right. Everything about the chip happens inside the `topo`
fixture, after this file's first test has started: only the xdist worker
that is handed this file loads the TPU compiler.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from delta_tpu.ops import json_parse, page_decode, pallas_kernels, skipping
from delta_tpu.ops import replay, scans, sqlops, zorder
from delta_tpu.ops import stats as ckstats
from delta_tpu.stats import device_index

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep these out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    # traces made with interpret=False must not leak to CPU tests that
    # share this worker
    json_parse._parse_fn_cached.cache_clear()
    page_decode._decode_fn.cache_clear()
    skipping._skip_fn_cached.cache_clear()
    device_index._halves_fn.cache_clear()
    ckstats._agg_fn_cached.cache_clear()
    jax.clear_caches()      # `zorder._curve_perm`'s trace among them


@pytest.fixture
def on_chip(topo, monkeypatch):
    """Shape builder placed on the described chip, with the kernels
    steered onto their TPU branch (compiled Mosaic, device byte
    classes) as `jax.default_backend() == "tpu"` would."""
    monkeypatch.setattr(pallas_kernels, "_use_interpret", lambda: False)
    monkeypatch.setattr(json_parse, "_use_device_classes", lambda: True)
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    return shape


def _assert_fits(compiled):
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, ma


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_interleave_bits_tiled_4m_rows(on_chip):
    cols = on_chip((3, 1 << 22), jnp.uint32)
    with jax.enable_x64(False):  # as interleave_bits_auto pins it
        compiled = (pallas_kernels.interleave_bits_tiled
                    .lower(cols, n_bits=32).compile())
    _assert_mosaic(compiled)


def test_zorder_curve_perm_one_sold_date(on_chip):
    """`optimize-zorder-under-ingest`'s launch: three key lanes of one
    sold date of the 3 TB `store_sales`, 4,739,406 rows in a bucket of
    5,242,880. Every sort of the program is one two-operand sort in one
    loop, so the compiler builds ONE; as three stable `argsort`s with
    their scatters and one stable four-operand sort it held seven
    multi-operand sorts and took this compiler 155 s here (76 s on the
    chip's host); on single-operand radix passes 8.5 s, but 3.75 s a
    launch on the chip for 0.15 s (PERF.md, PR 55)."""
    import time

    began = time.perf_counter()
    with jax.enable_x64(False):     # as `interleave_bits_auto` pins it
        compiled = zorder._curve_perm.lower(
            on_chip((3, 5_242_880), jnp.uint32), curve="zorder").compile()
    took = time.perf_counter() - began
    print(f"zorder.curve_perm (3, 5242880): compiled in {took:.1f} s, "
          f"temporaries "
          f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:.0f} MB")
    assert took < 90
    _assert_mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    sorts = [line for line in compiled.as_text().splitlines()
             if " sort(" in line]
    # one sort, of a key lane and the places: no scatter became another
    assert len(sorts) == 1 and sorts[0].count("[5242880]") == 2, sorts


def test_byte_class_tiled_64mib(on_chip):
    window = on_chip((64 << 20,), jnp.uint8)
    with jax.enable_x64(True):  # traced inside the x64 parse jit
        compiled = pallas_kernels.byte_class_tiled.lower(window).compile()
    _assert_mosaic(compiled)


def test_shift_extract_tiled_16m(on_chip):
    lane = on_chip((16 << 20,), jnp.uint32)
    with jax.enable_x64(False):  # as decode_part pins it
        compiled = (pallas_kernels.shift_extract_tiled
                    .lower(lane, lane, lane, lane).compile())
    _assert_mosaic(compiled)


def test_winner_kernel_1m_bucket(on_chip):
    m = replay.pad_bucket(1_000_000)
    operands = (on_chip((m,), jnp.uint8),) * 3 + (on_chip((), jnp.int32),)
    _assert_fits(replay._winner_kernel.lower(operands, width=3).compile())


def test_winner_kernel_fa_packed_1m_bucket(on_chip):
    # the smoke's cold-load layout: 3 ref planes over 262144 refs
    m, r_pad = replay.pad_bucket(1_000_000), 262144
    layout = (m, 3, r_pad, 0)
    buf = on_chip((8 + m // 8 + 3 * r_pad,), jnp.uint8)
    _assert_fits(replay._winner_kernel_fa_packed
                 .lower(buf, layout=layout).compile())


def test_json_parse_window_1mib(on_chip):
    n_pad, l_pad = 1 << 20, 8192
    with jax.enable_x64(True):  # as parse_window_fields runs it
        fn = json_parse._parse_fn_cached(
            n_pad, l_pad, json_parse._use_device_classes())
        compiled = fn.lower(
            on_chip((n_pad + json_parse._TAIL_PAD,), jnp.uint8),
            on_chip((), jnp.int32)).compile()
    _assert_mosaic(compiled)


def test_json_parse_window_product_shape(on_chip):
    """`json-cold-load`'s window: 64 Mi bytes, 512 Ki lines. The scan
    form reserves 3,720 MB of temporaries (the segment reduces it
    replaced: 4,251 MB) and keeps three scatters, all of positions."""
    n_pad, l_pad = 64 << 20, 512 << 10
    with jax.enable_x64(True):
        fn = json_parse._parse_fn_cached(n_pad, l_pad, True)
        compiled = fn.lower(
            on_chip((n_pad + json_parse._TAIL_PAD,), jnp.uint8),
            on_chip((), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    assert compiled.as_text().count(" scatter(") == 3


def test_chunked_scans_64m(on_chip):
    """The scans that made the parse jit uncompilable at its product
    window, at that window's length."""
    lane = on_chip((64 << 20,), jnp.int32)
    compiled = jax.jit(
        lambda x: (scans.cumsum_1d(x), scans.cummax_1d(x),
                   scans.cummin_1d(x), scans.cummin_1d(x, reverse=True))
    ).lower(lane).compile()
    _assert_fits(compiled)


def test_page_decode_part(on_chip):
    b_pad, r_pad, p_pad, h_pad, n_pad = 262144, 2048, 256, 1 << 20, 1 << 19
    with jax.enable_x64(False):  # as decode_part runs it
        fn = page_decode._decode_fn(b_pad, r_pad, p_pad, h_pad, n_pad,
                                    n_pad, True, True)
        compiled = fn.lower(
            on_chip((b_pad,), jnp.uint8),
            on_chip((r_pad, page_decode.RUN_F), jnp.int32),
            on_chip((p_pad, page_decode.PAGE_F), jnp.int32)).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("rows,f_pad,a_pad", [
    (4, 1 << 20, 16),
    # `ckpt-query-under-ingest`: 2.4M files, a range plan's two atoms
    (4, 2_621_440, 2),
    # `bids-query-under-ingest`: four indexed columns of seven (13 lanes);
    # an event-time window alone, and with five auctions listed (7 atoms)
    (13, 2_621_440, 2),
    (13, 2_621_440, 8),
    # `sales-query-under-ingest`: all 23 columns of `store_sales` indexed
    # (70 lanes); a range of sold dates alone, and with one of Query 28's
    # buckets, its OR over three ranges distributed (28 atoms)
    (70, 2_621_440, 2),
    (70, 2_621_440, 32),
])
def test_skipping_mask_block(on_chip, rows, f_pad, a_pad):
    tiles = (rows, f_pad // device_index.TILE_FILES, device_index.TILE_FILES)
    atoms = on_chip((a_pad,), jnp.int32)
    compiled = skipping._skip_fn_cached(a_pad).lower(
        on_chip(tiles, jnp.int32), on_chip(tiles, jnp.uint32),
        on_chip(tiles, jnp.bool_),
        atoms, atoms, atoms, atoms, atoms, on_chip((a_pad,), jnp.uint32),
        atoms, on_chip((), jnp.int32)).compile()
    _assert_fits(compiled)
    text = compiled.as_text()
    # the index is resident as 32-bit halves: no launch splits an int64
    # lane (as int64 the program split all `rows` of them, whichever the
    # atoms named: 1.5 GB of temporaries at 70 lanes)
    assert "X64Split" not in text
    # no `[a_pad, f_pad]` copy of the lanes, and nothing that grows with
    # the index: under 64 bytes a file whatever `rows`
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * f_pad
    # a lane row is whole tiles: never one sublane of every 8-row tile
    slices = [line.split(" dynamic-slice(")[0] for line in text.splitlines()
              if " dynamic-slice(" in line and f"[1,{tiles[1]},128]" in line]
    assert len(slices) >= 3
    assert all("T(8,128)" in sl and "T(1,128)" not in sl for sl in slices)
    assert " while(" not in text


@pytest.mark.parametrize("rows,piece", [(4, 4), (13, 4), (13, 1), (70, 4),
                                        (70, 2)])
def test_stats_index_upload_2_6m_files(on_chip, rows, piece):
    # the index of `ckpt-query-under-ingest` (4 lanes), of
    # `bids-query-under-ingest` (13) and of `sales-query-under-ingest`
    # (70): 2.4M files pad to 2,621,440; a whole piece of the upload and
    # the last one. As `jnp.unpackbits` over uint8 words the validity's
    # unpack took 102 s to compile
    n_pad = 2_621_440
    assert piece in (device_index._UPLOAD_ROWS,
                     rows % device_index._UPLOAD_ROWS)
    tiles = (rows, n_pad // device_index.TILE_FILES, device_index.TILE_FILES)
    resident = (on_chip(tiles, jnp.int32), on_chip(tiles, jnp.uint32),
                on_chip(tiles, jnp.bool_))
    with jax.enable_x64(True):
        compiled = device_index._halves_fn().lower(
            *resident, on_chip((piece, n_pad), jnp.int64),
            on_chip((piece, n_pad // 32), jnp.uint32),
            on_chip((), jnp.int32)).compile()
    _assert_fits(compiled)
    ma = compiled.memory_analysis()
    # the resident arrays are donated and written in place: the chip
    # holds the index once, and beside it a piece's int64 rows and what
    # splitting them takes (split whole, 70 lanes take 1.53 GB of
    # argument, 1.65 GB of results and 1.68 GB of temporaries at once)
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in resident)
    assert ma.alias_size_in_bytes == held
    assert ma.temp_size_in_bytes <= 3 * 8 * piece * n_pad


@pytest.mark.parametrize("p_pad, win, one_code", [
    (8, 2_621_440, True),     # the cell: one part, an unpartitioned table
    (128, 32_768, False),     # 100 parts of 24,000 rows, partition codes
], ids=["one-part-one-code", "a-hundred-parts-of-codes"])
def test_ckpt_stats_block_2_6m_files(on_chip, p_pad, win, one_code):
    # the checkpoint's stats block at `ckpt-write-under-ingest`'s bucket:
    # 2.4M live files pad to 2,621,440, one part to 8. The first form
    # (`jnp.unpackbits` over uint8 words, a one-key int64 `jnp.sort`,
    # sixteen vmapped scatters) took this compiler 110 s and 2.9 GB of
    # temporaries; this one seconds and a tenth of that, with the pairs'
    # radix sort (the second case) or without it (the cell's)
    import time

    lanes, n_pad = 4, 2_621_440
    began = time.perf_counter()
    with ckstats._x64():
        compiled = ckstats._agg_fn_cached(
            lanes, n_pad, p_pad, win, one_code).lower(
            on_chip((lanes, n_pad), jnp.int64),
            on_chip((lanes, n_pad // 32), jnp.uint32),
            on_chip((n_pad,), jnp.int32), on_chip((), jnp.int32),
            on_chip((), jnp.int64), on_chip((), jnp.int32)).compile()
    took = time.perf_counter() - began
    print(f"stats.ckpt_block {lanes, n_pad, p_pad, win, one_code}: compiled "
          f"in {took:.1f} s, temporaries "
          f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:.0f} MB")
    assert took < 60
    _assert_fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_sql_group_aggregate_4m_rows(on_chip):
    n = 1 << 22
    with jax.enable_x64(True):
        compiled = sqlops._segagg_kernel.lower(
            on_chip((n,), jnp.int32), on_chip((n,), jnp.int64),
            on_chip((n,), jnp.bool_), op="sum", n_seg=256).compile()
    _assert_fits(compiled)


@pytest.mark.parametrize("nl_pad, nr_pad", [
    (8_192, 3_145_728),       # a filtered dimension probes store_sales
    (3_145_728, 131_072),     # store_sales probes date_dim or time_dim
    (3_145_728, 2_097_152),   # store_sales probes customer_demographics
])
def test_sql_join_lanes_at_tpcds_sf1_shapes(on_chip, nl_pad, nr_pad):
    """The join's sort in its radix form (one single-operand uint32 sort
    a pass) at the padded shapes `tpcds-q-mix` gives it: 2,880,404 rows
    of `store_sales` in a bucket of 3,145,728 against a dimension."""
    with jax.enable_x64(True):
        compiled = sqlops._join_lanes_kernel.lower(
            on_chip((nl_pad,), jnp.int64), on_chip((nr_pad,), jnp.int64),
            on_chip((), jnp.int32), on_chip((), jnp.int32),
            on_chip((), jnp.int64), on_chip((), jnp.int32)).compile()
    _assert_fits(compiled)
    # one sort in the program, and that of one operand: the form the
    # compiler takes seconds over, not minutes
    sorts = [line for line in compiled.as_text().splitlines()
             if " sort(" in line]
    assert len(sorts) == 1 and "u32[" in sorts[0], sorts


def test_sharded_replay_fa_6m_rows_on_four_chips(topo):
    """The mesh route's `shard_map` program at the shape a cold load of
    `deltalog-10m-ckpt10` gives it (6,000,380 rows: 1,500,095 a shard in
    a bucket of 1,572,864; three byte planes of 128 refs; the key lane
    kept), for the four chips of the described host: what the compiler
    refuses costs no four-chip call."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from delta_tpu.parallel import sharded_replay
    from delta_tpu.parallel.mesh import REPLAY_AXIS

    shards = 4
    mesh = Mesh(np.array(topo.devices[:shards]), (REPLAY_AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(REPLAY_AXIS, None))
    m = replay.pad_bucket(1_500_095)
    width = replay.key_byte_width(1_500_095)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct((shards, *dims), dtype, sharding=rows)

    words = shape((m // 32,), jnp.uint32)
    fn = sharded_replay.build_sharded_replay_fa_fn(mesh, width, False, True)
    try:
        compiled = fn.lower(words, *[shape((128,), jnp.uint8)] * width,
                            shape((1,), jnp.int32), words).compile()
    finally:
        sharded_replay._fa_fn_cached.cache_clear()
    _assert_fits(compiled)
    ma = compiled.memory_analysis()
    # a chip's own: its lane of m keys kept, the winner words, the count
    assert ma.output_size_in_bytes < 2 * (m * 4 + m // 8)
    text = compiled.as_text()
    # one collective, the count's, under its scope
    assert text.count(" all-reduce(") == 1
    assert "replay.psum/psum" in text


def test_resident_append_6m_rows_on_four_chips(topo, monkeypatch):
    """The resident route's append (`parallel/resident.py`) at the shape
    a refresh of `deltalog-10m-stream` gives it: four key lanes of
    1,572,864 slots, a landed commit's 100 rows in 128 delta slots a
    shard, the lanes donated as on a chip (the CPU backend does not
    donate, so the builder is steered onto its TPU branch here): the
    scatter and the sort in place, no second lane."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from delta_tpu.parallel import resident
    from delta_tpu.parallel.mesh import REPLAY_AXIS

    shards, d_pad = 4, 128
    mesh = Mesh(np.array(topo.devices[:shards]), (REPLAY_AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(REPLAY_AXIS, None))
    m = replay.pad_bucket(1_500_095)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct((shards, *dims), dtype, sharding=rows)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        fn = resident._append_fn_cached(mesh, d_pad)
        compiled = fn.lower(shape((m,), jnp.uint32),
                            shape((d_pad,), jnp.int32),
                            shape((d_pad,), jnp.uint32),
                            shape((1,), jnp.int32)).compile()
    finally:
        resident._append_fn_cached.cache_clear()
    _assert_fits(compiled)
    ma = compiled.memory_analysis()
    # a chip's own: its lane of m keys comes back in the buffer it came
    # in, beside the winner words
    assert ma.alias_size_in_bytes == m * 4
    assert ma.output_size_in_bytes <= m * 4 + m // 8 + 1024
    assert " all-reduce(" not in compiled.as_text()     # no shard asks another
