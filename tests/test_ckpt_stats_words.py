"""The checkpoint stats block in the form the chip compiles in seconds
(`ops/stats.py`: validity in uint32 words unpacked by shift-and-mask,
the (part, code) pairs put in order by `sqlops._radix_perm`, a masked
pass a part, over the part's window of rows where the parts are runs
in order) against `host_stats_block`, bit for bit, at the edges the
words, the pairs and the windows have: a row count that is no multiple
of 32, lanes with no valid row, parts with no row, a partitioned
table's codes, pair keys that take more than one radix pass, parts in
the writer's fixed chunks with a short last one, and an unpartitioned
table's one code, which is counted without a sort."""

import numpy as np
import pytest

from delta_tpu import obs
from delta_tpu.ops import stats as ckstats
from delta_tpu.ops.replay import _pack_bits, pad_bucket


def lanes_of(rng, n, n_parts, n_codes, all_null=(), empty_parts=(),
             in_order=False):
    """Three value lanes with a fifth of their rows null and the codes
    lane, as `_checkpoint_aggregates` builds them; `in_order`: the parts
    are runs of rows, as the writer's are."""
    lanes = [rng.integers(-2**62, 2**62, size=n) for _ in range(3)]
    valids = [rng.random(n) > 0.2 for _ in range(3)]
    for i in all_null:
        valids[i] = np.zeros(n, bool)
    lanes.append(rng.integers(0, n_codes, size=n))
    valids.append(np.ones(n, bool))
    parts = [p for p in range(n_parts) if p not in empty_parts]
    part_of = rng.choice(parts, size=n).astype(np.int32)
    if in_order:
        part_of.sort()
    return lanes, valids, part_of


CASES = {
    # n, n_parts, n_codes, lanes that are null throughout, empty parts,
    # the parts in order
    "one-row": (1, 1, 1, (), (), False),
    "n-31": (31, 1, 1, (), (), False),
    "n-33-several-parts": (33, 3, 1, (), (), False),
    "n-1000-not-a-multiple-of-32": (1000, 9, 5, (), (), False),
    "a-word-boundary-of-the-bucket": (1024, 2, 1, (), (), False),
    "past-a-bucket": (1025, 2, 7, (), (), False),
    "all-null-lanes": (777, 4, 3, (0, 2), (), False),
    "an-empty-part": (900, 6, 4, (), (2,), False),
    "the-last-part-empty": (900, 6, 4, (), (5,), False),
    "a-partitioned-tables-codes": (5000, 3, 1200, (), (), False),
    # 40 parts pad to 64, x 70,001 codes: 23 bits of key, two passes of
    # the 19 bits a digit has at 8,192 rows
    "keys-of-two-radix-passes": (6000, 40, 70000, (), (7, 8), False),
    # runs of rows in order: a pass reads a window of 1,024 or 2,048 rows
    # of the 8,192 from the part's first row, the last ones clamped
    "parts-in-order": (5000, 7, 5, (), (), True),
    "parts-in-order-with-empty-ones": (5000, 9, 5, (1,), (0, 4, 8), True),
    "parts-in-order-of-one-code": (5000, 7, 1, (2,), (3,), True),
    "forty-parts-in-order": (6000, 40, 70000, (), (7, 8), True),
}


def test_a_pass_reads_a_window_where_the_parts_are_in_order():
    rng = np.random.default_rng(0)
    for case, (n, n_parts, *_, in_order) in CASES.items():
        part_of = lanes_of(rng, n, n_parts, 1, in_order=in_order)[2]
        win = ckstats._pass_rows(part_of, n, n_parts, pad_bucket(n))
        assert (win < pad_bucket(n)) == in_order, case


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_word_form_equals_the_host_twin(case):
    n, n_parts, n_codes, all_null, empty_parts, in_order = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    lanes, valids, part_of = lanes_of(rng, n, n_parts, n_codes, all_null,
                                      empty_parts, in_order)
    host = ckstats.host_stats_block(lanes, valids, part_of, n_parts, n_codes)
    dev = ckstats.checkpoint_stats_block(lanes, valids, part_of, n_parts,
                                         n_codes)
    assert dev.dtype == host.dtype == np.int64
    assert dev.shape == host.shape == (4 * len(lanes) + 1, n_parts)
    assert np.array_equal(dev, host)
    for p in empty_parts:       # the identities, not zeros
        assert (dev[0:4, p] == ckstats.IDENT_MIN).all()
        assert (dev[4:8, p] == ckstats.IDENT_MAX).all()
        assert (dev[8:, p] == 0).all()


def test_every_row_of_a_part_with_one_code_counts_once():
    n = 300
    lanes = [np.arange(n), np.zeros(n, np.int64)]
    valids = [np.ones(n, bool), np.ones(n, bool)]
    part_of = (np.arange(n) % 3).astype(np.int32)
    block = ckstats.checkpoint_stats_block(lanes, valids, part_of, 3, 1)
    assert block[-1].tolist() == [1, 1, 1]
    assert block[0].tolist() == [0, 1, 2] and block[2].tolist() == [297, 298,
                                                                    299]


@pytest.mark.parametrize("part_size", [1100, 1024, 4999, 5000, 7000])
def test_the_writers_fixed_chunks_with_a_short_last_one(part_size):
    """`checkpointer._chunk_plan`'s parts: `part_size` rows each, the
    last what is left; the codes lane null in the whole of part 1."""
    n = 5000
    rng = np.random.default_rng(part_size)
    n_parts = -(-n // part_size)
    lanes, valids, _ = lanes_of(rng, n, 1, 1)
    part_of = (np.arange(n) // part_size).astype(np.int32)
    valids[-1] = part_of != 1
    host = ckstats.host_stats_block(lanes, valids, part_of, n_parts, 1)
    dev = ckstats.checkpoint_stats_block(lanes, valids, part_of, n_parts, 1)
    assert np.array_equal(dev, host)
    assert dev[-1].tolist() == [int(p != 1) for p in range(n_parts)]
    assert ckstats._pass_rows(part_of, n, n_parts, 8192) == (
        8192 if n_parts == 1 else pad_bucket(part_size))


def test_the_dispatch_ships_uint32_words_and_says_its_shape():
    obs.set_device_obs_mode("on")
    obs.reset_device_obs()
    try:
        n = 1500
        rng = np.random.default_rng(3)
        lanes, valids, part_of = lanes_of(rng, n, 2, 1)
        ckstats.checkpoint_stats_block(lanes, valids, part_of, 2, 1)
        [record] = [r for r in obs.get_dispatch_records()
                    if r["kernel"] == "stats.ckpt_block"]
    finally:
        obs.set_device_obs_mode(None)
        obs.reset_device_obs()
    n_pad = pad_bucket(n)
    # part ids in no order: a pass reads every row
    assert record["attrs"] == {"lanes": 4, "n_pad": n_pad, "p_pad": 8,
                               "win": n_pad}
    assert record["violations"] == []
    shipped = {lane["name"]: lane["nbytes"] for lane in record["lanes"]
               if lane["dir"] == "h2d"}
    # one bit a padded row and lane, in whole 32-bit words
    assert shipped == {"lane_vals": 4 * n_pad * 8,
                       "valid_words": 4 * n_pad // 8, "part_ids": n_pad * 4}


def test_the_read_of_the_block_is_a_span_of_its_own():
    """`stats.wait` holds the blocking read, so what `checkpoint.aggregate`
    spends waiting for the chip is told from its host half."""
    obs.set_trace_mode("on")
    obs.reset_trace_buffer()
    try:
        lanes, valids, part_of = lanes_of(np.random.default_rng(4), 70, 2, 1)
        ckstats.checkpoint_stats_block(lanes, valids, part_of, 2, 1)
        [wait] = [s for s in obs.get_finished_spans()
                  if s.name == "stats.wait"]
    finally:
        obs.set_trace_mode(None)
        obs.reset_trace_buffer()
    assert wait.to_dict()["attrs"] == {"kernel": "stats.ckpt_block",
                                       "rows": 70, "parts": 2}


def test_the_words_are_the_trees_own_bit_order():
    """Bit k of word j is row 32 j + k, as `ops/replay.py::_pack_bits`
    packs and `_unpack_bits_device` reads."""
    import jax.numpy as jnp

    from delta_tpu.ops.replay import _unpack_bits_device

    mask = np.random.default_rng(0).random(2048) > 0.5
    words = _pack_bits(mask)
    assert words.dtype == np.uint32 and len(words) == 64
    again = np.asarray(_unpack_bits_device(jnp.asarray(words))) != 0
    assert np.array_equal(again, mask)
