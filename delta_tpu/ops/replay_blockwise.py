"""Blockwise (>HBM) replay: bounded-memory snapshot reconstruction.

SURVEY §5.7's scale path: a state too large for one device sort streams
through the kernel in blocks. The trick that keeps the merge bounded is
running the blocks in REVERSE chronological order with a persistent
device bitset of already-seen keys — the kernel-descending formulation
of replay (reference `ActiveAddFilesIterator.java:146`: first
occurrence wins when walking newest-to-oldest):

    for block in blocks[newest..oldest]:
        local_last = last occurrence of each key within the block
        winner     = local_last & ~seen[key]
        seen      |= block's keys

Device residency per step: one block's key lane + add bits + the seen
bitset (n_uniq / 8 bytes — 100M logical files = 12.5MB), regardless of
total row count. The bitset is donated between steps so XLA updates it
in place; winner masks come home bit-packed per block.

The output equals `replay_select` exactly (same winner-per-key
semantics, padding handling, live/tombstone split on the host).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from delta_tpu import obs
from delta_tpu.ops.replay import (
    _PAD_KEY,
    _unpack_bits,
    chrono_ok,
    combine_key_lanes,
    pad_bucket,
)

DEFAULT_BLOCK_ROWS = 1 << 22  # 4M rows/block: ~24MB device footprint


def _block_kernel_impl(seen_words, keys, n_real, m: int):
    """One reverse-order block step.

    seen_words u32[W]: bitset over key space (donated, updated in place).
    keys u32[m]: block's combined key lane (pad = sentinel); n_real i32.
    Returns (winner_words u32[m/32], updated seen_words) — the winner
    bits split into live/tombstone on the host, where is_add lives."""
    iota = jnp.arange(m, dtype=jnp.uint32)
    # sort by (key, pos): within a key run positions ascend, so the run's
    # LAST element is the block-locally-newest action for that key
    s_key, s_pos = lax.sort((keys, iota), num_keys=2)
    is_last = jnp.concatenate(
        [s_key[:-1] != s_key[1:], jnp.ones((1,), bool)])
    local_last = jnp.zeros((m,), bool).at[s_pos].set(is_last)

    valid = iota < jnp.uint32(n_real)
    key_clip = jnp.where(valid, keys, 0)
    seen_bit = (seen_words[key_clip >> 5] >> (key_clip & 31)) & jnp.uint32(1)
    winner = local_last & valid & (seen_bit == 0)

    # OR this block's keys into the bitset. Bits sharing a word must
    # combine, so: one bit per FIRST occurrence of each key (distinct
    # powers of two within a word), segment-sum by word (= exact OR for
    # distinct powers), scatter the per-word OR. Sorted keys make both
    # groupings contiguous. Sentinel pads contribute zero bits and
    # scatter a no-op value into word 0.
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), s_key[1:] != s_key[:-1]])
    real = s_key != jnp.uint32(0xFFFFFFFF)
    uniq_bit = jnp.where(is_first & real,
                         jnp.uint32(1) << (s_key & 31), jnp.uint32(0))
    # pads and unused segment slots scatter to an out-of-bounds sentinel
    # and DROP — a default of word 0 would race a real word-0 segment's
    # update with stale values (duplicate-index scatter is undefined)
    oob = jnp.uint32(seen_words.shape[0])
    word = jnp.where(real, s_key >> 5, oob)
    word_boundary = jnp.concatenate(
        [jnp.ones((1,), bool), word[1:] != word[:-1]])
    seg = jnp.cumsum(word_boundary.astype(jnp.int32)) - 1
    or_per_seg = jax.ops.segment_sum(uniq_bit, seg, num_segments=m)
    seg_word = jnp.full((m,), oob).at[seg].set(word)
    gathered = seen_words.at[seg_word].get(mode="clip")
    seen_words = seen_words.at[seg_word].set(
        gathered | or_per_seg.astype(jnp.uint32), mode="drop")

    bit_pos = jnp.arange(32, dtype=jnp.uint32)
    weights = jnp.uint32(1) << bit_pos
    winner_words = (winner.reshape(-1, 32).astype(jnp.uint32)
                    * weights).sum(axis=1, dtype=jnp.uint32)
    return winner_words, seen_words


_block_kernel = functools.partial(jax.jit, static_argnames=("m",),
                                  donate_argnums=(0,))(
    obs.program("replay.blockwise")(_block_kernel_impl))


def replay_select_blockwise(
    key_lanes,
    version: np.ndarray,
    order: np.ndarray,
    is_add: np.ndarray,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    device=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Bounded-memory replay over arbitrarily many rows; returns
    (live_mask, tombstone_mask) identical to `replay_select`."""
    n = int(version.shape[0])
    if n == 0:
        z = np.zeros((0,), dtype=bool)
        return z, z

    perm = None
    if not chrono_ok(np.asarray(version), np.asarray(order)):
        perm = np.lexsort((order, version))
        key_lanes = [np.asarray(k)[perm] for k in key_lanes]
        is_add = np.asarray(is_add)[perm]

    key = combine_key_lanes([np.asarray(k) for k in key_lanes])
    if key is None:
        wide = (np.asarray(key_lanes[0]).astype(np.uint64) << np.uint64(32)
                | np.asarray(key_lanes[1]).astype(np.uint64))
        _, key = np.unique(wide, return_inverse=True)
        key = key.astype(np.uint32)
    is_add = np.asarray(is_add, dtype=bool)

    n_uniq = int(key.max()) + 1 if n else 0
    m = pad_bucket(min(block_rows, n))
    n_words = pad_bucket(-(-max(n_uniq, 1) // 32), min_bucket=1024)
    seen = jnp.zeros((n_words,), jnp.uint32)
    if device is not None:
        # one-time seed upload of the persistent bitset (donated and
        # updated in place by every block step after)
        with obs.device_dispatch("replay.blockwise_seed",
                                 key=(n_words,)) as dd:
            seen = dd.h2d("seen", jax.device_put(seen, device))

    winner = np.zeros(n, dtype=bool)
    starts = list(range(0, n, m))
    for s in reversed(starts):
        e = min(s + m, n)
        blk = np.full(m, _PAD_KEY, np.uint32)
        blk[:e - s] = key[s:e]
        ops = (blk, np.int32(e - s))
        with obs.device_dispatch("replay.blockwise", key=(m, n_words),
                                 gate="replay", route="single") as dd:
            dd.h2d("block", int(blk.nbytes))
            if device is not None:
                ops = tuple(jax.device_put(o, device) for o in ops)
            winner_words, seen = _block_kernel(seen, *ops, m=m)
            winner[s:e] = _unpack_bits(
                dd.d2h("winner_words", np.asarray(winner_words)), m)[:e - s]

    live = winner & is_add
    tomb = winner & ~is_add
    if perm is not None:
        inv_live = np.zeros(n, dtype=bool)
        inv_tomb = np.zeros(n, dtype=bool)
        inv_live[perm] = live
        inv_tomb[perm] = tomb
        live, tomb = inv_live, inv_tomb
    return live, tomb
