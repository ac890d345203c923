"""Pallas kernels vs their jnp/numpy references (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np

from delta_tpu.ops.pallas_kernels import (
    interleave_bits_auto,
    interleave_bits_tiled,
)
from delta_tpu.ops.zorder import interleave_bits


def test_interleave_tiled_matches_jnp():
    rng = np.random.default_rng(0)
    n = 2048
    cols = [rng.integers(0, 2**32, n, dtype=np.uint32) for _ in range(3)]
    ref = np.asarray(interleave_bits([jnp.asarray(c) for c in cols]))
    got = np.asarray(interleave_bits_tiled(jnp.stack([jnp.asarray(c) for c in cols])))
    np.testing.assert_array_equal(got, ref)


def test_interleave_auto_fallback_on_ragged():
    rng = np.random.default_rng(1)
    n = 1000  # not a tile multiple -> fallback path
    cols = [rng.integers(0, 2**32, n, dtype=np.uint32) for _ in range(2)]
    ref = np.asarray(interleave_bits([jnp.asarray(c) for c in cols]))
    got = np.asarray(interleave_bits_auto([jnp.asarray(c) for c in cols]))
    np.testing.assert_array_equal(got, ref)
