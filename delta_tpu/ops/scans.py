"""Long 1-D inclusive scans in a form the TPU compiler handles quickly.

A flat `jnp.cumsum` / `lax.cummax` / `lax.associative_scan` over a
multi-million-element lane compiles for the v5e in tens of seconds to
minutes per op (the device JSON parse carries six of them and did not
finish compiling at its product window). The chunked form below — scan
rows of `_CHUNK`, scan the row totals, add the carried prefix back —
compiles in a second or two at any length and is bit-identical for
integer lanes (wrapping add and max are associative).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_CHUNK = 1024


def _chunked(x, scan, combine, identity):
    n = x.shape[0]
    if n <= _CHUNK or n % _CHUNK:
        return scan(x, 0)
    inner = scan(x.reshape(-1, _CHUNK), 1)
    totals = _chunked(inner[:, -1], scan, combine, identity)
    carry = jnp.concatenate(
        [jnp.full((1,), identity, x.dtype), totals[:-1]])
    return combine(inner, carry[:, None]).reshape(n)


def cumsum_1d(x):
    """Inclusive running sum of an integer lane (== `jnp.cumsum(x)`)."""
    return _chunked(x, lambda a, axis: jnp.cumsum(a, axis=axis),
                    jnp.add, 0)


def cummax_1d(x):
    """Inclusive running maximum of an integer lane
    (== `lax.cummax(x)`)."""
    return _chunked(x, lambda a, axis: lax.cummax(a, axis=axis),
                    jnp.maximum, jnp.iinfo(x.dtype).min)


def cummin_1d(x, reverse=False):
    """Inclusive running minimum of an integer lane
    (== `lax.cummin(x, reverse=reverse)`); reversed, each element holds
    the minimum of itself and everything after it. The reversed form
    flips the lane round a forward scan: `lax.cummin(reverse=True)`
    over the chunks compiles five times slower for the v5e."""
    if reverse:
        return cummin_1d(x[::-1])[::-1]
    return _chunked(x, lambda a, axis: lax.cummin(a, axis=axis),
                    jnp.minimum, jnp.iinfo(x.dtype).max)
