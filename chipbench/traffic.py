"""The one traffic generator: a mix file's `draws` become, per
operation, a dict of parameters. A mix is data (`mixes/<mix>.json`);
the driver it names gives the parameters their meaning.

Every seed sees the same values in another order, so that the seed
changes the order of the work and not the amount: each draw fills one
`block` of operations at a time from a fixed set and the seed only
permutes it. Kinds of draw:

- `uniform`: the `block` points `(i + 0.5) / block` of [0, 1), permuted;
- `log_grid`: `block` points spaced evenly in the logarithm from `lo`
  to `hi`, permuted;
- `every`: each `period`-th operation of the block takes the next of
  `values` (permuted; used up once per block, so `block` is `period`
  times their number), every other operation takes `otherwise`.

`uniform` and `log_grid` may be held to the operations at which an
earlier draw `where` has the value `equals`: the points are then as many
as those operations, and every other operation takes `otherwise`. So a
kind of operation that is timed apart (a refresh) takes no point away
from the kind beside it.
"""

from __future__ import annotations

import zlib

import numpy as np


def _fill(draw: dict, block: int, rng, filled: dict) -> list:
    kind = draw["kind"]
    if kind in ("uniform", "log_grid"):
        at = [i for i in range(block) if "where" not in draw
              or filled[draw["where"]][i] == draw["equals"]]
        points = ((np.arange(len(at)) + 0.5) / len(at) if kind == "uniform"
                  else np.geomspace(draw["lo"], draw["hi"], len(at)))
        out = [draw.get("otherwise")] * block
        for i, value in zip(at, rng.permutation(points).tolist()):
            out[i] = value
        return out
    if kind == "every":
        period, values = int(draw["period"]), list(draw["values"])
        if period * len(values) != block:
            raise ValueError(
                f"draw of kind 'every': period {period} x {len(values)} "
                f"values is not the block of {block}")
        out = [draw["otherwise"]] * block
        out[period - 1::period] = rng.permutation(values).tolist()
        return out
    raise ValueError(f"unknown kind of draw {kind!r}")


def schedule(mix: dict, seed: int):
    """Endless iterator of per-operation parameter dicts."""
    block = int(mix.get("block", 1))
    draws = mix.get("draws", {})
    rngs = {name: np.random.default_rng([seed, zlib.crc32(name.encode())])
            for name in draws}
    while True:
        filled: dict = {}
        for name, draw in draws.items():
            filled[name] = _fill(draw, block, rngs[name], filled)
        for i in range(block):
            yield {name: values[i] for name, values in filled.items()}
