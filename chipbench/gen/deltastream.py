"""A table that a streaming sink keeps: `gen/deltalog.py`'s log, byte
for byte but for the stats, in the layout a micro-batch writer leaves.

Each write task of a micro-batch holds a slice of the batch's rows, so
every file of a commit spans the batch's whole event-time interval:
the `adds_per_commit` adds of commit `v` all have `x` in
`[(v + 1) * width, (v + 2) * width]`, where `width` is what the
source's files of one commit span between them, `adds_per_commit *
deltalog.X_STEP`: 80,000 at 80 adds a commit, so `x` stays in the
source's magnitude and a commit stays about 21 KB. Events come in
order: the next commit's interval starts where this one ends, so
intervals of different commits meet only at their ends. Commits,
checkpoint and staged commits alike.

Everything but the stats is `deltalog`'s own code: this module runs a
private copy of it whose `stats_of` is the one below, so the two
generators cannot drift apart and `deltalog` itself is not touched.
"""

from __future__ import annotations

import dataclasses
import importlib.util

import numpy as np

from chipbench.gen import deltalog


def batch_width(adds_per_commit: int) -> int:
    """The event-time interval of one micro-batch, in units of `x`."""
    return adds_per_commit * deltalog.X_STEP


def stats_of(fid: int, adds_per_commit: int) -> str:
    width = batch_width(adds_per_commit)
    lo = (fid // adds_per_commit + 1) * width
    return ('{"numRecords":1000,"minValues":{"x":%d},"maxValues":{"x":%d},'
            '"nullCount":{"x":0}}' % (lo, lo + width))


@dataclasses.dataclass
class Manifest(deltalog.Manifest):
    adds_per_commit: int = 0

    def scan_expected(self, lo: int, hi: int) -> np.ndarray:
        """Ids of the live files whose `[min, max]` on `x` can hold a
        row with `lo <= x < hi`: max >= lo and min < hi, i.e. the files
        of the commits `v` with `(v + 2) * width >= lo` and
        `(v + 1) * width < hi`."""
        width, per = batch_width(self.adds_per_commit), self.adds_per_commit
        first = max(0, -(-lo // width) - 2) * per
        last = min(len(self.alive), max(0, -(-hi // width) - 1) * per)
        if last <= first:
            return np.empty(0, np.int64)
        return first + np.flatnonzero(self.alive[first:last])


def generate(root: str, params: dict, seed: int) -> Manifest:
    """`deltalog.generate` with this module's stats. `params` as there."""
    per_commit = int(params["actions_per_commit"])
    n_add = per_commit - int(per_commit * float(params["remove_fraction"]))
    spec = importlib.util.find_spec("chipbench.gen.deltalog")
    private = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(private)
    private.stats_of = lambda fid: stats_of(fid, n_add)
    made = private.generate(root, params, seed)
    return Manifest(**vars(made), adds_per_commit=n_add)
