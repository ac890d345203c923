"""`gen/deltalog.py`'s table with the source's next commits held in
memory for a writer to commit: the writer's side of upstream's
`delta.checkpointInterval` = 10.

`deltalog.generate` runs unchanged, with `pending_commits` as its
`staged_commits`: its loop alone knows the order in which the source's
generator would draw the next removes (a swap-remove over the list of
live ids, whose order is the whole history's), so the next commits are
its `StagedCommit`s and no second simulation. The files it writes for
them beside the table (21 KB each) are deleted again before the
manifest is returned: in this deployment a commit appears in
`_delta_log` because the program's `Transaction.commit()` put it there,
never by a rename.

`paths_of` and `stats_of` give `deltalog.path_of` / `deltalog.stats_of`
of many ids at once, as Arrow string arrays: what the check of a
2.4M-row checkpoint compares its `add.path` and `add.stats` with.
"""

from __future__ import annotations

import dataclasses
import shutil

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from chipbench.gen import deltalog


def paths_of(ids: np.ndarray) -> pa.Array:
    """`deltalog.path_of` of every id."""
    number = pc.utf8_lpad(pc.cast(pa.array(ids, pa.int64()), pa.string()),
                          10, "0")
    return pc.binary_join_element_wise("part-", number, ".parquet", "")


def stats_of(ids: np.ndarray) -> pa.Array:
    """`deltalog.stats_of` of every id."""
    ids = np.asarray(ids, np.int64)
    low = pc.cast(pa.array((ids + 1) * deltalog.X_STEP), pa.string())
    high = pc.cast(pa.array((ids + 2) * deltalog.X_STEP), pa.string())
    return pc.binary_join_element_wise(
        '{"numRecords":1000,"minValues":{"x":', low,
        '},"maxValues":{"x":', high, '},"nullCount":{"x":0}}', "")


@dataclasses.dataclass
class Manifest(deltalog.Manifest):
    """`staged` holds the pending commits; nothing lies in `staged_dir`."""

    checkpoint_interval: int = 0
    adds_per_commit: int = 0

    def land(self, k: int) -> None:
        raise RuntimeError("this table's commits are the writer's to make: "
                           "nothing is staged as a file")


def generate(root: str, params: dict, seed: int) -> Manifest:
    """`deltalog.generate`'s table, and in `staged` the `pending_commits`
    commits that the source's generator would write next. `params` as
    there, with `pending_commits` in the place of `staged_commits`."""
    per_commit = int(params["actions_per_commit"])
    n_add = per_commit - int(per_commit * float(params["remove_fraction"]))
    theirs = {k: v for k, v in params.items() if k != "pending_commits"}
    theirs["staged_commits"] = int(params["pending_commits"])
    made = deltalog.generate(root, theirs, seed)
    shutil.rmtree(made.staged_dir)
    return Manifest(**vars(made),
                    checkpoint_interval=int(params["checkpoint_interval"]),
                    adds_per_commit=n_add)
