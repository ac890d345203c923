"""`gen/deltastream.py`'s table with the writer's half of upstream's
defaults kept up while commits land: `delta.checkpointInterval` = 10,
so the committing writer writes a classic single-file checkpoint after
every commit whose version is a multiple of the interval, and then
`_last_checkpoint` (PROTOCOL.md, "Checkpoints", "Last Checkpoint
File").

`deltastream.generate` runs unchanged. From its manifest's `alive` and
`staged` this module then writes, for the first `staged_checkpoints`
staged versions that are multiples of `checkpoint_interval`,
`<version>.checkpoint.parquet` into the staged directory: schema, row
order (`_writer_order`), writer settings and stats strings as the base
checkpoint's, so that it equals `_checkpoint_table` of what is live at
that version. `_checkpoint_table` formats every path and stats string
in a Python list (12 s at 2.4M files); here the two string columns are
built once for every file id and `take`n per checkpoint.

`Manifest.land` moves a commit, after it its checkpoint if it has one,
and then rewrites `_last_checkpoint`: each of the three appears by
rename, in that order, and all are there when `land` returns. Nothing
is cleaned up, so older checkpoints and commits stay.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

from chipbench.gen import deltalog, deltastream


def checkpoint_name(version: int) -> str:
    return f"{version:020d}.checkpoint.parquet"


class CheckpointColumns:
    """The `add` rows of a checkpoint of this table, for any live set:
    `path` and `stats` of every file id, made once."""

    def __init__(self, n_files: int, adds_per_commit: int):
        self.per = adds_per_commit
        number = pc.utf8_lpad(pc.cast(pa.array(np.arange(n_files)),
                                      pa.string()), 10, "0")
        self.paths = pc.binary_join_element_wise(
            "part-", number, ".parquet", "")
        self.stats = pa.array(      # one string a commit
            [deltastream.stats_of(v * adds_per_commit, adds_per_commit)
             for v in range(-(-n_files // adds_per_commit))], pa.string())
        # the protocol and metaData rows, and the schema, are deltalog's
        self.head = deltalog._checkpoint_table(np.empty(0, np.int64),
                                               adds_per_commit)

    def table(self, live: np.ndarray) -> pa.Table:
        """Equal to `_checkpoint_table(live, adds_per_commit)` of the
        `deltalog` that `deltastream.generate` runs."""
        n = len(live)
        commits = live // self.per
        add_type = self.head.schema.field("add").type
        no_partition = pa.MapArray.from_arrays(
            pa.array(np.zeros(n + 1, np.int32)),
            pa.array([], pa.string()), pa.array([], pa.string()))
        add = pa.StructArray.from_arrays(
            [self.paths.take(pa.array(live)), no_partition,
             pa.array(np.full(n, deltalog.FILE_SIZE, np.int64)),
             pa.array(commits), pa.array(np.ones(n, bool)),
             self.stats.take(pa.array(commits))],
            fields=list(add_type))
        columns = {}
        for name in ("protocol", "metaData"):
            head = self.head.column(name).combine_chunks()
            columns[name] = pa.concat_arrays([head, pa.nulls(n, head.type)])
        columns["add"] = pa.concat_arrays([pa.nulls(2, add_type), add])
        return pa.table(columns)


@dataclasses.dataclass
class StagedCheckpoint:
    version: int
    num_files: int
    size_in_bytes: int      # of the Parquet file


@dataclasses.dataclass
class Manifest(deltastream.Manifest):
    checkpoint_interval: int = 0
    staged_checkpoints: list = dataclasses.field(default_factory=list)

    def land(self, k: int) -> None:
        """Land the next `k` staged commits one by one, as their writer
        does: the commit, then, after a commit whose version is a
        multiple of the interval, its checkpoint and `_last_checkpoint`.
        `checkpoint_version` tells which checkpoint landed last."""
        log = os.path.join(self.table_path, "_delta_log")
        for c in self.staged[:k]:
            due = c.version % self.checkpoint_interval == 0
            if due and not (self.staged_checkpoints and
                            self.staged_checkpoints[0].version == c.version):
                raise RuntimeError(
                    "the staged checkpoints are used up: the mix needs more "
                    "`staged_checkpoints` for a system this fast")
            super().land(1)
            if not due:
                continue
            ckpt = self.staged_checkpoints.pop(0)
            name = checkpoint_name(ckpt.version)
            os.replace(os.path.join(self.staged_dir, name),
                       os.path.join(log, name))
            hint = os.path.join(self.staged_dir, "_last_checkpoint")
            with open(hint, "w") as f:
                f.write(json.dumps(
                    {"version": ckpt.version, "size": ckpt.num_files + 2,
                     "sizeInBytes": ckpt.size_in_bytes,
                     "numOfAddFiles": ckpt.num_files},
                    separators=(",", ":")))
            os.replace(hint, os.path.join(log, "_last_checkpoint"))
            self.checkpoint_version = ckpt.version


def generate(root: str, params: dict, seed: int) -> Manifest:
    """`deltastream.generate`, then the staged checkpoints. `params` as
    there, with `staged_checkpoints`: how many to write."""
    made = deltastream.generate(root, params, seed)
    interval = int(params["checkpoint_interval"])
    wanted = int(params["staged_checkpoints"])
    t0 = time.perf_counter()
    columns = CheckpointColumns(len(made.alive), made.adds_per_commit)
    alive = made.alive.copy()
    written = []
    for c in made.staged:
        if len(written) == wanted:
            break
        alive[c.removed] = False
        alive[c.add_lo:c.add_hi] = True
        if c.version % interval:
            continue
        live = deltalog._writer_order(np.flatnonzero(alive))
        name = os.path.join(made.staged_dir, checkpoint_name(c.version))
        pq.write_table(columns.table(live), name, compression="snappy",
                       use_dictionary=True, data_page_version="1.0")
        written.append(StagedCheckpoint(c.version, len(live),
                                        os.path.getsize(name)))
    print(f"staged checkpoints: {len(written)} written in "
          f"{time.perf_counter() - t0:.2f} s of set-up, "
          f"{sum(c.size_in_bytes for c in written)} bytes under "
          f"{made.staged_dir}, versions "
          f"{[c.version for c in written]}", flush=True)
    return Manifest(**vars(made), checkpoint_interval=interval,
                    staged_checkpoints=written)
