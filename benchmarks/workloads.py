"""The log generator that `BENCHMARK.json`'s `deltalog-500k` cites as
the form of its source (BASELINE.json configs[1]: commits of 100
actions, 20% removes, one long column `x` with stats).
`chip_smoke.py` writes its log with it; the benchmark itself runs a
fast copy, `chipbench/gen/deltalog.py`, and
`tests/test_benchmark_source_shape.py` holds the two to the same lines.
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np


def synth_delta_log(path: str, commits: int, files_per_commit: int,
                    remove_fraction: float = 0.2, seed: int = 0) -> None:
    """Write a synthetic `_delta_log` directly (no data files — replay
    only touches the log)."""
    rng = np.random.default_rng(seed)
    # compact separators, as Delta writers emit them: the device JSON
    # parse (ops/json_parse.py) matches `"key":` patterns and sends
    # anything else to the host scanner
    dumps = functools.partial(json.dumps, separators=(",", ":"))
    log = os.path.join(path, "_delta_log")
    os.makedirs(log, exist_ok=True)
    protocol = '{"protocol":{"minReaderVersion":1,"minWriterVersion":2}}'
    metadata = json.dumps({
        "metaData": {
            "id": "bench", "format": {"provider": "parquet", "options": {}},
            "schemaString": '{"type":"struct","fields":[{"name":"x","type":"long","nullable":true,"metadata":{}}]}',
            "partitionColumns": [], "configuration": {},
        }
    })
    alive: list = []
    fid = 0
    for v in range(commits):
        lines = []
        if v == 0:
            lines += [protocol, metadata]
        n_rm = int(files_per_commit * remove_fraction)
        if alive and n_rm:
            for _ in range(min(n_rm, len(alive))):
                p = alive.pop(rng.integers(0, len(alive)))
                lines.append(dumps({
                    "remove": {"path": p, "deletionTimestamp": v, "dataChange": True}
                }))
        for _ in range(files_per_commit - n_rm):
            p = f"part-{fid:010d}.parquet"
            fid += 1
            alive.append(p)
            stats = dumps({"numRecords": 1000,
                           "minValues": {"x": int(fid) * 1000},
                           "maxValues": {"x": int(fid + 1) * 1000},
                           "nullCount": {"x": 0}})
            lines.append(dumps({
                "add": {"path": p, "partitionValues": {}, "size": 1 << 20,
                        "modificationTime": v, "dataChange": True,
                        "stats": stats}
            }))
        with open(os.path.join(log, f"{v:020d}.json"), "w") as f:
            f.write("\n".join(lines) + "\n")
