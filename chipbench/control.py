"""The control: the system with one guarantee of its configuration
broken, put in the system's place. A run on a control has to come out
`correct: false`; the benchmark's own runs never run one.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 --seconds 5

exits 0 when every seed came out not correct.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench.system import DeltaTpu


class StaleReader(DeltaTpu):
    """Breaks 'the snapshot equals the replay of every commit' and
    'after update() the snapshot is at the newest landed version': a
    load stops one commit short of the newest, a refresh hands out the
    snapshot of the refresh before it (for the cells of
    `tests/chipbench/extra/` that refresh; PERF.md, Open questions)."""

    def load(self, path):
        table, snapshot = super().load(path)
        self._held = table.snapshot_at(snapshot.version - 1)
        return table, self._held

    def refresh(self, table):
        fresh = super().refresh(table)
        fresh.state     # advance now, as the plan on it would have
        stale, self._held = self._held, fresh
        return stale


def main(argv) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(prog="python3 -m chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    passed_as_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(args.workload, seed, args.seconds, False,
                                  time.perf_counter(),
                                  system=StaleReader())
        print(json.dumps({"control": "stale", "seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"]}), flush=True)
        passed_as_correct += result["correct"]
    return 1 if passed_as_correct else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
