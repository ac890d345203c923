"""Entry points: per operation, the time in the program's
`state.add_files_table` and `state.size_in_bytes` spans: reading the
reconstructed state out (the deferred stats decode, the filter to the
live files, the sum of their sizes), after `snapshot.load` is done."""

from chipbench import spans


def read(run):
    mine = spans.named(run.spans, "state.add_files_table",
                       "state.size_in_bytes")
    if not mine:
        return None
    return sum(s["duration_ns"] for s in mine) / 1e6 / len(run.ops)
