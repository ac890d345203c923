"""Entry points: the median `txn.commit` span of the commits that lie
outside any operation: the nine between two checkpoints, made in the
driver's `prepare`, whose post-commit hooks write nothing. What a
commit costs where it does not checkpoint."""

from chipbench import spans


def read(run):
    inside = [(op["start_unix_ns"], op["end_unix_ns"]) for op in run.ops]
    return spans.median_ms([
        s for s in spans.named(run.spans, "txn.commit")
        if not any(lo <= s["start_unix_ns"] < hi for lo, hi in inside)])
