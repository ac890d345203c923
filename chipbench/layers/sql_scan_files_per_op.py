"""Host pipeline: the data files a pass opens: per pass, the sum of
`files` over its `scan.read` spans (median over the window's passes).
What partition pruning through a join would lower first. None on a
program without the span or without the attribute."""

import statistics

from chipbench import spans


def read(run):
    totals = []
    for op in run.ops:
        if op["kind"] != "pass":
            continue
        files = [s.get("attrs", {}).get("files") for s in spans.named(
            spans.inside(run.spans, op["start_unix_ns"], op["end_unix_ns"]),
            "scan.read")]
        if files and None not in files:
            totals.append(sum(files))
    return statistics.median(totals) if totals else None
