"""Tests only: a per-layer metric that is one new file. Mean of the
`surviving` attribute of the program's `scan.plan` spans."""

from chipbench import spans


def read(run):
    plans = spans.named(run.spans, "scan.plan")
    if not plans:
        return None
    return sum(s["attrs"]["surviving"] for s in plans) / len(plans)
