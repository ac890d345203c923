"""Entry points: median `scan.plan` over the operations that only plan
(`scan_plan_ms`' reading, under the resident cell's name): a plan over
6.0M files on the snapshot the resident route advanced."""

from chipbench.layers import scan_plan_ms


def read(run):
    return scan_plan_ms.read(run)
