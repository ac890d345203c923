"""Stats index: of the atoms the window's `plan.skip` spans launched,
the share on decimal lanes (`decimal_atoms` / `atoms`): 83.7% by the mix
(71 plans of 28 atoms, 24 of them on money columns, and 24 plans of 2
on the sold date). None on a program whose `plan.skip` does not count
its atoms by kind, or where no plan compiled any."""

from chipbench import spans


def read(run):
    attrs = [s.get("attrs", {}) for s in spans.named(run.spans, "plan.skip")]
    counted = [a for a in attrs if "atoms" in a and "decimal_atoms" in a]
    atoms = sum(a["atoms"] for a in counted)
    if not atoms:
        return None
    return 100.0 * sum(a["decimal_atoms"] for a in counted) / atoms
