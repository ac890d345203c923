"""Stats index: of the window's `plan.skip` spans, the share in which a
conjunct was left to the host: the Arrow ladder took one
(`skip_fallback_conjuncts` > 0), one could not be compared at all
(`uncompared` > 0), or the compiled conjuncts did not run on the chip
(`skip_route` other than `device`, or none: nothing compiled). 0 when
the money columns are on decimal lanes and the OR over a bucket's three
ranges is distributed; 100 on a program that leaves either to the
ladder. None where no plan skipped. `bids_host_conjuncts_pct`'s
reading, over this cell's plans."""

from chipbench.layers.bids_host_conjuncts_pct import read  # noqa: F401
