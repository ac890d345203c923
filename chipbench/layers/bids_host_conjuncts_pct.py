"""Stats index: of the window's `plan.skip` spans, the share in which a
conjunct was left to the host: the Arrow ladder took one
(`skip_fallback_conjuncts` > 0), one could not be compared at all
(`uncompared` > 0), or the compiled conjuncts did not run on the chip
(`skip_route` other than `device`, or none: nothing compiled). 0 when
the event-time column is on the lanes; 100 on a program that reads a
timestamp's stats as text. None where no plan skipped."""

from chipbench import spans


def read(run):
    attrs = [s.get("attrs", {}) for s in spans.named(run.spans, "plan.skip")]
    if not attrs:
        return None
    on_host = sum(a.get("skip_fallback_conjuncts", 0) > 0
                  or a.get("uncompared", 0) > 0
                  or a.get("skip_route") != "device" for a in attrs)
    return 100.0 * on_host / len(attrs)
