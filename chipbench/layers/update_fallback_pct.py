"""Log segment: of the window's `table.update` spans, the share whose
`outcome` is `full_load`: the held state could not be advanced and the
table was loaded anew. 10 by construction while every crossing falls
back (one landing in ten brings a checkpoint). None on a program whose
span does not say how the update ended."""

from chipbench import spans


def read(run):
    outcomes = [s.get("attrs", {}).get("outcome")
                for s in spans.named(run.spans, "table.update")]
    if not outcomes or None in outcomes:
        return None
    return 100.0 * outcomes.count("full_load") / len(outcomes)
