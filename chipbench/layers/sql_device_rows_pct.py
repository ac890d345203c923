"""Routing gate: of the rows that the window's `sql` gate decisions
priced (joins, aggregations and sorts; `inputs.n_rows`), the share that
was sent to the device. None where the gate priced no operator."""

from chipbench.layers.sql_gate import operators


def read(run):
    mine = operators(run)
    rows = sum(r["inputs"]["n_rows"] for r in mine)
    if not rows:
        return None
    return 100.0 * sum(r["inputs"]["n_rows"] for r in mine
                       if r["chosen"] == "device") / rows
