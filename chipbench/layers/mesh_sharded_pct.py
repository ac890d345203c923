"""Routing gate: of the window's decisions of the `replay` gate
(`obs.get_gate_records()`), the share that chose the mesh (`sharded`).
100 where every load's replay ran across the chips; a load that fell to
one chip or to the host lowers it. None where no replay was routed."""


def read(run):
    mine = [r for r in run.gates if r["gate"] == "replay"]
    if not mine:
        return None
    return 100.0 * sum(r["chosen"] == "sharded" for r in mine) / len(mine)
