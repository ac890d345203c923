"""Routing gate: the share of the window's gate decisions
(`obs.get_gate_records()`) that chose another route than `host`."""


def read(run):
    if not run.gates:
        return None
    return 100.0 * sum(r["chosen"] != "host" for r in run.gates) / len(
        run.gates)
