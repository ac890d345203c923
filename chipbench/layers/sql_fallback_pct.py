"""Routing gate: of the SQL operators the gate sent to the device in
the window, the share that fell back to the host in mid-flight
(`gate_fell_back`: an input the kernel does not take, a device error).
0 expected. None where none was sent there."""

from chipbench.layers.sql_gate import operators


def read(run):
    sent = [r for r in operators(run) if r["chosen"] == "device"]
    if not sent:
        return None
    return 100.0 * sum(r.get("fell_back_to") is not None
                       for r in sent) / len(sent)
