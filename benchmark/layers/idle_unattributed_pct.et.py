"""Share of the device's idle time under no ``dx/*`` annotation in the
event-time cell: the health of the tracing itself.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["idle_unattributed_pct"]
