"""``device_query_ms_per_batch`` of the four-chip cell: device time a batch
under the step's ``dx.project.*`` and ``dx.view.*`` scopes, mean over the
device planes (the stateful view is computed replicated: ROADMAP S6).
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_query_ms_per_batch"]
