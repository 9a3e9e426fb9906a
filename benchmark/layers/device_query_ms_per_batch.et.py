"""Device time a batch under the step's ``dx.project.*`` and ``dx.view.*``
scopes in the event-time cell: the projection (six columns, the time
among them), the rule and ``HeatAvg``'s select list over the combined
groups.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_query_ms_per_batch"]
