"""Device time a batch under the step's ``dx.project.*`` and ``dx.view.*``
scopes: the flow's own queries (projection, rules, GROUP BY).
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing (a commit before PR 25)."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_query_ms_per_batch"]
