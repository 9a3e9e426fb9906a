"""Device time a batch under ``dx.compact.*`` and ``dx.counts`` plus every
operation outside the step program (the sized-transfer helpers, the
conversions of the step's scalar arguments).
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing (a commit before PR 25)."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_egress_ms_per_batch"]
