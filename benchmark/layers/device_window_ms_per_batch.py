"""Device time a batch under ``dx.ring`` and ``dx.window``: the ring update
and the window's view of it.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing (a commit before PR 25)."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_window_ms_per_batch"]
