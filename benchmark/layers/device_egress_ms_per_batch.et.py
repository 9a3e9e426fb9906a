"""Device time a batch under ``dx.compact.*`` and ``dx.counts`` plus every
operation outside the step program, in the event-time cell: the two
compactions (``OpenDoors``' 1 % and ``HeatAvg``'s 131,072 rows) are the
largest item of the device's busy time there after the fold.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_egress_ms_per_batch"]
