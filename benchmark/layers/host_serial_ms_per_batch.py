"""Device-idle time a batch that lies under a ``dx/*`` annotation other
than ``dx/pace``: host work the device waits out, which a pipelined
loop could hide.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing (a commit before PR 25)."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["host_serial_ms_per_batch"]
