"""Device-idle time a batch that lies under a ``dx/*`` annotation other
than ``dx/pace`` in the event-time cell: host work the device waits out.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["host_serial_ms_per_batch"]
