"""Device time a batch under ``dx.ring`` and ``dx.window*`` in the
event-time cell: the batch put on its clock's grid (``event_rows``, under
``dx.window``), the fold (``dx.window.partial``) and the combine
(``dx.window.combine``), by prefix.
Read from the capture by ``benchmark/xplane.py``; ``None`` where the
program names nothing."""

from benchmark import xplane


def read(cell, run, m, trace):
    return xplane.stages(run)["device_window_ms_per_batch"]
