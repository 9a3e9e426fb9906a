"""The least time any exact implementation of an event-time window state
needs for one batch (``benchmark/window_roofline_eventtime.py``: the batch
read, the slots the batch's rows fell into read and written, the leaving
slot and one running state read, the output written, at the chip's peak
HBM rate) over the device time of ``dx.window.partial`` and
``dx.window.combine`` a batch, in %. ``None`` where the program has
neither scope or does not count the slots a fold wrote."""

from benchmark import window_roofline_eventtime


def read(cell, run, m, trace):
    return window_roofline_eventtime.roofline_pct(cell, run, m)
