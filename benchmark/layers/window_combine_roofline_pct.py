"""The least time any exact implementation of the window state needs for
one batch (``benchmark/window_roofline.py``: the batch read, one slot
written, the leaving slot and one running state read, the output written,
at the chip's peak HBM rate) over the device time of ``dx.window.partial``
and ``dx.window.combine`` a batch, in %. ``None`` where the program has
neither scope."""

from benchmark import window_roofline


def read(cell, run, m, trace):
    return window_roofline.roofline_pct(cell, run, m)
