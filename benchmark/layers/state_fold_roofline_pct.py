"""The least time any exact accumulator needs for one batch
(``benchmark/state_roofline.py``: the batch's key and value columns read
once, the table's valid rows read and written once, at the chip's peak
HBM rate) over the device time of the ``dx.state.*`` scopes a batch, in
%. ``None`` where the program has no such scope or does not count the
table's rows (``State_Rows``)."""

from benchmark import state_roofline


def read(cell, run, m, trace):
    return state_roofline.roofline_pct(cell, run, m)
