"""``step_roofline_pct`` of the four-chip cell: the least time the cell's
chips together need for one batch's work (``roofline.least_time`` of the
flow's shapes, divided by the chips: the work divides over them, what the
exchange costs is not in it) over the busy time a chip a batch in the
trace, in %. A step that computes the view on every chip (ROADMAP S6)
reads a quarter of the one-chip share; one that divides it cannot pass
100 %."""

from benchmark import readers


def read(cell, run, m, trace):
    one_chip = readers.read_declared({"from": "roofline"}, cell, run, m, trace)
    return None if one_chip is None else one_chip / cell["chips"]
