"""Device time a batch under ``dx.window.combine`` in the event-time
cell: the partial aggregates reduced over the slots of the seconds the
lagged window covers (slots x groups cells) and sorted by key for the
view. ``None`` where the program has no such scope."""

from benchmark import window_roofline


def read(cell, run, m, trace):
    return window_roofline.scope_ms(run, window_roofline.COMBINE)
