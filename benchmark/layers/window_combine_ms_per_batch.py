"""Device time a batch under ``dx.window.combine``: the partial aggregates
reduced over the live slots (slots x groups cells) and sorted by key for
the view. ``None`` where the program has no such scope (a raw-row ring)."""

from benchmark import window_roofline


def read(cell, run, m, trace):
    return window_roofline.scope_ms(run, window_roofline.COMBINE)
