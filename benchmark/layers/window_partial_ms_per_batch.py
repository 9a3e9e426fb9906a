"""Device time a batch under ``dx.window.partial``: the batch's rows
folded into its slot's per-group partial aggregates (one sort over the
batch, a merge with the key directory, one scatter of ``groups`` updates).
``None`` where the program has no such scope (a raw-row ring)."""

from benchmark import window_roofline


def read(cell, run, m, trace):
    return window_roofline.scope_ms(run, window_roofline.PARTIAL)
