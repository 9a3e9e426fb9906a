"""Device time a batch under the step's ``dx.state.*`` scopes: the
statements that read or write a state table (the accumulator's UNION ALL
of the batch's rows and the table, and its GROUP BY). Read from the
capture by ``benchmark/xplane.py``; ``None`` where the program has no such
scope (an engine that runs them under ``dx.view.*``)."""

from benchmark import state_roofline


def read(cell, run, m, trace):
    return state_roofline.state_ms(cell, run)
