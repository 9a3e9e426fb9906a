"""Device time a batch under ``dx.window.partial`` in the event-time cell:
the batch sorted by (key, second), its keys merged with the directory,
each (second, key) total added into its second's slot (the 12 slot rows
a batch can reach read, reset where a new second takes the slot over, and
written back). Putting the batch on its clock's grid (``event_rows``) is
not in it: that runs once a table under ``dx.window``, which
``device_window_ms_per_batch.et`` reads with both window scopes by
prefix. ``None`` where the program has no such scope."""

from benchmark import window_roofline


def read(cell, run, m, trace):
    return window_roofline.scope_ms(run, window_roofline.PARTIAL)
