"""Device time a batch in the collectives the partitioner placed in a mesh's
step (``all-gather``, ``all-reduce``, ``all-to-all``, ``collective-permute``,
``reduce-scatter`` and their ``-start`` / ``-done`` forms, told apart by
operation name): the union of those operations on each plane's ``XLA Ops``
line inside the window, mean over the device planes. A part of
``device_busy_ms_per_batch.mesh4``, exposed by construction.
Read from the capture by ``benchmark/mesh.py``."""

from benchmark import mesh


def read(cell, run, m, trace):
    return mesh.numbers(run)["mesh_collective_ms_per_batch"]
