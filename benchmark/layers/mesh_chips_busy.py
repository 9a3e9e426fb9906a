"""Device planes of the capture on which the step program ran inside the
window: the cell's four, or a mesh that silently stepped on fewer.
Read from the capture by ``benchmark/mesh.py``."""

from benchmark import mesh


def read(cell, run, m, trace):
    return mesh.numbers(run)["mesh_chips_busy"]
