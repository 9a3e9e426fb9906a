"""Device mesh + sharding layout for the flow step.

The reference scales by partitioning RDDs across Spark executors and
letting Spark's shuffle service move rows for GROUP BY/JOIN
(CommonProcessorFactory.scala:405-421; shuffle implicit in the
``spark.sql`` calls at :257,271). TPU-native equivalent: one
``jax.sharding.Mesh`` over the slice with a single ``data`` axis —

- micro-batch rows shard over ``data`` (the executor-partition analog);
- window ring buffers ``[slots, capacity]`` shard their *capacity* dim
  over ``data`` so each chip retains only its shard of window history
  (the sequence/context-parallel layout: long windows never materialize
  on one chip);
- reference/state tables replicate (they are small and join-broadcast,
  like Spark broadcast joins);
- aggregation outputs replicate — XLA GSPMD inserts the
  all-gather/reduce-scatter collectives over ICI that replace Spark's
  host shuffle.

The whole step stays ONE jitted program: GSPMD partitions it from these
in/out shardings, so sorts (group-by) lower to distributed sorts and
segment reductions lower to psum-style collectives without any
host-level communication code.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def make_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence] = None,
    axis_name: str = DATA_AXIS,
) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (all by
    default). Multi-host: pass ``jax.devices()`` of the whole slice."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Rows of a [capacity] column shard over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def ring_sharding(mesh: Mesh) -> NamedSharding:
    """Window ring cols are [slots, capacity]: shard capacity."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def packed_sharding(mesh: Mesh) -> NamedSharding:
    """A source's packed raw matrix is [columns + 1, capacity]: shard
    capacity, so a chip's block holds every column of its rows and a
    row of the matrix, sliced inside the step, is that chip's row
    shard of the column (no collective)."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def step_shardings(mesh: Mesh, rings=(), partials=(), packed=None):
    """(in_shardings, out_shardings) pytree prefixes for
    ``FlowProcessor``'s step signature:

    in:  (raw tables per source — rows shard, rings per windowed table —
          capacity dim shards, state, refdata, base_s, now_rel_ms,
          counter, delta_ms, aux string-op dictionary tables —
          replicated: every chip gathers locally, like a broadcast join
          side)
    out: (datasets, new_rings, new_state, counts_vec)

    The prefixes apply leaf-wise over the dict pytrees, so N sources and
    N rings inherit the same layout without per-flow sharding code.

    ``packed``: by source name, whether its raw table comes as the one
    packed matrix (``runtime/processor.py source_raw_form``), whose
    rows lie on axis 1, and not as a column a leaf; None where every
    source comes in columns.

    The window-state argument also carries, by view name, the per-slot
    partial aggregates of the windowed GROUP BYs the planner decomposed
    (``partials``; their leaves are [slots, groups], [groups] and
    [slots], replicated: a group bound is small beside a batch, and
    partitioned aggregation is ROADMAP S6's). A flow that has any names
    its entries: ``rings`` the tables that keep raw rows.
    """
    row = row_sharding(mesh)
    ring = ring_sharding(mesh)
    rep = replicated(mesh)
    if partials:
        ring = {**{t: ring for t in rings}, **{v: rep for v in partials}}
    raw = {
        s: packed_sharding(mesh) if p else row for s, p in packed.items()
    } if packed else row
    in_shardings = (raw, ring, rep, rep, rep, rep, rep, rep, rep)
    out_shardings = (rep, ring, rep, rep)
    return in_shardings, out_shardings


# ---------------------------------------------------------------------------
# Observed collective communication: what the SPMD partitioner actually
# put on the ICI.
#
# GSPMD inserts the collectives during compilation (the StableHLO the
# tracer produces is still logical), so the ground truth for "how many
# bytes does this program move over the interconnect per batch" is the
# compiled module's HLO text. `collective_summary` parses it into a
# typed per-op-kind byte census. Two consumers, one convention:
#
# - the runtime (`FlowProcessor` under a mesh) summarizes its own
#   compiled step and exports the census per batch as the
#   `Mesh_ICI_Bytes` / `Mesh_Reshard_Count` registry series — the real
#   observation the DX51x conformance ratios judge;
# - the DX7xx mesh analyzer (`analysis/meshcheck.py`) summarizes its
#   per-stage lowerings and asserts the closed-form model equals the
#   extraction exactly.
#
# Byte convention: `result_bytes` per collective = the full logical
# size of the op's result (chip-count-independent; the exactness
# contract's unit). Wire bytes apply the ring closed forms
# (`analysis/costmodel.py collective_wire_bytes`) per op kind.
# ---------------------------------------------------------------------------

# compiled-HLO scalar type -> bytes (everything this engine lowers is
# 32-bit except bool; wider types listed for robustness)
_HLO_DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# one HLO instruction: `[ROOT] %name = <shape> <op>(operands...`. The
# shape may be a tuple, and on the TPU backend carries tiling in its
# layout (`s32[64]{0:T(128)}`, `pred[64]{0:T(512)(128)(4,1)}`) and
# `/*index=5*/` markers inside long tuples — so it is matched lazily
# up to the op token and cleaned afterwards, not spelled out
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shape>.+?)\s+"
    r"(?P<op>all-reduce|all-gather|all-to-all|collective-permute"
    r"|reduce-scatter)(?P<phase>-start|-done)?\(",
    re.MULTILINE,
)
_LAYOUT_RE = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


@dataclass
class MeshCollectives:
    """Census of the collective ops in one compiled SPMD program."""

    # op kind -> (instruction count, total result bytes)
    ops: Dict[str, Tuple[int, int]] = field(default_factory=dict)

    @property
    def op_count(self) -> int:
        return sum(c for c, _b in self.ops.values())

    @property
    def result_bytes(self) -> int:
        return sum(b for _c, b in self.ops.values())

    def wire_bytes(self, chips: int) -> float:
        """Total slice-wide ICI bytes per execution under the ring
        closed forms (the Mesh_ICI_Bytes unit)."""
        from ..analysis.costmodel import collective_wire_bytes

        return sum(
            collective_wire_bytes(op, b, chips)
            for op, (_c, b) in self.ops.items()
        )

    def to_dict(self) -> dict:
        return {
            op: {"count": c, "resultBytes": b}
            for op, (c, b) in sorted(self.ops.items())
        }


def collective_summary(compiled_hlo_text: str) -> MeshCollectives:
    """Parse a compiled module's HLO text into a collective census.

    Counts every all-reduce / all-gather / all-to-all /
    collective-permute / reduce-scatter instruction and sums each
    instruction's result shape bytes (every element of a tuple result:
    XLA combines same-typed collectives into one tuple-shaped op).
    Async pairs count once, on the ``-done``: its shape is the result
    alone, where a ``-start`` returns operands, results and context
    scalars together."""
    ops: Dict[str, Tuple[int, int]] = {}
    for m in _COLLECTIVE_RE.finditer(compiled_hlo_text):
        if m.group("phase") == "-start":
            continue
        total = 0
        for sm in _SHAPE_RE.finditer(_LAYOUT_RE.sub("", m.group("shape"))):
            dt, dims = sm.group(1), sm.group(2)
            n_el = 1
            for d in dims.split(","):
                if d:
                    n_el *= int(d)
            total += n_el * _HLO_DTYPE_BYTES.get(dt, 4)
        op = m.group("op")
        c, b = ops.get(op, (0, 0))
        ops[op] = (c + 1, b + total)
    return MeshCollectives(ops)


def summarize_compiled(compiled) -> MeshCollectives:
    """Census of a ``jax`` compiled executable (``lowered.compile()``
    result)."""
    return collective_summary(compiled.as_text())
