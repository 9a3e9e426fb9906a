"""The engine core: build and run a flow's per-batch processing step.

reference: datax-host processor/CommonProcessorFactory.scala:42-660 —
init loads schema/projections/transform/refdata/UDFs, then per batch:
``project()`` raw->typed projection (:90-103), ``route()`` SQL pipeline +
time windows + state tables + outputs (:131-328), ``processDataset()``
orchestration + metrics (:333-399).

TPU-native shape: everything device-side — projection, ring-buffer
window update, the whole SQL pipeline, state-table production and count
metrics — compiles into ONE jitted step function. The host loop only
encodes ingest, invokes the step, materializes output datasets, and runs
sinks/checkpoints.

Multi-source flows (reference: the ``input.sources`` map in
flattenerConfig.json and the per-source grouping in
input/BlobPointerInput.scala:30-160): ``datax.job.input.sources.<name>.*``
declares N named sources, each with its own schema and projection into
its own named table; time windows may target any of those tables, so a
flow can join two independent streams across sliding windows — all still
inside the single jitted step.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..compile.pipeline import Pipeline, PipelineCompiler, parse_state_table_schema
from ..compile.planner import (
    WINDOW_PARTIALS_PREFIX,
    EventClock,
    PlannerConfig,
    TableData,
    ViewSchema,
    WindowInput,
)
from ..compile.sqlparser import parse_select
from ..compile.transform_parser import TransformParser
from ..constants import ColumnName, DatasetName
from ..core.config import EngineException, SettingDictionary, SettingNamespace
from ..obs.tracing import current_trace as _current_trace
from ..obs.tracing import span as _trace_span
from ..core.schema import ColType, Schema, StringDictionary
from .materialize import ColumnBatch
from .statetable import StateTable
from .timewindow import (
    EventRows,
    WindowBuffers,
    WindowPartials,
    event_rows,
    make_buffers,
    num_slots,
    update_buffers,
    window_table,
)

logger = logging.getLogger(__name__)

# default in-flight window of the pipelined hosts (conf
# datax.job.process.pipeline.depth): decode/dispatch of batch N+k
# proceeds while up to `depth` earlier batches compute and their D2H
# copies land; finish/commit stays strictly FIFO
DEFAULT_PIPELINE_DEPTH = 2

# donation contract of the fused step jit: the window rings (positional
# arg 1) are donated so XLA updates them in place; nothing else is.
# The compile-surface analyzer (analysis/compilecheck.py) records this
# pattern per manifest entry — DX602 fires when a shipped manifest
# disagrees with it.
STEP_DONATE_ARGNUMS = (1,)

_CTYPE_TO_PLAN = {
    ColType.LONG: "long",
    ColType.DOUBLE: "double",
    ColType.BOOLEAN: "boolean",
    ColType.STRING: "string",
    ColType.TIMESTAMP: "timestamp",
}


def schema_to_view(schema: Schema) -> ViewSchema:
    return ViewSchema({c.name: _CTYPE_TO_PLAN[c.ctype] for c in schema.columns})


def default_projection(schema: Schema, timestamp_column: Optional[str]) -> str:
    """The HomeAutomation normalization snippet shape
    (gui.input.properties.normalizationSnippet) used when a source
    declares no projection of its own."""
    lines = ["Raw.*"]
    if timestamp_column and not schema.has(timestamp_column):
        lines.insert(0, f"current_timestamp() AS {timestamp_column}")
    return "\n".join(lines)


def projection_select(step_text: str, from_table: str):
    """One projection step (selectExpr lines) -> parsed Select
    (handler/ProjectionHandler.scala semantics)."""
    items = [
        ln.strip()
        for ln in step_text.replace("\r", "").split("\n")
        if ln.strip() and not ln.strip().startswith("--")
    ]
    return parse_select("SELECT " + ", ".join(items) + f" FROM {from_table}")


def batch_constant_time(steps: List[str], ts_col: Optional[str]) -> bool:
    """Is the projected timestamp column the ``current_timestamp()``
    projection? Every row of a batch then carries the batch's one time:
    the table's windows are processing-time windows. A payload time
    column (or anything computed from one) makes them event-time windows
    (``runtime/timewindow.py`` has both rules)."""
    from ..compile.sqlparser import Col, Func, Star

    col = ts_col
    for i in reversed(range(len(steps))):
        sel = projection_select(steps[i], "Raw")
        found = None
        for item in sel.items:
            named = item.alias or (
                item.expr.parts[-1] if isinstance(item.expr, Col) else None
            )
            if named == col:
                found = item.expr
        if isinstance(found, Func) and found.name == "CURRENT_TIMESTAMP" \
                and not found.args:
            return True
        if i > 0 and isinstance(found, Col):
            col = found.parts[-1]  # renamed from the step before
        elif i > 0 and found is None and any(
            isinstance(it.expr, Star) for it in sel.items
        ):
            pass  # carried through by a star
        else:
            return False
    return False


def event_time_table(
    projections: Dict[str, List[List[str]]], table: str,
    ts_col: Optional[str],
) -> bool:
    """Do the windows over ``table`` go by a time its rows bring (some
    source's projection of the timestamp column is not the
    ``current_timestamp()`` one)? ``projections`` as ``window_inputs``
    takes them."""
    return not all(
        batch_constant_time(steps, ts_col)
        for steps in projections.get(table, [[]])
    )


def window_inputs(
    windows: Dict[str, Tuple[str, float]],
    table_slots: Dict[str, int],
    projections: Dict[str, List[List[str]]],
    ts_col: Optional[str],
    handoff_by_key: bool = False,
    interval_s: float = 1.0,
    watermark_s: float = 0.0,
) -> Dict[str, WindowInput]:
    """What the planner is told of every TIMEWINDOW table (window name ->
    (table, seconds)); ``projections``: per projected table, the
    projection steps of each source that feeds it; ``handoff_by_key``:
    the runtime ships window state to a snapshot mirror by key partition
    (the rescale handoff re-packs rows); the batch interval and
    ``process.watermark`` are the grid of an event-time window. The ONE
    definition the runtime and the device-plan analyzer share, so both
    see the planner make the same choice of window state."""
    uniform = {
        table: not event_time_table(projections, table, ts_col)
        for table, _dur_s in windows.values()
    }
    clock = EventClock(
        int(round(interval_s * 1000)), int(round(watermark_s * 1000))
    ) if not all(uniform.values()) else None
    return {
        wname: WindowInput(
            table=table, slots=table_slots[table],
            duration_ms=int(dur_s * 1000), ts_col=ts_col,
            slot_uniform_time=uniform[table],
            handoff_by_key=handoff_by_key,
            clock=None if uniform[table] else clock,
        )
        for wname, (table, dur_s) in windows.items()
    }


def window_target(wname: str, targets: List[str]) -> str:
    """Bind a window name to its projected table: the longest target
    ``T`` such that the window is named ``T_<duration>``. A
    single-source flow may name windows freely (they can only mean
    its one table); multi-source flows must prefix-match or set the
    window's ``table`` conf key."""
    best = ""
    for t in targets:
        if wname.startswith(t + "_") and len(t) > len(best):
            best = t
    if best:
        return best
    return targets[0] if len(targets) == 1 else ""


def _read_maybe_file(value: str) -> str:
    """Conf values may inline content or point at a file (the reference
    always loads from storage; one-box flows inline the schema JSON).
    ``objstore://`` URLs fetch from the shared object store — the path
    shape a control plane on another host generates."""
    if value is None:
        return None
    v = value.strip()
    if v.startswith("{") or v.startswith("[") or "\n" in v or "--" in v[:4]:
        return value
    if v.startswith("objstore://") or v.startswith("objstore+https://"):
        from ..utils.fs import read_text

        return read_text(v)
    if os.path.exists(v):
        with open(v, "r", encoding="utf-8") as f:
            return f.read()
    return value


def load_reference_data_tables(
    dict_: SettingDictionary, dictionary: StringDictionary
) -> Dict[str, Tuple[ViewSchema, TableData]]:
    """CSV reference data as joinable tables
    (reference: handler/ReferenceDataHandler.scala:17-66)."""
    import csv

    out: Dict[str, Tuple[ViewSchema, TableData]] = {}
    groups = dict_.group_by_sub_namespace(
        SettingNamespace.JobInputPrefix + "referencedata."
    )
    for name, sub in groups.items():
        path = sub.get_string("path")
        delimiter = sub.get_or_else("delimiter", ",") or ","
        header = (sub.get_or_else("header", "true") or "true").lower() == "true"
        with open(path, "r", encoding="utf-8") as f:
            reader = csv.reader(f, delimiter=delimiter)
            rows = [r for r in reader if r]
        if not rows:
            continue
        if header:
            col_names, data_rows = rows[0], rows[1:]
        else:
            col_names = [f"_c{i}" for i in range(len(rows[0]))]
            data_rows = rows
        types: Dict[str, str] = {}
        for j, cname in enumerate(col_names):
            vals = [r[j] for r in data_rows if j < len(r)]
            types[cname] = _infer_csv_type(vals)
        cols: Dict[str, jnp.ndarray] = {}
        n = len(data_rows)
        for j, cname in enumerate(col_names):
            t = types[cname]
            if t == "long":
                arr = np.array([int(r[j]) for r in data_rows], dtype=np.int32)
            elif t == "double":
                arr = np.array([float(r[j]) for r in data_rows], dtype=np.float32)
            else:
                arr = np.array(
                    [dictionary.encode(r[j]) for r in data_rows], dtype=np.int32
                )
            cols[cname] = jnp.asarray(arr)
        table = TableData(cols, jnp.ones((n,), dtype=jnp.bool_))
        out[name] = (ViewSchema(types), table)
    return out


def _infer_csv_type(vals: List[str]) -> str:
    try:
        for v in vals:
            int(v)
        return "long"
    except ValueError:
        pass
    try:
        for v in vals:
            float(v)
        return "double"
    except ValueError:
        return "string"


@jax.tree_util.register_pytree_node_class
@dataclass
class PackedRaw:
    """One-matrix host->device transfer of a raw batch.

    Each host->device array costs a transfer op; a 7-column batch pays
    7. Packing every 4-byte column into rows of ONE [n_cols+1, capacity]
    int32 matrix (floats bitcast, bools widened, validity as the last
    row) makes ingest a single contiguous transfer; the jitted step
    bitcasts/slices the rows back apart device-side, which XLA fuses to
    nothing.
    """

    data: jnp.ndarray  # [len(layout)+1, capacity] int32; last row = valid
    layout: Tuple[Tuple[str, str], ...]  # (column, kind: i32|f32|bool)

    # Code that rewrites the step's arguments outside it handles every
    # table among them by ``cols`` / ``valid`` and rebuilds it as
    # ``type(t)(cols, valid)`` (the window states are laid out so for
    # the same reason, runtime/timewindow.py; the benchmark's planted
    # mesh fault masks the validity that way). A packed batch reads as
    # the table it unpacks to, and that table, rebuilt, is a TableData.
    def __new__(cls, data=None, layout=None):
        if isinstance(data, dict):
            return TableData(data, layout)
        return super().__new__(cls)

    @property
    def cols(self) -> Dict[str, jnp.ndarray]:
        return self.unpack().cols

    @property
    def valid(self) -> jnp.ndarray:
        return self.data[len(self.layout)] != 0

    def tree_flatten(self):
        return (self.data,), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], layout)

    def unpack(self) -> TableData:
        """Device-side (traceable) split back into named columns."""
        cols: Dict[str, jnp.ndarray] = {}
        for i, (name, kind) in enumerate(self.layout):
            row = self.data[i]
            if kind == "f32":
                row = jax.lax.bitcast_convert_type(row, jnp.float32)
            elif kind == "bool":
                row = row != 0
            cols[name] = row
        return TableData(cols, self.data[len(self.layout)] != 0)


def pack_raw(
    np_cols: Dict[str, np.ndarray], valid: np.ndarray,
    to_device: bool = True,
) -> PackedRaw:
    """Stack host columns into the single-transfer matrix (cheap host
    memcpy; the win is one device transfer instead of n_cols+1).

    ``to_device=False`` keeps the matrix as numpy — the jitted step's
    call transfers it implicitly — so a decode-ahead worker thread can
    build batches without touching jax from off the main thread."""
    rows: List[np.ndarray] = []
    layout: List[Tuple[str, str]] = []
    for c, a in np_cols.items():
        if a.dtype == np.float32:
            kind = "f32"
            a = a.view(np.int32)
        elif a.dtype == np.float64:
            kind = "f32"
            a = a.astype(np.float32).view(np.int32)
        elif a.dtype == np.bool_:
            kind = "bool"
            a = a.astype(np.int32)
        else:
            kind = "i32"
            if a.dtype != np.int32:
                a = a.astype(np.int32)  # x64-off semantics: wrap like jnp
        rows.append(a)
        layout.append((c, kind))
    rows.append(valid.astype(np.int32))
    stacked = np.stack(rows)
    return PackedRaw(
        jnp.asarray(stacked) if to_device else stacked, tuple(layout)
    )


def pack_from_matrix(
    matrix: np.ndarray, layout: Tuple[Tuple[str, str], ...],
    to_device: bool = True, sharding=None,
) -> PackedRaw:
    """PackedRaw over an ALREADY-packed matrix — the zero-copy sibling
    of ``pack_raw`` for the native decoder's pooled ingest buffers,
    which are written in the transfer layout to begin with. On the CPU
    backend ``jnp.asarray`` of the 64-byte-aligned pool matrix is a
    zero-copy view, which is exactly why the pool may only reuse a
    matrix after its batch has landed (PendingBatch slot release).

    ``sharding`` (a mesh's ``dist/mesh.py packed_sharding``): the
    matrix goes to the devices now, whatever ``to_device`` says, each
    chip's block of the capacity axis straight from the host (a
    transfer a chip, not one a column a chip; jax reads the pooled
    matrix behind the call, so the slot is pinned as above)."""
    if sharding is not None:
        return PackedRaw(jax.device_put(matrix, sharding), tuple(layout))
    # dx-race: param matrix=pool
    # dx-race: allow-zero-copy THE designed pooled zero-copy ingest site;
    # lifetime pinned by the PendingBatch owner-handoff
    return PackedRaw(
        jnp.asarray(matrix) if to_device else matrix, tuple(layout)
    )


@dataclass
class StagedBatch:
    """A batch's pooled packed matrix while its lines are decoded into
    it in passes (``FlowProcessor.decode_ahead``, then the poll's own
    call): the counts after the latest pass, and after each one, so
    that the passes past a cut can be dropped."""

    pool: object  # the PackedBufferPool ``matrix`` goes back to
    matrix: np.ndarray
    base_ms: int  # what the time cells decoded so far are relative to
    nbytes: int = 0  # of the lines decoded so far
    slots: int = 0  # row slots they own: the next pass starts here
    rows: int = 0  # valid rows among them
    bad_ts: int = 0  # rows dropped for a timestamp that does not parse
    seconds: float = 0.0  # in the decoder, dropped passes included
    # (nbytes, slots, rows, bad_ts) after each pass before the poll
    passes: List[Tuple[int, int, int, int]] = field(default_factory=list)

    def keep(self, nbytes: int) -> None:
        """Drop the passes that end past the batch's first ``nbytes``
        bytes: the next call decodes from the last one kept (and zeroes
        every slot from its end on)."""
        while self.passes and self.passes[-1][0] > nbytes:
            self.passes.pop()
        self.nbytes, self.slots, self.rows, self.bad_ts = \
            self.passes[-1] if self.passes else (0, 0, 0, 0)


def build_step_fn(
    ts_col: Optional[str],
    windows: Dict[str, Tuple[str, float]],
    output_datasets: List[str],
    state_names: List[str],
    refdata_names: List[str],
    ring_tables: List[str],
    pipeline,
    source_targets: List[Tuple[str, str]],  # (source name, target table)
    proj_views: Dict[str, list],
    primary_target: str,
    window_states: Optional[Dict[str, object]] = None,
):
    """Build the fused per-batch step function from its compiled parts.

    The ONE definition of the whole-flow device program: ``FlowProcessor
    ._jit_step`` jits exactly this, and the compile-surface analyzer
    (``analysis/compilecheck.py``) lowers exactly this over eval_shape
    avals to prove the trace surface closed — sharing the builder is
    what makes the emitted compile manifest drift-free by construction
    (the DX603 byte-exactness contract)."""

    def step(
        raw: Dict[str, TableData],
        # window state, donated: a raw-row ring a windowed table, and per
        # view in ``window_states`` its per-slot partial aggregates
        rings: Dict[str, object],
        state: Dict[str, TableData],
        refdata: Dict[str, TableData],
        base_s: jnp.ndarray,
        now_rel_ms: jnp.ndarray,
        counter: jnp.ndarray,
        delta_ms: jnp.ndarray,
        aux: Dict[str, jnp.ndarray],
    ):
        # Every stage runs under a ``jax.named_scope`` (``dx.<stage>``):
        # metadata only, the compiled program is the same, but a device
        # trace then says which stage an operation belongs to.
        # 1. per-source projection into its target table (each source
        # gets its own env so `Raw` binds to ITS raw table)
        projected: Dict[str, TableData] = {}
        for sname_, target_ in source_targets:
            with jax.named_scope(f"dx.project.{sname_}"):
                rt = raw[sname_]
                if isinstance(rt, PackedRaw):
                    rt = rt.unpack()  # split the single-transfer matrix
                env: Dict[str, TableData] = {
                    "Raw": rt,
                    DatasetName.DataStreamRaw: rt,
                    "__aux": aux,
                }
                for v in proj_views[sname_]:
                    env[v.name] = v.fn(env, base_s, now_rel_ms)
                projected[target_] = env[target_]

        # 2. ring updates (one ring per windowed table; each ring's
        # slot index derives from the shared batch counter). A table
        # whose rows bring their own time is put on its clock's grid
        # first: which rows the watermark accepts, and how many
        # intervals behind the batch each lies (runtime/timewindow.py)
        events: Dict[str, EventRows] = {}
        with jax.named_scope("dx.window"):
            for table, clock in pipeline.event_tables.items():
                events[table] = event_rows(
                    projected[table].cols[ts_col], projected[table].valid,
                    base_s, now_rel_ms, clock,
                )
        new_rings: Dict[str, WindowBuffers] = {}
        with jax.named_scope("dx.ring"):
            for table in ring_tables:
                buf = rings[table]
                slot = jax.lax.rem(
                    counter, jnp.asarray(buf.valid.shape[0], jnp.int32)
                )
                new_rings[table] = update_buffers(
                    buf, projected[table], slot, delta_ms, ts_col,
                    now_rel_ms, events.get(table),
                )

        tables: Dict[str, TableData] = dict(projected)
        with jax.named_scope("dx.window"):
            for wname, (table, dur_s) in windows.items():
                if table in new_rings:
                    tables[wname] = window_table(
                        new_rings[table], int(dur_s * 1000), now_rel_ms,
                        ts_col, events.get(table),
                    )
        # windowed GROUP BYs held as per-slot partial aggregates: the
        # batch's rows fold into its slot (``dx.window.partial``), the
        # view reads the groups combined over the live slots
        # (``dx.window.combine``); outside the view's own scope so a
        # device trace tells the window's state from the view's select
        slots_live = []
        # slots of window state written this batch, a table: one a ring,
        # the most a view's fold wrote
        touched = {table: jnp.asarray(1, jnp.int32) for table in new_rings}
        for vname, ws in (window_states or {}).items():
            with jax.named_scope("dx.window.partial"):
                new_rings[vname], in_window, live_rows, dropped, wrote = \
                    ws.fold(
                        projected[ws.table], rings[vname], counter, delta_ms,
                        base_s, now_rel_ms, aux, events.get(ws.table),
                    )
                touched[ws.table] = jnp.maximum(
                    touched.get(ws.table, 0), wrote)
            with jax.named_scope("dx.window.combine"):
                tables[WINDOW_PARTIALS_PREFIX + vname] = ws.combine(
                    new_rings[vname], in_window, live_rows, dropped
                )
                slots_live.append(in_window.astype(jnp.int32).sum())
        for rname in refdata_names:
            tables[rname] = refdata[rname]
        for sname in state_names:
            tables[sname] = state[sname]

        # each view under ``dx.view.<name>`` (compile/pipeline.py)
        out = pipeline.run(tables, base_s, now_rel_ms, aux=aux)

        new_state = {n: out.get(n, state[n]) for n in state_names}

        # compact outputs device-side (valid rows to the front) so the
        # host transfers only [:count] rows — the device->host hop is
        # the expensive boundary, so bytes AND round-trips are
        # minimized: all per-batch scalars ride ONE packed vector.
        from ..ops.compact import compact_indices

        datasets = {}
        with jax.named_scope("dx.counts"):
            counts = [projected[primary_target].count()]
        for n in output_datasets:
            t = out[n]
            with jax.named_scope(f"dx.compact.{n}"):
                idx, ov = compact_indices(t.valid, t.valid.shape[0])
                datasets[n] = TableData(
                    {c: v[idx] if v.shape[:1] == t.valid.shape else v
                     for c, v in t.cols.items()},
                    ov,
                )
            with jax.named_scope("dx.counts"):
                counts.append(t.count())
        with jax.named_scope("dx.counts"):
            # fixed layout: per output one groups-overflow then one
            # join-overflow slot; -1 marks "output does not track this
            # overflow" so the host can keep emitting 0 for ones that do
            for key in ("__overflow.groups", "__overflow.joins"):
                for n in output_datasets:
                    counts.append(
                        out[n].cols[key][0]
                        if key in out[n].cols
                        else jnp.asarray(-1, jnp.int32)
                    )
            # per-target projected input counts (multi-source metrics)
            for _sname, target_ in source_targets:
                counts.append(projected[target_].count())
            # live slots of every partial-aggregate window state
            counts.extend(slots_live)
            # event-time windows: accepted rows stamped over an interval
            # before the batch's time, rows the watermark refused, slots
            # written (summed over the tables)
            if events:
                counts.append(sum(e.late for e in events.values()))
                counts.append(sum(e.too_late for e in events.values()))
                counts.append(sum(touched.get(t, 0) for t in events))
            counts_vec = jnp.stack(
                [jnp.asarray(c, jnp.int32) for c in counts]
            )
        # plain tuple of pytrees for the jit boundary
        return (datasets, new_rings, new_state, counts_vec)

    return step


def source_raw_form(input_type: Optional[str]) -> str:
    """``packed`` when production dispatch ships a source of this input
    type as the single-matrix PackedRaw (native decoder hot path: a
    non-local input, on one chip as under a mesh, where the matrix is
    put with its capacity axis sharded), else ``columns``. The ONE
    definition both the runtime (``FlowProcessor._source_raw_form``)
    and the compile-surface analyzer use — the raw form is part of the
    step's trace signature, so the two may never disagree."""
    itype = (input_type or "local").lower()
    if itype in ("", "local"):
        return "columns"
    return "packed"


# raw-schema type -> PackedRaw row kind (the bitcast pack_raw applies)
_PACK_KINDS = {"double": "f32", "boolean": "bool"}


def packed_raw_layout(raw_types: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    """The PackedRaw layout the ingest hot path builds for a raw schema
    (column order preserved; kinds per the pack_raw bitcast rules).
    Layout is pytree aux data, i.e. part of the step's jit cache key —
    the compile manifest derives it from the same map."""
    return tuple(
        (c, _PACK_KINDS.get(t, "i32")) for c, t in raw_types.items()
    )


def packed_raw_struct(raw_types: Dict[str, str], capacity: int) -> PackedRaw:
    """Abstract (ShapeDtypeStruct) PackedRaw for one source — the exact
    aval the jitted step sees on the packed ingest path."""
    layout = packed_raw_layout(raw_types)
    return PackedRaw(
        jax.ShapeDtypeStruct((len(layout) + 1, capacity), jnp.int32), layout
    )


def aval_signature(tree) -> dict:
    """Canonical, JSON-stable description of a pytree of avals: the
    treedef repr (which carries custom-node aux data like the PackedRaw
    layout — part of the jit cache key) plus every leaf's shape and
    dtype. Two entries trace-compatible <=> identical signatures."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return {
        "tree": str(treedef),
        "leaves": [
            [list(map(int, l.shape)), str(np.dtype(l.dtype))] for l in leaves
        ],
    }


def step_compile_entry(step_avals: tuple) -> dict:
    """The one jit entry point a flow dispatches, the fused step, as a
    manifest-shaped dict. Shared by the runtime
    (``FlowProcessor.derive_compile_entries``, which feeds the AOT
    warm) and the static analyzer (``analysis/compilecheck.py``, which
    emits the manifest), so the two can only disagree when the flow
    itself changed (the DX603 drift signal)."""
    return {
        "entry": "step",
        "donate": list(STEP_DONATE_ARGNUMS),
        "static": {},
        "avals": aval_signature(step_avals),
    }


@dataclass
class SourceSpec:
    """One named input stream of a flow: its schema, projection chain,
    the table its projected rows land in, and its batch capacity.

    reference: one entry of the flattener's ``input.sources`` map
    (DataX.Config.Local/Resources/flattenerConfig.json) — per-source
    schema + normalization snippet + target table.
    """

    name: str
    target: str
    schema: Schema
    raw_schema: ViewSchema
    projection_steps: List[str]
    capacity: int
    conf: SettingDictionary


DEFAULT_SOURCE = "default"


class FlowProcessor:
    """Compiled per-flow processor. Build once; call process_batch per
    micro-batch (the closure the reference builds at
    CommonProcessorFactory.scala:50-120)."""

    def __init__(
        self,
        dict_: SettingDictionary,
        dictionary: Optional[StringDictionary] = None,
        udfs: Optional[dict] = None,
        batch_capacity: Optional[int] = None,
        output_datasets: Optional[List[str]] = None,
        mesh=None,
    ):
        self.dict = dict_
        self.dictionary = dictionary or StringDictionary()
        # dictionary capacity bound (see StringDictionary.__init__) —
        # applied even to an injected shared dictionary so the flow conf
        # stays authoritative
        sd_conf = dict_.get_sub_dictionary(
            SettingNamespace.JobProcessPrefix + "stringdictionary."
        )
        maxsize = sd_conf.get_int_option("maxsize")
        if maxsize is not None:
            if maxsize < 1:
                raise EngineException(
                    f"process.stringdictionary.maxsize must be >= 1, "
                    f"got {maxsize}"
                )
            self.dictionary.max_size = maxsize
        if (sd_conf.get_or_else("strict", "false") or "").lower() == "true":
            self.dictionary.strict = True
        # conf-declared UDFs (jar.udf/jar.udaf namespaces) + direct ones;
        # reference: ExtendedUDFHandler/JarUDFHandler reflection loading
        from ..udf import load_udfs_from_conf

        self.udfs = {**load_udfs_from_conf(dict_), **(udfs or {})}
        self.mesh = mesh

        input_conf = dict_.get_sub_dictionary(SettingNamespace.JobInputPrefix)
        process_conf = dict_.get_sub_dictionary(SettingNamespace.JobProcessPrefix)
        self.process_conf = process_conf
        # designer chip count (jobNumChips -> guiJobNumChips -> S650
        # process.numchips): honored when no mesh was passed in. A
        # conf asking for more chips than this process can see is an
        # error, never a smaller mesh — a 4-chip job on one chip would
        # run, and say so only in its throughput.
        if self.mesh is None:
            chips = process_conf.get_int_option("numchips")
            if chips is not None and chips > 1:
                from ..dist.mesh import make_mesh

                try:
                    self.mesh = make_mesh(chips)
                except ValueError as e:
                    raise EngineException(
                        f"process.numchips={chips}: {e}"
                    ) from None

        # sanitizer wiring — the runtime counterpart of the DX3xx UDF
        # analyzer: conf process.debug.nans / process.debug.tracerleaks
        # arm jax.debug_nans and tracer-leak checking around the jitted
        # step, turning surviving UDF impurity (NaNs from bad math,
        # tracers stashed in closures/globals) into loud failures in
        # test jobs instead of silent corruption
        dbg_conf = process_conf.get_sub_dictionary("debug.")
        self.debug_nans = (
            dbg_conf.get_or_else("nans", "false") or ""
        ).lower() == "true"
        self.debug_tracer_leaks = (
            dbg_conf.get_or_else("tracerleaks", "false") or ""
        ).lower() == "true"
        # process.debug.buffersanitizer arms the dynamic half of the
        # DX8xx buffer-lifetime defense (runtime/sanitizer.py): released
        # pool slots are poisoned, sink payloads and window checkpoints
        # scanned for leakage; hits fire runtime DX805
        from .sanitizer import from_conf as _sanitizer_from_conf

        self.buffer_sanitizer = _sanitizer_from_conf(dbg_conf)
        # on_interval failures skipped this/previous batches, drained
        # into the DATAX-<flow>:UdfRefreshError metric at collect()
        self.udf_refresh_errors = 0

        # datax.job.process.pipeline.depth: the in-flight window of the
        # pipelined hosts
        pipe_conf = process_conf.get_sub_dictionary("pipeline.")
        depth = pipe_conf.get_int_option("depth")
        if depth is None:
            depth = DEFAULT_PIPELINE_DEPTH
        elif depth < 1:
            raise EngineException(
                f"process.pipeline.depth must be >= 1, got {depth}"
            )
        self.pipeline_depth = depth
        # ingest decode sharding (datax.job.process.ingest.*): the
        # conf'd shard count the native decoder fans each payload
        # across (designer knob jobDecoderThreads -> generation;
        # DATAX_DECODER_THREADS stays the operator override). None =
        # engine default (cap 4 — ingest shares the host with the
        # engine loop and sinks).
        ing_conf = process_conf.get_sub_dictionary("ingest.")
        decoder_threads = ing_conf.get_int_option("decoderthreads")
        if decoder_threads is not None and decoder_threads < 1:
            raise EngineException(
                f"process.ingest.decoderthreads must be >= 1, got "
                f"{decoder_threads}"
            )
        self.decoder_threads = decoder_threads
        # observed mesh communication (datax.job.process.mesh.observe,
        # default on): under a mesh the compiled step's collective
        # census (dist/mesh.py collective_summary) exports per batch as
        # Mesh_ICI_Bytes / Mesh_Reshard_Count — the real runtime
        # counterpart the DX51x conformance ratios judge against the
        # embedded sharding model (process.mesh.model). The census
        # costs one extra lower+compile of the step (the persistent
        # compilation cache makes it a deserialize when configured).
        self.mesh_observe = (
            (
                process_conf.get_sub_dictionary("mesh.")
                .get_or_else("observe", "true") or ""
            ).lower() != "false"
        ) and self.mesh is not None
        # None = not yet censused; False = census failed (don't retry
        # every batch); else a dist.mesh.MeshCollectives
        self.mesh_collectives = None
        # serializes ring/state donation in dispatch against the
        # window-state snapshot a background landing thread may take at
        # checkpoint time (snapshotting a ring the next dispatch has
        # already donated would read a deleted buffer)
        self._device_state_lock = threading.Lock()

        # partitioned state (datax.job.process.state.*): every stateful
        # surface — accumulator tables AND window-ring snapshots —
        # hashes onto `partitions` key-range partitions
        # (runtime/statepartition.py); this replica owns the contiguous
        # range `replicaindex`/`replicacount` assigns it, persists only
        # those partitions, and (with `snapshoturl` set) ships them
        # through the shared objstore:// store so a successor replica
        # pulls exactly its assigned partitions on a rescale handoff.
        # `partitionkey` names the key column (per-table override:
        # statetable.<name>.partitionkey); `filteringest` drops rows of
        # un-owned partitions at encode time (key-routed ingest — the
        # Kafka key-partitioning contract restated for this engine).
        from .statepartition import (
            DEFAULT_STATE_PARTITIONS,
            ObjstoreSnapshotStore,
            owned_partitions,
        )

        state_conf = process_conf.get_sub_dictionary("state.")
        sp = state_conf.get_int_option("partitions")
        if sp is not None and sp < 1:
            raise EngineException(
                f"process.state.partitions must be >= 1, got {sp}"
            )
        self.state_partitions = sp or DEFAULT_STATE_PARTITIONS
        self.state_replica_count = max(
            1, state_conf.get_int_option("replicacount") or 1
        )
        self.state_replica_index = state_conf.get_int_option("replicaindex") or 1
        if not 1 <= self.state_replica_index <= self.state_replica_count:
            raise EngineException(
                f"process.state.replicaindex must be in "
                f"1..{self.state_replica_count}, got {self.state_replica_index}"
            )
        self.state_owned = owned_partitions(
            self.state_replica_index, self.state_replica_count,
            self.state_partitions,
        )
        self.state_partition_key = state_conf.get("partitionkey")
        self.state_filter_ingest = (
            (state_conf.get_or_else("filteringest", "false") or "")
            .lower() == "true"
        ) and self.state_replica_count > 1
        self._filter_warned: set = set()
        self.state_mirror = None
        snapshot_url = state_conf.get("snapshoturl")
        if snapshot_url:
            try:
                self.state_mirror = ObjstoreSnapshotStore(snapshot_url)
            except ValueError as e:
                raise EngineException(
                    f"process.state.snapshoturl invalid: {e}"
                ) from None
        # State_* metric deltas drained at collect + DX53x events the
        # host flight-records (shared with every StateTable)
        self.state_stats: Dict[str, float] = {}
        self.state_events: List[dict] = []

        # AOT compile + persistent compilation cache (the zero-cold-
        # start path, datax.job.process.compile.*): `manifest` carries
        # the compile manifest config generation embedded (inline JSON,
        # a file path, or objstore:// — analysis/compilecheck.py emits
        # it); with `aot` (default on when a manifest is present) every
        # manifest entry is compiled at INIT instead of first dispatch.
        # XLA's persistent compilation cache is always armed, at the
        # one directory compile/aotcache.py resolves (operator env, else
        # the checkout), so restarts and preemption recovery deserialize
        # instead of recompiling; `cacheurl` adds the shared object
        # store layer.
        comp_conf = process_conf.get_sub_dictionary("compile.")
        self.compile_manifest: Optional[dict] = None
        manifest_raw = _read_maybe_file(comp_conf.get("manifest"))
        if manifest_raw:
            try:
                self.compile_manifest = json.loads(manifest_raw)
            except ValueError as e:
                logger.warning("compile manifest does not parse: %s", e)
        self.aot_enabled = (
            (comp_conf.get_or_else("aot", "true") or "").lower() != "false"
        ) and self.compile_manifest is not None
        from ..compile.aotcache import PersistentCompileCache

        self._compile_cache: Optional[PersistentCompileCache] = (
            PersistentCompileCache(comp_conf.get("cacheurl"))
        )
        try:
            self._compile_cache.enable()
        except OSError as e:
            # an unwritable cache dir costs compile time, not results
            logger.warning(
                "persistent compile cache unavailable at %s: %s",
                self._compile_cache.dir, e,
            )
            self._compile_cache = None
        # Compile_* metric deltas drained at collect (ColdStart_Ms,
        # Cache_Hit_Count, Cache_Miss_Count, WarmMiss_Count)
        self.compile_stats: Dict[str, float] = {}
        self._aot_warmed = False
        # step jit-cache size right after the warm: growth past it at
        # dispatch time means a promised warm start was missed (DX604)
        self._warm_step_mark: Optional[int] = None

        self.interval_s = float(
            input_conf.get_or_else("streaming.intervalinseconds", "1")
        )
        max_rate = int(input_conf.get_or_else("eventhub.maxrate", "1000"))
        # flow-level default batch capacity: ctor arg > process conf
        # (generation.py S400 writes process.batchcapacity) > input conf
        default_capacity = (
            batch_capacity
            or process_conf.get_int_option("batchcapacity")
            or int(
                input_conf.get_or_else(
                    "streaming.maxbatchsize",
                    str(max(64, int(max_rate * self.interval_s))),
                )
            )
        )

        self.timestamp_column = process_conf.get("timestampcolumn")
        self.watermark_s = process_conf.get_duration_option("watermark") or 0.0

        # per-row Properties map (reference: handler/PropertiesHandler.scala
        # — appendproperty.* conf entries + BatchTime/InputTime/Partition/
        # CPTime/CPExecutor per row). Conf-gated: encoding per-batch
        # strings costs a dictionary entry per batch second, so flows opt
        # in by declaring appendproperty.* keys or
        # process.properties.enabled=true; otherwise the column stays
        # NULL. SystemProperties stays NULL — it carries AMQP transport
        # metadata the TCP/Kafka ingest paths do not have.
        self.append_properties = dict(
            process_conf.get_sub_dictionary("appendproperty.").dict
        )
        self.properties_enabled = bool(self.append_properties) or (
            process_conf.get_or_else("properties.enabled", "false") or ""
        ).lower() == "true"
        self._props_cache: Dict[Tuple, int] = {}
        import socket as _socket

        self._executor_id = f"{_socket.gethostname()}:{os.getpid()}"

        # planner capacities are flow conf, not constants: maxgroups
        # bounds GROUP BY fan-out, joincapacity bounds join output rows
        # (both surface overflow as metrics rather than failing)
        self.planner_config = self._planner_config(process_conf)

        # -- named sources ------------------------------------------------
        self.specs: Dict[str, SourceSpec] = {}
        source_groups = dict_.group_by_sub_namespace(
            SettingNamespace.JobPrefix + "input.sources."
        )
        global_projection = process_conf.get_string_seq_option("projection")
        if source_groups:
            # the flow's main input (input.default.*) joins the map as
            # the primary source when it is declared and the sources map
            # doesn't name its own "default" — the designer's model is
            # "main input + additional sources"
            if (
                DEFAULT_SOURCE not in source_groups
                and input_conf.get("blobschemafile")
            ):
                self.specs[DEFAULT_SOURCE] = self._make_spec(
                    DEFAULT_SOURCE, input_conf, default_capacity,
                    global_projection,
                )
            for sname, sub in source_groups.items():
                self.specs[sname] = self._make_spec(
                    sname, sub, default_capacity,
                    # the flow-level projection applies to the default
                    # source only; others declare their own
                    global_projection if sname == DEFAULT_SOURCE else None,
                )
        else:
            self.specs[DEFAULT_SOURCE] = self._make_spec(
                DEFAULT_SOURCE, input_conf, default_capacity, global_projection
            )
        targets = [s.target for s in self.specs.values()]
        if len(set(targets)) != len(targets):
            raise EngineException(
                f"input sources project into duplicate tables: {targets}"
            )

        # back-compat single-source surface: the primary spec
        self.primary = (
            DEFAULT_SOURCE if DEFAULT_SOURCE in self.specs
            else next(iter(self.specs))
        )
        primary = self.specs[self.primary]
        self.input_schema = primary.schema
        self.raw_schema = primary.raw_schema
        self.batch_capacity = primary.capacity

        # transform
        transform_text = _read_maybe_file(process_conf.get("transform")) or ""
        self.transform_text = transform_text

        # reference data
        self.refdata = load_reference_data_tables(dict_, self.dictionary)

        # time windows (handler/TimeWindowHandler.scala:23-68); each
        # window targets one projected table (conf `table`, else the
        # longest target that prefixes the window name, else the default)
        self.windows: Dict[str, Tuple[str, float]] = {}
        for wname, sub in dict_.group_by_sub_namespace(
            SettingNamespace.JobProcessPrefix + "timewindow."
        ).items():
            table = sub.get("table") or self._window_target(wname, targets)
            if table not in targets:
                raise EngineException(
                    f"timewindow {wname} targets unknown table {table!r} "
                    f"(projected tables: {targets})"
                )
            self.windows[wname] = (table, sub.get_duration("windowduration"))

        # state tables — partitioned: each replica persists only its
        # owned key-range partitions, mirrored through objstore:// when
        # process.state.snapshoturl is set (the rescale-handoff path)
        self.state_tables: Dict[str, StateTable] = {}
        for sname, sub in dict_.group_by_sub_namespace(
            SettingNamespace.JobProcessPrefix + "statetable."
        ).items():
            schema = parse_state_table_schema(sub.get_string("schema"))
            location = sub.get_or_else("location", f"/tmp/dxtpu-state/{sname}")
            key = sub.get("partitionkey") or (
                self.state_partition_key
                if self.state_partition_key in schema.types else None
            )
            self.state_tables[sname] = StateTable(
                sname, schema, self.batch_capacity * 4, location,
                partitions=self.state_partitions,
                owned=self.state_owned,
                partition_key=key,
                mirror=self.state_mirror,
                stats=self.state_stats,
                events=self.state_events,
            )

        # jit re-traces observed since the last collect (UDF-refresh
        # rebuilds + shape/dictionary-growth cache misses past the
        # initial trace) — drained into the Retrace_Count metric, the
        # conformance monitor's DX503 input. The mark is the jit cache
        # size already accounted for (None = initial trace still due).
        self.retrace_count = 0
        self._retrace_mark: Optional[int] = None

        self._build_pipeline(output_datasets)
        self._init_device_state()
        # source -> whether its raw batch comes as the one packed
        # matrix: what the input type says (``source_raw_form``) until a
        # dispatch is handed the other form (``_note_raw_forms``)
        self._raw_packed: Dict[str, bool] = {
            name: self._source_raw_form(spec) == "packed"
            for name, spec in self.specs.items()
        }
        self._jit_step()
        if self.aot_enabled:
            self._aot_warm()

    # -- build -----------------------------------------------------------
    def _planner_config(self, process_conf: SettingDictionary) -> PlannerConfig:
        kwargs = {}
        maxgroups = (
            process_conf.get_int_option("maxgroups")
            or process_conf.get_int_option("groupcapacity")
        )
        if maxgroups is not None:
            if maxgroups < 1:
                raise EngineException(
                    f"process.maxgroups must be >= 1, got {maxgroups}"
                )
            kwargs["max_group_capacity"] = maxgroups
        joincap = process_conf.get_int_option("joincapacity")
        if joincap is not None:
            if joincap < 1:
                raise EngineException(
                    f"process.joincapacity must be >= 1, got {joincap}"
                )
            kwargs["join_capacity"] = joincap
        return PlannerConfig(**kwargs)

    def _make_spec(
        self,
        name: str,
        conf: SettingDictionary,
        default_capacity: int,
        global_projection: Optional[List[str]],
    ) -> SourceSpec:
        schema_text = _read_maybe_file(conf.get("blobschemafile"))
        if schema_text is None:
            raise ValueError(
                f"input schema (blobschemafile) is required for source {name!r}"
            )
        schema = Schema.from_spark_json(schema_text)

        capacity = (
            conf.get_int_option("streaming.maxbatchsize") or default_capacity
        )
        if self.mesh is not None:
            # row shards must divide evenly over the data axis
            n = self.mesh.size
            capacity = ((capacity + n - 1) // n) * n

        target = conf.get("target") or (
            DatasetName.DataStreamProjection if name == DEFAULT_SOURCE else name
        )

        raw_types = dict(schema_to_view(schema).types)
        raw_types.setdefault(ColumnName.RawPropertiesColumn, "string")
        raw_types.setdefault(ColumnName.RawSystemPropertiesColumn, "string")
        raw_schema = ViewSchema(raw_types)

        # projection: selectExpr lines (handler/ProjectionHandler.scala);
        # per-source `projection` conf wins, then the flow-level one for
        # the default source, then the normalization default
        projections = (
            conf.get_string_seq_option("projection") or global_projection or []
        )
        steps = [_read_maybe_file(p) for p in projections] or [
            self._default_projection(schema)
        ]
        return SourceSpec(
            name=name,
            target=target,
            schema=schema,
            raw_schema=raw_schema,
            projection_steps=steps,
            capacity=capacity,
            conf=conf,
        )

    @staticmethod
    def _window_target(wname: str, targets: List[str]) -> str:
        return window_target(wname, targets)

    def _default_projection(self, schema: Schema) -> str:
        return default_projection(schema, self.timestamp_column)

    def _projection_select(self, step_text: str, from_table: str):
        return projection_select(step_text, from_table)

    def _build_pipeline(self, output_datasets: Optional[List[str]]):
        pc = PipelineCompiler(
            self.dictionary, self.udfs, config=self.planner_config
        )
        # one dictionary-table registry for the whole flow (projection +
        # transform share string-op tables; see compile/stringops.py);
        # the builder materializes them per batch for the jitted step
        self.aux_registry = pc.aux

        # 1. per-source projection pipelines: Raw -> <target table>
        from ..compile.planner import SelectCompiler

        self.projection_views: Dict[str, List] = {}
        self.target_schemas: Dict[str, ViewSchema] = {}
        for spec in self.specs.values():
            proj_catalog = {
                "Raw": spec.raw_schema,
                DatasetName.DataStreamRaw: spec.raw_schema,
            }
            proj_caps = {
                "Raw": spec.capacity,
                DatasetName.DataStreamRaw: spec.capacity,
            }
            cur_name = "Raw"
            views = []
            for i, step_text in enumerate(spec.projection_steps):
                sel = self._projection_select(step_text, cur_name)
                compiler = SelectCompiler(
                    proj_catalog, proj_caps, self.dictionary, self.udfs,
                    self.planner_config, aux=pc.aux,
                )
                vname = (
                    spec.target
                    if i == len(spec.projection_steps) - 1
                    else f"__proj{i}"
                )
                view = compiler.compile_select(vname, sel)
                views.append(view)
                proj_catalog[vname] = view.schema
                proj_caps[vname] = view.capacity
                cur_name = vname
            self.projection_views[spec.name] = views
            self.target_schemas[spec.target] = proj_catalog[spec.target]
        self.projected_schema = self.target_schemas[
            self.specs[self.primary].target
        ]

        # 2. window slots per windowed target table
        projections: Dict[str, List[List[str]]] = {}
        for s in self.specs.values():
            projections.setdefault(s.target, []).append(s.projection_steps)
        table_slots: Dict[str, int] = {}
        for wname, (table, dur_s) in self.windows.items():
            if self.timestamp_column not in self.target_schemas[table].types:
                raise EngineException(
                    f"timewindow {wname} requires timestamp column "
                    f"{self.timestamp_column!r} in table {table}"
                )
            slots = num_slots(
                dur_s, self.watermark_s, self.interval_s,
                event_time_table(projections, table, self.timestamp_column),
            )
            table_slots[table] = max(table_slots.get(table, 1), slots)

        # 3. main pipeline inputs
        target_caps = {s.target: s.capacity for s in self.specs.values()}
        inputs: Dict[str, Tuple[ViewSchema, int]] = {
            t: (sch, target_caps[t]) for t, sch in self.target_schemas.items()
        }
        for wname, (table, _dur) in self.windows.items():
            inputs[wname] = (
                self.target_schemas[table],
                table_slots[table] * target_caps[table],
            )
        for rname, (rschema, rtable) in self.refdata.items():
            inputs[rname] = (rschema, rtable.capacity)
        state_inputs = {
            sname: (st.schema, st.capacity) for sname, st in self.state_tables.items()
        }

        self.pipeline: Pipeline = pc.compile_transform(
            self.transform_text, inputs, state_inputs,
            windows=window_inputs(
                self.windows, table_slots, projections,
                self.timestamp_column,
                handoff_by_key=self.state_mirror is not None,
                interval_s=self.interval_s, watermark_s=self.watermark_s,
            ),
        )
        # the planner's choice, from the statements alone: a window every
        # reader of which is a decomposable GROUP BY keeps per-slot
        # partial aggregates (a state a view); any other keeps its
        # table's raw-row ring
        self.window_states = self.pipeline.window_states
        self.ring_slots: Dict[str, int] = {
            table: table_slots[table]
            for wname, (table, _dur) in self.windows.items()
            if wname not in self.pipeline.partial_windows
        }
        from ..compile.stringops import AuxTableBuilder

        from ..compile.stringops import _MAX_ROUNDS

        try:
            max_rounds = self.dict.get_int_option(
                "datax.job.process.stringmap.maxrounds")
        except ValueError as e:
            raise EngineException(
                f"datax.job.process.stringmap.maxrounds must be an "
                f"integer: {e}"
            ) from None
        if max_rounds is None:
            max_rounds = _MAX_ROUNDS
        elif max_rounds < 1:
            raise EngineException(
                "datax.job.process.stringmap.maxrounds must be >= 1, got "
                f"{max_rounds}"
            )
        self.aux_tables = AuxTableBuilder(
            self.aux_registry, self.dictionary,
            max_rounds=max_rounds,
            strict=(self.dict.get_or_else(
                "datax.job.process.stringmap.strict", "false") or ""
            ).lower() == "true",
        )

        # output datasets: explicit list or conf-declared output names that
        # match pipeline views (S500-style dataset==output-name contract)
        if output_datasets is None:
            conf_outputs = self.dict.get_sub_dictionary(
                SettingNamespace.JobOutputPrefix
            ).group_by_sub_namespace()
            output_datasets = [
                n for n in conf_outputs if n in self.pipeline.catalog
            ]
        self.output_datasets = [
            n for n in output_datasets if n in self.pipeline.catalog
        ]

    def _init_device_state(self):
        # dx-race: single-threaded init/reset path — runs before the host
        # starts the landing worker (or with it quiesced on LQ reset)
        self.window_buffers = self._fresh_window_state()
        # state load is the handoff-critical path of a successor
        # replica (pull owned partitions from the mirror): time it once
        # so State_Handoff_Ms reports what the rescale actually cost
        t0 = time.time()
        self.state_data: Dict[str, TableData] = {
            sname: st.load(self.dictionary) for sname, st in self.state_tables.items()
        }
        if self.state_tables:
            self.state_stats.setdefault(
                "Handoff_Ms", (time.time() - t0) * 1000.0
            )
        self._slot_counter = 0
        self._base_ms: Optional[int] = None
        # host-side ingest counters (e.g. rows dropped for garbage
        # timestamps), drained into metrics at each collect
        self.ingest_stats: Dict[str, int] = {}
        # monotonic malformed-line total (never cleared — the host's
        # pilot reads per-poll deltas off it, so the collect-time drain
        # of ingest_stats can't race the flood signal)
        self.malformed_rows_total = 0
        self._native_decoders: Dict[str, object] = {}
        # source -> devices holding its last dispatched raw batch
        # (placement(); 0 = host array the step call transfers)
        self._raw_devices: Dict[str, int] = {}
        # ingest decode fast path state: per-source pools of persistent
        # 64-byte-aligned packed H2D matrices (decoder shards write
        # straight into them; slots release when their batch lands),
        # the schema-column -> matrix-row maps, and the decode gauges
        # (Decode_Shards / Decode_RowsPerSec / Decode_BufferReuse_Count)
        self._ingest_pools: Dict[str, object] = {}
        self._ingest_col_rows: Dict[str, List[int]] = {}
        self._decode_shards: Optional[int] = None
        self._decode_rows_per_sec: Optional[float] = None
        # source -> its NEXT batch's matrix while ``decode_ahead`` fills
        # it, and what the latest packed encode found decoded ahead:
        # (rows kept, the batch's rows, ms of the passes, passes)
        self._staged: Dict[str, StagedBatch] = {}
        self.decode_ahead_stats: Dict[str, Tuple[int, int, float, int]] = {}
        # which decode engine served the last encode_json_bytes call:
        # "native-sharded" (packed pool path) / "native-mt" (row-layout
        # native, under a mesh)
        self.last_decoder_path: Optional[str] = None

    def _fresh_window_state(self) -> Dict[str, object]:
        """Empty window state as the step takes it: a raw-row ring a
        windowed table the planner left as rows, per-slot partial
        aggregates a view it decomposed (keys: table names, view names)."""
        target_caps = {s.target: s.capacity for s in self.specs.values()}
        state: Dict[str, object] = {
            table: make_buffers(
                self.target_schemas[table], target_caps[table], slots
            )
            for table, slots in self.ring_slots.items()
        }
        for vname, ws in self.window_states.items():
            state[vname] = ws.init()
        return {n: self._place_rings(b) for n, b in state.items()}

    def _place_rings(self, buf):
        """Under a mesh, lay window state out as the step's in/out
        shardings expect (a ring's capacity dim over the data axis,
        partial aggregates replicated) — each chip holds its share from
        the start, and the first step's donation finds buffers it can
        reuse. Single chip: unchanged."""
        if self.mesh is None:
            return buf
        from ..dist.mesh import replicated, ring_sharding

        sh = ring_sharding(self.mesh) if isinstance(buf, WindowBuffers) \
            else replicated(self.mesh)
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), buf)

    def window_state_bytes(self) -> int:
        """Device bytes of window state (rings and partial aggregates):
        static, from the shapes."""
        return sum(
            int(a.size) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(self.window_buffers)
        )

    def _put_rows(self, a) -> jnp.ndarray:
        """One raw column (host or device array) -> where the step
        wants it. Under a mesh each chip receives only its row shard,
        straight from the host (not the whole column on chip 0 and a
        scatter inside the step)."""
        if self.mesh is None:
            return jnp.asarray(a)
        from ..dist.mesh import row_sharding

        return jax.device_put(a, row_sharding(self.mesh))

    def _packed_sharding(self):
        """Where the step wants a source's packed matrix under a mesh
        (``pack_from_matrix``'s ``sharding``); None on one chip."""
        if self.mesh is None:
            return None
        from ..dist.mesh import packed_sharding

        return packed_sharding(self.mesh)

    def reset_state(self) -> None:
        """Zero device state (rings, slot counter, time base; state
        tables reload from their location). For re-entrant uses like
        LiveQuery kernels where each execute must be idempotent."""
        self._init_device_state()

    # -- window-state checkpoint ------------------------------------------
    def snapshot_window_state(
        self, since: Optional[int] = None
    ) -> Dict[str, object]:
        """Host copy of everything a restart would otherwise lose: the
        window ring buffers, the per-slot partial aggregates, the slot
        counter, the time base the slot timestamps are relative to, AND
        the string dictionary — ring columns hold dictionary ids, which
        only mean anything against the dictionary that encoded them.
        Numpy-only; feed to ``WindowStateCheckpointer.save`` (reference
        restores window state via the StreamingContext checkpoint,
        StreamingHost.scala:83-89).

        Partial aggregates are snapshotted a slot at a time: ``since``
        is the slot counter of the checkpoint the caller already holds
        (``WindowStateCheckpointer.landed_counter``), and only the live
        slots a batch has changed since cross to the host (a late row
        changes a slot an earlier checkpoint wrote: it crosses again),
        with the small per-view head (key directory, slot times, slot
        generations). ``None`` takes every live slot."""
        # under the device-state lock: the checkpoint may run on the
        # background landing thread while the dispatch thread is about
        # to donate these very ring buffers into the next step. The
        # copies must be REAL copies — ``np.asarray`` of a CPU jax
        # array is a zero-copy VIEW of the device buffer, and a view
        # escaping this lock dangles the moment the next dispatch
        # donates the ring (reads after that are use-after-free: heap
        # corruption, not just stale data)
        with self._device_state_lock:
            rings, partials = {}, {}
            cur = self._slot_counter
            for name, buf in self.window_buffers.items():
                if isinstance(buf, WindowPartials):
                    partials[name] = _snapshot_partials(buf, cur, since)
                    continue
                rings[name] = {
                    "cols": {
                        c: np.array(a, copy=True)
                        for c, a in buf.cols.items()
                    },
                    "valid": np.array(buf.valid, copy=True),
                }
            snap = {
                "rings": rings,
                "slot_counter": cur,
                "base_ms": self._base_ms,
                "dictionary": self.dictionary.entries(),
            }
            if partials:
                snap["partials"] = partials
            return snap

    def restore_window_state(self, snap: Dict[str, object]) -> bool:
        """Restore a ``snapshot_window_state`` result. Shape-checked: a
        conf change that resized the rings invalidates the snapshot
        (returns False and keeps the fresh zero state). The saved
        dictionary must agree with the strings this process has already
        encoded (same conf => same compile-time literals in the same
        order); on agreement the remaining saved entries replay so every
        restored ring id decodes to the string it meant before the
        restart."""
        saved_dict = snap.get("dictionary")
        if saved_dict is not None:
            if not self.dictionary.restore_entries(saved_dict):
                return False
        rings = snap.get("rings", {})
        partials = snap.get("partials", {})
        restored: Dict[str, object] = {}
        for table, buf in self.window_buffers.items():
            if isinstance(buf, WindowPartials):
                state = _restore_partials(buf, partials.get(table))
                if state is None:
                    return False
                restored[table] = state
                continue
            saved = rings.get(table)
            if saved is None:
                return False
            if set(saved["cols"]) != set(buf.cols) or any(
                saved["cols"][c].shape != buf.cols[c].shape
                # dx-race: allow-zero-copy dtype probe only — no element read
                or saved["cols"][c].dtype != np.asarray(buf.cols[c]).dtype
                for c in buf.cols
            ):
                return False
            # copy=True is load-bearing: ``jnp.asarray`` ZERO-COPIES a
            # 64-byte-aligned numpy buffer on the CPU backend, and the
            # rings are the step's DONATED argument — donating an
            # aliased buffer has XLA free memory numpy owns (heap
            # corruption, flaky segfaults under the pipelined loop)
            restored[table] = WindowBuffers(
                {c: jnp.array(a, copy=True)
                 for c, a in saved["cols"].items()},
                jnp.array(saved["valid"], copy=True),
            )
        restored = {t: self._place_rings(b) for t, b in restored.items()}
        # publish atomically under the device-state lock: a checkpoint on
        # the landing thread must never see half-swapped ring state
        with self._device_state_lock:
            self.window_buffers = restored
            self._slot_counter = int(snap.get("slot_counter", 0))
            base = snap.get("base_ms")
            self._base_ms = int(base) if base is not None else None
        return True

    # -- partitioned window state (the rescale-handoff path) --------------
    WINDOW_STORE_NAME = "__window__"

    def _window_key_cols(self) -> Dict[str, Tuple[str, str]]:
        """Ring table -> (partition-key column, kind): the conf'd
        ``state.partitionkey`` when the table carries it, else the
        first non-timestamp column (rows of tables with no usable key
        land in partition 0 — statepartition.split_window_snapshot)."""
        out: Dict[str, Tuple[str, str]] = {}
        for table in self.ring_slots:
            types = self.target_schemas[table].types
            key = None
            if self.state_partition_key and \
                    self.state_partition_key in types:
                key = self.state_partition_key
            else:
                key = next(
                    (c for c in types if c != self.timestamp_column), None
                )
            if key is not None:
                out[table] = (key, types[key])
        return out

    def push_window_partitions(self, snap: Dict[str, object]) -> int:
        """Ship this replica's OWNED window partitions to the objstore
        mirror as per-partition A/B snapshots + pointer (the same
        layout the state tables use). Called on the checkpoint cadence
        after commit; fail-closed like every state push."""
        if self.state_mirror is None or not snap.get("rings"):
            return 0
        from .statepartition import other_side, snapshot_to_bytes
        from .statepartition import split_window_snapshot

        parts = split_window_snapshot(
            snap, self.state_partitions, self._window_key_cols(),
            dictionary=self.dictionary, only=self.state_owned,
        )
        for p, part_snap in parts.items():
            prefix = f"{self.WINDOW_STORE_NAME}/p{p:02d}"
            side = other_side(self.state_mirror.get_pointer(prefix) or "B")
            self.state_mirror.put_files(
                prefix, side, {"window.npz": snapshot_to_bytes(part_snap)}
            )
            self.state_mirror.put_pointer(prefix, side)
        self.state_stats["Snapshot_Push_Count"] = (
            self.state_stats.get("Snapshot_Push_Count", 0) + len(parts)
        )
        return len(parts)

    def pull_window_partitions(self) -> List[Dict]:
        """Fetch this replica's assigned window partitions from the
        mirror — possibly written by SEVERAL predecessors. A corrupt
        active side falls back to the standby (DX530 +
        ``State_LoadFallback_Count``), both-bad loads nothing for that
        partition (DX531) and the un-acked window replay re-aggregates."""
        if self.state_mirror is None:
            return []
        from .statepartition import other_side, snapshot_from_bytes

        out: List[Dict] = []
        pulled = 0
        for p in self.state_owned:
            prefix = f"{self.WINDOW_STORE_NAME}/p{p:02d}"
            pointer = self.state_mirror.get_pointer(prefix)
            if pointer is None:
                continue
            snap = None
            for attempt, side in enumerate((pointer, other_side(pointer))):
                data = self.state_mirror.get_file(prefix, side, "window.npz")
                if data is None:
                    continue
                try:
                    snap = snapshot_from_bytes(data)
                    break
                except Exception as e:  # noqa: BLE001 — corrupt snapshot
                    self.state_stats["LoadFallback_Count"] = (
                        self.state_stats.get("LoadFallback_Count", 0) + 1
                    )
                    code = "DX530" if attempt == 0 else "DX531"
                    self.state_events.append({
                        "code": code, "table": self.WINDOW_STORE_NAME,
                        "partition": p, "side": side,
                        "message": (
                            f"window partition {p} side {side} "
                            f"unreadable ({e})"
                        ),
                        "ts": time.time(),
                    })
            if snap is not None:
                out.append(snap)
                pulled += 1
        if pulled:
            self.state_stats["Snapshot_Pull_Count"] = (
                self.state_stats.get("Snapshot_Pull_Count", 0) + pulled
            )
        return out

    def restore_window_partitions(self) -> bool:
        """The successor half of a window handoff: pull the assigned
        partitions, merge them (re-packed per slot, timestamps rebased,
        string ids remapped into the LIVE dictionary —
        statepartition.merge_window_snapshots) and restore. False when
        the mirror holds nothing usable."""
        parts = self.pull_window_partitions()
        if not parts:
            return False
        from .statepartition import merge_window_snapshots

        merged = merge_window_snapshots(
            parts,
            {t: dict(self.target_schemas[t].types) for t in self.ring_slots},
            self.dictionary,
            self.timestamp_column,
        )
        if merged is None:
            return False
        dropped = merged.pop("dropped_rows", 0)
        if dropped:
            self.state_stats["WindowRows_Dropped_Count"] = (
                self.state_stats.get("WindowRows_Dropped_Count", 0) + dropped
            )
        return self.restore_window_state(merged)

    # -- the jitted step --------------------------------------------------
    def _jit_step(self):
        step = build_step_fn(
            ts_col=self.timestamp_column,
            windows=dict(self.windows),
            output_datasets=list(self.output_datasets),
            state_names=list(self.state_tables),
            refdata_names=list(self.refdata),
            ring_tables=list(self.ring_slots),
            pipeline=self.pipeline,
            source_targets=[(s.name, s.target) for s in self.specs.values()],
            proj_views=dict(self.projection_views),
            primary_target=self.specs[self.primary].target,
            window_states=dict(self.window_states),
        )
        self._step_fn = step
        # donate the rings: the old buffers are dead after the step, so
        # XLA updates the (large) window rings in place instead of
        # allocating copies each batch. State tables are NOT donated — a
        # pipelined PendingBatch still reads its state for the A/B
        # overwrite after the next batch has been dispatched.
        if self.mesh is not None:
            from ..dist.mesh import step_shardings

            in_shardings, out_shardings = step_shardings(
                self.mesh, rings=tuple(self.ring_slots),
                partials=tuple(self.window_states),
                packed=self._raw_packed,
            )
            self._step = jax.jit(
                step,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
                donate_argnums=STEP_DONATE_ARGNUMS,
            )
        else:
            self._step = jax.jit(step, donate_argnums=STEP_DONATE_ARGNUMS)

    # -- per-batch host path ----------------------------------------------
    def _spec(self, source: Optional[str]) -> SourceSpec:
        return self.specs[source or self.primary]

    def _properties_id(self, base_ms: int, file_info: Optional[dict] = None) -> int:
        """Dictionary id of the per-row Properties JSON map (reference:
        PropertiesHandler's per-row UDF result). Cached per (batch
        second, file) so repeated rows share one dictionary entry."""
        import datetime as _dt

        key = (base_ms, file_info.get("path") if file_info else None)
        sid = self._props_cache.get(key)
        if sid is not None:
            return sid

        def iso(ms: int) -> str:
            return _dt.datetime.fromtimestamp(
                ms / 1000, _dt.timezone.utc
            ).strftime("%Y-%m-%d %H:%M:%S")

        from ..constants import ProcessingPropertyName as P

        props = dict(self.append_properties)
        props[P.BatchTime] = iso(base_ms)
        props[P.CPTime] = iso(int(time.time()) * 1000)
        props[P.CPExecutor] = self._executor_id
        if file_info:
            if file_info.get("fileTimeMs"):
                props[P.BlobTime] = iso(int(file_info["fileTimeMs"]))
            if file_info.get("path"):
                props[P.BlobPathHint] = os.path.basename(file_info["path"])
        sid = self.dictionary.encode(json.dumps(props, sort_keys=True))
        if len(self._props_cache) > 4096:
            self._props_cache.clear()
        self._props_cache[key] = sid
        return sid

    def encode_rows(
        self, rows: List[dict], base_ms: int, source: Optional[str] = None
    ) -> TableData:
        """Host-side fallback encoder (python loop). The C++ decoder in
        native/ covers the hot path; benchmarks use the vectorized
        generator."""
        from ..core.batch import batch_from_rows

        spec = self._spec(source)
        b = batch_from_rows(
            rows, spec.schema, spec.capacity, self.dictionary,
            base_ms, stats=self.ingest_stats,
        )
        cols = dict(b.columns)
        if self.properties_enabled:
            default_id = self._properties_id(base_ms)
            props = np.full(spec.capacity, 0, np.int32)
            for i in range(min(len(rows), spec.capacity)):
                fi = rows[i].get(ColumnName.InternalColumnFileInfo)
                props[i] = (
                    self._properties_id(base_ms, fi) if fi else default_id
                )
            cols[ColumnName.RawPropertiesColumn] = jnp.asarray(props)
        cols.setdefault(
            ColumnName.RawPropertiesColumn,
            jnp.zeros((spec.capacity,), jnp.int32),
        )
        cols.setdefault(
            ColumnName.RawSystemPropertiesColumn,
            jnp.zeros((spec.capacity,), jnp.int32),
        )
        valid = b.valid
        if self.state_filter_ingest:
            key = self.state_partition_key
            src = cols.get(key)
            valid = jnp.asarray(self._filter_unowned(
                np.asarray(src) if src is not None else None,
                np.asarray(valid), spec,
            ))
        return TableData(cols, valid)

    def encode_json_bytes(
        self,
        data: bytes,
        base_ms: int,
        source: Optional[str] = None,
        packed: bool = True,
        to_device: bool = True,
        fmt: str = "jsonl",
        ahead_bytes: int = 0,
    ) -> Union[TableData, "PackedRaw"]:
        """Native ingest hot path: raw wire bytes decoded by the C++
        decoder (native/decoder.cpp) straight into columnar buffers —
        the from_json role at CommonProcessorFactory.scala:90-103
        without any per-event Python objects. A native library that
        cannot be built raises (``native.NativeBuildError``); the
        per-row Python encoder (``_encode_json_python``) is the parity
        reference tests call directly, never a served-path substitute.

        ``fmt``: ``"jsonl"`` (newline-delimited JSON — socket/file
        sources) or ``"kafka-v2"`` (whole Kafka message-format-v2
        record batches from ``KafkaSource.poll_raw`` — the native
        walker verifies CRC-32C per batch, skips+counts corrupt
        batches, rejects compressed ones with a typed error, and feeds
        record values to the JSON column decoder in the same call).

        ``packed`` (the default, on one chip as under a mesh: bytes
        off the wire are a non-local input, which ``source_raw_form``
        ships packed): decoder shards write directly into a persistent
        64-byte-aligned pooled matrix in the single-transfer PackedRaw
        layout — zero per-row Python objects, zero per-call column
        allocations, no pack copy. The matrix is reused only after its
        batch lands (PendingBatch releases the slot), double-buffering
        the pool against the pipelined in-flight window. Under a mesh
        the matrix is put here, each chip's block of its capacity axis
        a transfer (span ``shard-put``). False: the row layout, a
        fresh array a column, which only the parity tests ask for.

        ``ahead_bytes``: how many of ``data``'s leading bytes
        ``decode_ahead`` has already decoded into this batch's matrix
        (``_encode_packed_native``)."""
        spec = self._spec(source)
        decoder = self._native_decoder(spec)
        self.decode_ahead_stats.pop(spec.name, None)
        if packed:
            return self._encode_packed_native(
                decoder, data, base_ms, spec, fmt, to_device, ahead_bytes
            )

        # row-layout native path
        self.last_decoder_path = "native-mt"
        if fmt == "kafka-v2":
            data = self._kafka_values_to_lines(data)
        arrays, valid, rows, _consumed = decoder.decode(data, spec.capacity)
        self._decode_shards = decoder.last_shards
        self._count_jsonl_malformed(data, 0, _consumed, rows)
        if decoder.last_bad_timestamps:
            self.ingest_stats["bad_timestamps"] = (
                self.ingest_stats.get("bad_timestamps", 0)
                + decoder.last_bad_timestamps
            )
        cap = spec.capacity
        np_cols: Dict[str, np.ndarray] = {}
        for col in spec.schema.columns:
            a = arrays[col.name]
            if col.ctype == ColType.TIMESTAMP:
                # slots the decoder left at 0 (field missing) stay at
                # relative 0; deltas saturate at the int32 range like the
                # Python encoder (core/batch.py) instead of wrapping
                a = np.where(
                    a == 0,
                    np.int64(0),
                    np.clip(a - np.int64(base_ms), -2**31, 2**31 - 1),
                ).astype(np.int32)
            elif col.ctype == ColType.BOOLEAN:
                a = a.astype(np.bool_)
            np_cols[col.name] = a
        for extra in (
            ColumnName.RawPropertiesColumn,
            ColumnName.RawSystemPropertiesColumn,
        ):
            if extra in spec.raw_schema.types and extra not in np_cols:
                if (
                    extra == ColumnName.RawPropertiesColumn
                    and self.properties_enabled
                ):
                    np_cols[extra] = np.full(
                        cap, self._properties_id(base_ms), np.int32
                    )
                else:
                    np_cols[extra] = np.zeros(cap, np.int32)
        valid = np.asarray(valid)
        if self.state_filter_ingest:
            valid = self._filter_unowned(
                np_cols.get(self.state_partition_key), valid, spec
            )
        # under a mesh every column goes to each chip's row shard, one
        # transfer a column a shard (the host's time in the calls; jax
        # completes them behind it)
        with _trace_span("shard-put") if self.mesh is not None \
                else contextlib.nullcontext():
            return TableData(
                {c: self._put_rows(a) for c, a in np_cols.items()},
                self._put_rows(valid),
            )

    # -- ingest fast-path helpers -----------------------------------------
    def _native_decoder(self, spec: SourceSpec):
        decoder = self._native_decoders.get(spec.name)
        if decoder is None:
            from ..native import NativeDecoder

            decoder = NativeDecoder(
                spec.schema, self.dictionary, threads=self.decoder_threads
            )
            self._native_decoders[spec.name] = decoder
        return decoder

    def _count_jsonl_malformed(self, data: bytes, start: int, consumed: int,
                               rows: int) -> None:
        """Malformed lines in the range a decode from ``data[start]`` on
        consumed = newline count minus decoded rows (the decoder
        zero-gaps them); feeds the Input_malformed_rows_Count metric and
        the pilot flood signal. Allocation-free line count (bytes.count
        is C): blank lines are rare enough that miscounting one as
        malformed can't move the pilot's 30% flood threshold."""
        stop = start + consumed if consumed else len(data)
        lines_seen = data.count(b"\n", start, stop)
        if stop > start and data[stop - 1] != 0x0A:
            lines_seen += 1
        malformed = max(0, lines_seen - int(rows))
        if malformed:
            self.ingest_stats["malformed_rows"] = (
                self.ingest_stats.get("malformed_rows", 0) + malformed
            )
            self.malformed_rows_total += malformed

    def _count_ingest(self, key: str, n: int, malformed: bool = False) -> None:
        if not n:
            return
        self.ingest_stats[key] = self.ingest_stats.get(key, 0) + n
        if malformed:
            self.malformed_rows_total += n

    def _kafka_values_to_lines(self, data: bytes) -> bytes:
        """Python record-batch walk for the row-layout/fallback paths:
        extract record values (CRC verified, corrupt batches counted,
        compressed rejected typed) and hand them to the line decoder.
        Well-formed JSON never contains a raw newline, so the join is
        loss-free; a malformed value containing one just counts as
        malformed twice."""
        from .kafka_wire import decode_record_batches

        stats: Dict[str, int] = {}
        recs, _next = decode_record_batches(data, stats=stats)
        self._count_ingest("CorruptBatch", stats.get("corrupt_batches", 0))
        return b"\n".join(v for _o, _ts, v in recs) + (b"\n" if recs else b"")

    def _encode_json_python(
        self, data: bytes, base_ms: int, spec: SourceSpec, fmt: str,
    ) -> TableData:
        """The reference decode the native parity tests compare
        against: per-row Python (json.loads into the row encoder), with
        the same malformed/corrupt accounting as the native path."""
        import json as _json

        if fmt == "kafka-v2":
            from .kafka_wire import decode_record_batches

            stats: Dict[str, int] = {}
            recs, _next = decode_record_batches(data, stats=stats)
            self._count_ingest(
                "CorruptBatch", stats.get("corrupt_batches", 0)
            )
            lines: List[bytes] = [v for _o, _ts, v in recs]
        else:
            lines = data.splitlines()
        rows = []
        malformed = 0
        for ln in lines:
            if not ln.strip():
                # a blank jsonl line is framing noise; an EMPTY Kafka
                # record value is a real record with no event — count
                # it malformed like the native walker does
                if fmt == "kafka-v2":
                    malformed += 1
                continue
            try:
                rows.append(_json.loads(ln))
            except ValueError:
                malformed += 1  # skip malformed lines, but count
                continue        # them: the pilot's flood signal
            if len(rows) >= spec.capacity:
                break
        self._count_ingest("malformed_rows", malformed, malformed=True)
        return self.encode_rows(rows, base_ms, source=spec.name)

    def _ingest_plan(self, spec: SourceSpec):
        """Where a source's packed decode writes: (the pool of its
        matrices, the layout, the matrix row of each schema column)."""
        from ..native import PackedBufferPool

        layout = packed_raw_layout(spec.raw_schema.types)
        n_rows = len(layout) + 1
        cap = spec.capacity
        pool = self._ingest_pools.get(spec.name)
        if (
            pool is None or pool.n_rows != n_rows or pool.capacity != cap
        ):
            pool = PackedBufferPool(n_rows, cap)
            # armed debug.buffersanitizer: released slots get poisoned
            pool.sanitizer = self.buffer_sanitizer
            self._ingest_pools[spec.name] = pool
        col_rows = self._ingest_col_rows.get(spec.name)
        if col_rows is None:
            index = {c: i for i, (c, _k) in enumerate(layout)}
            col_rows = [index[c.name] for c in spec.schema.columns]
            self._ingest_col_rows[spec.name] = col_rows
        return pool, layout, col_rows

    def _decode_pass(
        self, decoder, data, staged: "StagedBatch", col_rows: List[int],
        valid_row: int, lines: Optional[int] = None,
    ) -> None:
        """One call of the packed decoder into ``staged``'s matrix, from
        its next free row slot on and against its base. With ``lines``
        (a pass before the poll) ``data`` is that many whole lines and
        the call owns exactly as many slots; without (the poll's own
        call) ``data`` is the batch's blob, decoded from the byte the
        passes stopped at into every slot that is left, so the tail is
        zeroed, and its malformed lines are counted (a pass's are the
        slots it owns less its rows: counted with the batch, once the
        poll has said which passes are in it)."""
        start = staged.nbytes if lines is None else 0
        t0 = time.perf_counter()
        rows, consumed = decoder.decode_packed(
            memoryview(data)[start:] if start else data, staged.matrix,
            col_rows, valid_row, staged.base_ms,
            max_rows=lines, slot=staged.slots,
        )
        staged.seconds += time.perf_counter() - t0
        if lines is None:
            self._count_jsonl_malformed(data, start, consumed, rows)
        else:
            staged.slots += lines
        staged.nbytes += consumed
        staged.rows += rows
        staged.bad_ts += decoder.last_bad_timestamps

    def decode_ahead(
        self, data, lines: int, base_ms: int, source: Optional[str] = None,
    ) -> bool:
        """Decode ``lines`` whole, non-blank lines of the source's NEXT
        batch before its poll, into that batch's pooled matrix at the
        next free row slot: what the paced host does with the lines a
        socket source shows it while it waits for its interval.
        ``base_ms``: the base the caller expects the batch to get (the
        first pass fixes it; when the poll comes at another,
        ``encode_json_bytes`` decodes the batch again, whole). ``data``
        must stay as it is until the call returns. False, with nothing
        decoded, when the matrix has no room for the lines. The caller's next
        ``encode_json_bytes(..., ahead_bytes=)`` finishes the batch;
        ``drop_decode_ahead`` gives the matrix back without one."""
        spec = self._spec(source)
        pool, layout, col_rows = self._ingest_plan(spec)
        staged = self._staged.get(spec.name)
        if staged is not None and staged.pool is not pool:
            self.drop_decode_ahead(spec.name)
            staged = None
        if lines > spec.capacity - (staged.slots if staged else 0):
            return False
        if staged is None:
            staged = self._staged[spec.name] = StagedBatch(
                pool, pool.acquire(), base_ms
            )
        try:
            self._decode_pass(
                self._native_decoder(spec), data, staged, col_rows,
                len(layout), lines,
            )
        except Exception:
            self.drop_decode_ahead(spec.name)
            raise
        staged.passes.append(
            (staged.nbytes, staged.slots, staged.rows, staged.bad_ts)
        )
        return True

    def decode_ahead_cursor(self, source: Optional[str] = None
                            ) -> Tuple[int, int]:
        """(bytes, lines) of the source's next batch that
        ``decode_ahead`` has decoded so far."""
        staged = self._staged.get(self._spec(source).name)
        return (staged.nbytes, staged.slots) if staged else (0, 0)

    def drop_decode_ahead(self, source: Optional[str] = None) -> None:
        """Give back the matrix ``decode_ahead`` was filling (every
        source's when none is named): its lines are decoded again by
        the batch that is handed them."""
        for name in [self._spec(source).name] if source else \
                list(self._staged):
            staged = self._staged.pop(name, None)
            if staged is not None:
                staged.pool.release(staged.matrix)

    def _encode_packed_native(
        self, decoder, data: bytes, base_ms: int, spec: SourceSpec,
        fmt: str, to_device: bool, ahead_bytes: int = 0,
    ) -> "PackedRaw":
        """The allocation-free hot path: acquire a pooled, persistent,
        64-byte-aligned matrix already laid out as the packed H2D
        transfer and let the decoder shards write straight into it.
        The returned PackedRaw carries its pool slot; dispatch hands it
        to the PendingBatch, which releases it when the batch lands (or
        abandons) — never while the device step may still be reading
        the zero-copied buffer.

        ``ahead_bytes``: how many of ``data``'s leading bytes are the
        lines ``decode_ahead`` was given since the last call, in its
        order (0: none, and what it decoded is decoded again here, as
        it is when the passes decoded against another base than
        ``base_ms``). The passes that end inside them are kept; the
        rest of ``data`` is decoded from their last slot on. The
        matrix is the one this call alone would have written: the same
        valid rows in the same order with the same cells, every other
        slot zero (where a line is malformed its empty slot lies at the
        end of its pass)."""
        pool, layout, col_rows = self._ingest_plan(spec)
        names = [c for c, _k in layout]
        valid_row = len(layout)
        staged = self._staged.pop(spec.name, None)
        if staged is not None and staged.pool is not pool:
            staged.pool.release(staged.matrix)
            staged = None
        # (one expression, so that the race lint sees the pool's matrix
        # reach the hand-off below: analysis/racecheck.py)
        mat = pool.acquire() if staged is None else staged.matrix
        if staged is None:
            staged = StagedBatch(pool, mat, base_ms)
        ahead_ms, ahead_passes = staged.seconds * 1000.0, len(staged.passes)
        try:
            # a poll that cut before the passes' end is right: the
            # passes past its cut go, and their lines come with a later
            # one. So is a poll in another second than the passes
            # expected (a second's edge between the deadline and the
            # poll): their time cells are on the wrong base, all go
            if fmt != "jsonl" or staged.base_ms != base_ms:
                ahead_bytes = 0
            staged.keep(min(ahead_bytes, len(data)))
            staged.base_ms = base_ms
            ahead_rows = staged.rows
            self._count_ingest(
                "malformed_rows", staged.slots - ahead_rows, malformed=True
            )
            # the interval Decode_RowsPerSec times, as a span
            with _trace_span("native-decode"):
                if fmt == "kafka-v2":
                    t0 = time.perf_counter()
                    staged.rows, kstats = decoder.decode_kafka_packed(
                        data, mat, col_rows, valid_row, base_ms,
                        max_rows=spec.capacity,
                    )
                    staged.seconds += time.perf_counter() - t0
                    self._count_ingest(
                        "malformed_rows", kstats["malformed"], malformed=True
                    )
                    self._count_ingest("CorruptBatch", kstats["corrupt_batches"])
                    # records that arrived without a row slot are LOST data
                    # (a producer batch larger than the flow capacity) —
                    # loud, never silent
                    self._count_ingest(
                        "kafka_overflow_rows", kstats["overflow_dropped"]
                    )
                else:
                    self._decode_pass(
                        decoder, data, staged, col_rows, valid_row
                    )
        except Exception:
            pool.release(mat)
            raise
        rows = staged.rows
        self._count_ingest("bad_timestamps", staged.bad_ts)
        self.last_decoder_path = "native-sharded"
        self._decode_shards = decoder.last_shards
        if staged.seconds > 0 and rows:
            # the batch's rows over all its passes, the dropped ones'
            # time included: the decoder's speed, wherever it ran
            self._decode_rows_per_sec = rows / staged.seconds
        self.decode_ahead_stats[spec.name] = (
            ahead_rows, rows, ahead_ms, ahead_passes
        )
        # rows the decoder doesn't own (Properties/SystemProperties):
        # the pool hands back dirty matrices, so (re)fill them per call
        # — one vectorized fill per extra row, not a fresh allocation
        schema_rows = set(col_rows)
        for i, cname in enumerate(names):
            if i in schema_rows:
                continue
            if (
                cname == ColumnName.RawPropertiesColumn
                and self.properties_enabled
            ):
                mat[i].fill(self._properties_id(base_ms))
            else:
                mat[i].fill(0)
        if self.state_filter_ingest:
            key = self.state_partition_key
            kv = None
            if key in names:
                krow = mat[names.index(key)]
                kind = dict(layout).get(key)
                kv = krow.view(np.float32) if kind == "f32" else krow
            new_valid = self._filter_unowned(
                kv, mat[valid_row] != 0, spec
            )
            mat[valid_row] = new_valid.astype(np.int32)
        # under a mesh the matrix goes to the chips here, a block of
        # its capacity axis each: the mesh's own share of ``decode`` (the
        # host's time in the call; jax completes the transfers behind it)
        sharding = self._packed_sharding()
        with _trace_span("shard-put") if sharding is not None \
                else contextlib.nullcontext():
            pr = pack_from_matrix(
                mat, layout, to_device=to_device, sharding=sharding
            )
        # dx-race: owner-handoff pool slot rides the PackedRaw into the
        # PendingBatch, which releases it on land/abandon
        pr._ingest_pool = (pool, mat)
        return pr

    def encode_columns(
        self, np_cols: Dict[str, np.ndarray], n: int,
        source: Optional[str] = None,
    ) -> TableData:
        spec = self._spec(source)
        cap = spec.capacity
        fill_dtype = {"double": jnp.float32, "boolean": jnp.bool_}
        cols = {}
        for c, t in spec.raw_schema.types.items():
            if c in np_cols:
                a = np_cols[c]
                pad = np.zeros(cap, dtype=a.dtype)
                pad[: min(n, cap)] = a[: min(n, cap)]
                cols[c] = self._put_rows(pad)
            elif (
                c == ColumnName.RawPropertiesColumn and self.properties_enabled
            ):
                cols[c] = self._put_rows(jnp.full(
                    (cap,),
                    self._properties_id(int(time.time()) * 1000),
                    jnp.int32,
                ))
            else:
                cols[c] = self._put_rows(
                    jnp.zeros((cap,), fill_dtype.get(t, jnp.int32))
                )
        valid = np.zeros(cap, dtype=bool)
        valid[: min(n, cap)] = True
        if self.state_filter_ingest and n > 0:
            key = self.state_partition_key
            src = cols.get(key)
            valid = self._filter_unowned(
                np.asarray(src) if src is not None else None, valid, spec
            )
        return TableData(cols, self._put_rows(valid))

    def _empty_raw(self, spec: SourceSpec) -> Union[TableData, PackedRaw]:
        """A batch of no rows, in the form the step takes this source's
        batches in (``_raw_packed``): one trace signature, and under a
        mesh one sharding, whether the source brought rows or not."""
        if not self._raw_packed[spec.name]:
            return self.encode_columns({}, 0, source=spec.name)
        layout = packed_raw_layout(spec.raw_schema.types)
        return pack_from_matrix(
            np.zeros((len(layout) + 1, spec.capacity), np.int32), layout,
            sharding=self._packed_sharding(),
        )

    def _filter_unowned(self, key_vals, valid: np.ndarray,
                        spec: SourceSpec) -> np.ndarray:
        """Key-routed ingest (``process.state.filteringest``): zero the
        validity of rows whose key hashes to a partition this replica
        does NOT own, so N replicas fed the same stream process each
        key exactly once between them (the consumer-group contract
        restated over key-range partitions). Dropped rows count into
        ``State_IngestFiltered_Count``. No-op unless armed AND the
        source's raw schema carries the conf'd partition key."""
        key = self.state_partition_key
        if not key or key not in spec.raw_schema.types:
            if spec.name not in self._filter_warned:
                self._filter_warned.add(spec.name)
                logger.warning(
                    "state.filteringest armed but source %r has no "
                    "partition-key column %r; NOT filtering",
                    spec.name, key,
                )
            return valid
        if key_vals is None:
            return valid
        from .statepartition import partition_ids

        pids = partition_ids(
            np.asarray(key_vals), self.state_partitions,
            spec.raw_schema.types[key], dictionary=self.dictionary,
        )
        mask = np.isin(pids, np.asarray(self.state_owned, dtype=np.int64))
        valid = np.asarray(valid)
        dropped = int(np.count_nonzero(valid & ~mask))
        if dropped:
            self.state_stats["IngestFiltered_Count"] = (
                self.state_stats.get("IngestFiltered_Count", 0) + dropped
            )
        return valid & mask

    def _debug_guard(self):
        """Context armed by the ``process.debug`` conf block around the
        jitted step: ``jax.debug_nans`` re-runs de-optimized on the
        first NaN and names the producing primitive; tracer-leak
        checking raises when user code lets a tracer escape the traced
        step. Both sanitize UDF-bearing test jobs — off (a no-op stack)
        in production confs."""
        import contextlib

        stack = contextlib.ExitStack()
        if self.debug_nans:
            stack.enter_context(jax.debug_nans(True))
        if self.debug_tracer_leaks:
            stack.enter_context(jax.checking_leaks())
        return stack

    def dispatch_batch(
        self,
        raw: Union[TableData, Dict[str, TableData]],
        batch_time_ms: Optional[int] = None,
    ) -> "PendingBatch":
        """Queue one micro-batch on the device and return a handle.

        ``raw``: one TableData (routed to the primary source) or a dict
        {source name -> TableData}; sources absent from the dict run with
        an empty batch, so independent streams may tick at their own pace.

        The device runs asynchronously: the caller can encode/dispatch
        the next batch (or run sinks for the previous one) while this
        batch computes — the P6 fetch/process overlap, done with the
        device stream instead of Spark's receiver threads. Collect the
        results with ``PendingBatch.collect()``.
        """
        t0 = time.time()
        if batch_time_ms is None:
            batch_time_ms = int(time.time() * 1000)
        if isinstance(raw, (TableData, PackedRaw)):
            raw = {self.primary: raw}
        for name in raw:
            if name not in self.specs:
                raise EngineException(
                    f"dispatch_batch got unknown source {name!r} "
                    f"(declared: {list(self.specs)})"
                )
        raw = {
            name: raw.get(name) or self._empty_raw(spec)
            for name, spec in self.specs.items()
        }
        self._note_raw_forms(raw)
        # per-interval UDF refresh hooks; state changes re-trace the step
        # (CommonProcessorFactory.scala:351-353 onInterval invocation).
        # A throwing hook skips its refresh (previous trace keeps
        # serving) and surfaces as the UdfRefreshError metric rather
        # than killing the batch loop.
        from ..udf import UdfRegistry

        registry = UdfRegistry(self.udfs)
        if registry.refresh(batch_time_ms):
            self._build_pipeline(self.output_datasets)
            self._jit_step()  # the old jit closed over the old pipeline
            # the rebuild discards the compiled step: the re-trace the
            # next dispatch pays is real work the steady-state model
            # does not include
            self.retrace_count += 1
            self._retrace_mark = None
        if registry.last_errors:
            self.udf_refresh_errors += len(registry.last_errors)
        # whole-second base so device absolute-time math is exact
        new_base_ms = (batch_time_ms // 1000) * 1000
        if self._base_ms is None:
            with self._device_state_lock:
                self._base_ms = new_base_ms
        delta_ms = new_base_ms - self._base_ms
        if abs(delta_ms) > 2**31 - 1:
            # a restored checkpoint (or clock jump) more than ~24.8 days
            # out: every ring row is long past any window horizon, and
            # the int32 rebase would overflow — start from clean rings.
            # Published under the device-state lock so a checkpoint on
            # the landing thread never snapshots mid-swap rings.
            fresh = self._fresh_window_state()
            with self._device_state_lock:
                self.window_buffers = fresh
            delta_ms = 0
        # the landing thread's checkpoint reads base/counter under this
        # lock; writes pair with it so a snapshot is never torn
        with self._device_state_lock:
            self._base_ms = new_base_ms
            counter = jnp.asarray(self._slot_counter, jnp.int32)
            self._slot_counter += 1

        base_s = jnp.asarray(new_base_ms // 1000, jnp.int32)
        now_rel_ms = jnp.asarray(batch_time_ms - new_base_ms, jnp.int32)

        refdata_tables = {n: t for n, (_, t) in self.refdata.items()}
        # string-op dictionary tables: refreshed AFTER this batch's encode
        # (so they cover every id the batch can contain), cached until the
        # dictionary grows; growth past table capacity retraces the step
        aux = self.aux_tables.tables()
        self._raw_devices = {n: _device_count(r) for n, r in raw.items()}
        # pooled ingest buffers riding this batch's raw inputs: owned by
        # the PendingBatch until its landing (or abandon) — the step
        # zero-copies them on the CPU backend, so early reuse would be
        # a read of freed-for-overwrite memory
        ingest_buffers = [
            r._ingest_pool for r in raw.values()
            if getattr(r, "_ingest_pool", None) is not None
        ]
        # child span of the host's "dispatch" when a batch trace is
        # active (obs/tracing.py); a no-op under LiveQuery drivers
        try:
            with _trace_span("device-enqueue"), self._debug_guard(), \
                    self._device_state_lock:
                out_datasets, new_rings, new_state, counts_vec = self._step(
                    raw, self.window_buffers, self.state_data, refdata_tables,
                    base_s, now_rel_ms, counter,
                    jnp.asarray(delta_ms, jnp.int32),
                    aux,
                )
                # carry device state forward without materializing — the
                # next dispatch may consume these handles before this
                # batch collects
                self.window_buffers = new_rings
                self.state_data = new_state
        except Exception:
            # the step never launched: the pool slots are safe to reuse
            for pool, mat in ingest_buffers:
                pool.release(mat)
            raise
        handle = PendingBatch(
            self, self.pipeline, out_datasets, new_state, counts_vec,
            batch_time_ms, new_base_ms, t0,
            out_names=list(self.output_datasets),
            target_names=[s.target for s in self.specs.values()],
        )
        # this batch's pooled ingest matrices: released by the handle
        # when the batch lands/abandons, never before the step is done
        # dx-race: owner-handoff pool slots ride the PendingBatch; its
        # collect/abandon path is the unique releaser
        handle._ingest_buffers = ingest_buffers
        # begin the device->host result copies NOW (async enqueue, free):
        # by the time collect() runs — typically one pipelined iteration
        # later — the data has already crossed the boundary, so collect
        # pays no synchronous transport round trip.
        handle.start_fetch()
        return handle

    def _note_raw_forms(self, raw: Dict[str, object]) -> None:
        """The form each source's batch came in. One that differs from
        what the step was built for (a source of a non-local type that
        hands rows and not bytes, a caller with tables of its own) is a
        new trace signature; under a mesh it is also another sharding of
        that argument, which a jit fixes when it is made: the step is
        jitted again for the forms it is handed, and counted as the
        re-trace it is."""
        packed = {n: isinstance(r, PackedRaw) for n, r in raw.items()}
        if packed == self._raw_packed:
            return
        self._raw_packed = packed
        if self.mesh is not None:
            self._jit_step()
            self.retrace_count += 1
            self._retrace_mark = None

    def process_batch(
        self,
        raw: Union[TableData, Dict[str, TableData]],
        batch_time_ms: Optional[int] = None,
    ) -> Tuple[Dict[str, List[dict]], Dict[str, float]]:
        """Run one micro-batch; returns (materialized datasets, metrics).

        reference: processDataset (CommonProcessorFactory.scala:333-399)
        incl. the metric names it emits (:344-379).
        """
        return self.dispatch_batch(raw, batch_time_ms).collect()

    # -- retrace accounting ------------------------------------------------
    def _step_cache_size(self) -> Optional[int]:
        try:
            return int(self._step._cache_size())
        except Exception:  # noqa: BLE001 — accounting only, never fails a batch
            return None

    def drain_retraces(self) -> int:
        """Jit re-traces since the last drain: explicit rebuilds
        (UDF refresh) plus jit-cache growth past the mark. The initial
        trace is expected — only growth BEYOND the accounted cache size
        counts (a dictionary-table resize or an input-shape change that
        silently re-traced the step)."""
        cur = self._step_cache_size()
        if cur is not None:
            if self._retrace_mark is None:
                self._retrace_mark = cur  # first trace: modeled, not drift
            elif cur > self._retrace_mark:
                self.retrace_count += cur - self._retrace_mark
                self._retrace_mark = cur
        n = self.retrace_count
        self.retrace_count = 0
        return n

    def refresh_mesh_collectives(self) -> None:
        """(Re)census the compiled mesh step's collectives — the
        observed side of the DX51x ICI conformance ratios. Called
        lazily at first collect (the step has compiled by then, so
        with a persistent compilation cache the extra ``compile()``
        deserializes) and again after any re-trace (the new program
        may partition differently — exactly what DX511 watches)."""
        if self.mesh is None or not self.mesh_observe:
            self.mesh_collectives = None
            return
        try:
            from ..dist.mesh import summarize_compiled

            lowered = self._step.lower(*self._step_input_avals())
            self.mesh_collectives = summarize_compiled(lowered.compile())
        except Exception as e:  # noqa: BLE001 — observability never fails a batch
            logger.warning("mesh collective census unavailable: %s", e)
            self.mesh_collectives = False  # don't retry every batch

    # -- AOT compile surface (the zero-cold-start path) --------------------
    def _source_raw_form(self, spec: SourceSpec) -> str:
        """The raw transfer form (and therefore trace signature) the
        AOT warm must use for this source — same rule as production
        dispatch (module-level ``source_raw_form``)."""
        return source_raw_form(spec.conf.get("inputtype"))

    def _warm_raw(self) -> Dict[str, Union[TableData, PackedRaw]]:
        """Zero-filled per-source raw batches in the exact form (and
        therefore trace signature) production dispatch will use."""
        return {
            name: self._empty_raw(spec) for name, spec in self.specs.items()
        }

    def _step_input_avals(self) -> tuple:
        """The 9-argument aval tuple of the fused step — the trace
        signature the jit cache keys on, derived from this processor's
        own device state (so it can never drift from what dispatch
        passes)."""
        def aval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        tm = jax.tree_util.tree_map
        raw = {n: tm(aval, r) for n, r in self._warm_raw().items()}
        rings = tm(aval, self.window_buffers)
        state = tm(aval, self.state_data)
        refdata = {n: tm(aval, t) for n, (_s, t) in self.refdata.items()}
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        aux = tm(aval, self.aux_tables.tables())
        return (raw, rings, state, refdata, scalar, scalar, scalar, scalar,
                aux)

    def derive_compile_entries(self) -> List[dict]:
        """Every jit entry point this processor can ever dispatch (the
        fused step, and nothing else), as manifest-shaped dicts (entry
        name, aval signature, static args, donation pattern) — the
        runtime side of the DX603 byte-exactness contract: the compile
        analyzer derives the same list statically from the flow
        config."""
        return [step_compile_entry(self._step_input_avals())]

    def _aot_warm(self) -> None:
        """AOT-compile every manifest entry at init instead of first
        dispatch: run one zero-filled batch through the jitted step
        (the exact production trace signature, so the first real
        dispatch hits a warm jit cache). The XLA compiles inside the
        warm resolve from the persistent compilation cache, and with ``process.compile.cacheurl`` newly
        compiled entries are pushed back through ``objstore://`` so
        the NEXT start (restart, preemption recovery, scale-out
        replica) deserializes instead of compiling. A warm failure
        never kills init: the flow falls back to
        compile-at-first-dispatch, loudly."""
        t0 = time.time()
        try:
            # manifest-vs-runtime drift check (the runtime face of
            # DX603): a manifest generated for a different flow shape
            # still warms — the signatures it promised just won't all
            # be the ones dispatch uses, which the drift count surfaces
            entries = self.derive_compile_entries()
            shipped = {
                e.get("entry"): e
                for e in (self.compile_manifest or {}).get("entries", [])
                if isinstance(e, dict)
            }
            drift = sum(
                1 for e in entries
                if e["entry"] not in shipped
                or shipped[e["entry"]].get("avals") != e["avals"]
                or list(shipped[e["entry"]].get("donate") or [])
                != list(e["donate"])
            )
            if drift:
                logger.warning(
                    "compile manifest drift (DX603): %d of %d entries "
                    "disagree with this flow's lowering — regenerate "
                    "the manifest", drift, len(entries),
                )
                self.compile_stats["ManifestDrift_Count"] = float(drift)
            # compile the fused step at the exact production trace
            # signature (zero-filled batch, production raw form). The
            # warm batch is NEVER collected: collect_tables() would
            # overwrite the state tables' standby snapshot with
            # warm-derived rows — only the counts sync (which completes
            # the device work) runs.
            handle = self.dispatch_batch(self._warm_raw(), batch_time_ms=0)
            handle.collect_counts()
            handle.abandon()
            self._aot_warmed = True
        except Exception:  # noqa: BLE001 — warm must never fail the flow
            logger.exception("AOT warm failed; first dispatch will compile")
        finally:
            # the warm batch must leave no trace in device state
            self.reset_state()
        if self._compile_cache is not None:
            self._compile_cache.push()
        self._warm_step_mark = self._step_cache_size()
        self.compile_stats["ColdStart_Ms"] = (time.time() - t0) * 1000.0

    def commit(self) -> None:
        """Commit state-table pointers after sinks succeed."""
        for st in self.state_tables.values():
            st.persist()

    def step_devices(self) -> list:
        """The devices the step runs on: every mesh device, else the
        default device."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return [jax.local_devices()[0]]

    def placement(self) -> Dict[str, object]:
        """Where this processor's device data lives, as jax reports it:
        how many devices hold each windowed table's state (ring or
        partial aggregates) and each source's last raw batch (``sharding.device_set``; 0 = a host array the step
        call transfers itself), and the allocator's bytes in use on
        every device the step runs on."""
        with self._device_state_lock:
            # by windowed table: its raw-row ring, or the partial
            # aggregates of the views over its windows (the fewest)
            rings: Dict[str, int] = {}
            for name, b in self.window_buffers.items():
                ws = self.window_states.get(name)
                table = ws.table if ws is not None else name
                rings[table] = min(
                    rings.get(table, _device_count(b)), _device_count(b)
                )
        devices = self.step_devices()
        return {
            "stepDevices": len(devices),
            "ringDevices": rings,
            "rawDevices": dict(self._raw_devices),
            "deviceBytesInUse": [
                int((d.memory_stats() or {}).get("bytes_in_use") or 0)
                for d in devices
            ],
        }

    def device_memory_stats(self) -> Optional[Dict[str, int]]:
        """The device allocator's live watermark — ``bytes_in_use`` /
        ``peak_bytes_in_use`` from ``memory_stats()``, the per-chip
        MAXIMUM over the devices the step runs on (a chip runs out of
        HBM alone, so the fullest one is the watermark DX522 judges).
        None when the backend doesn't report (CPU) — the host's Hbm_*
        sampler and the DX522 conformance check then stay silent."""
        per_device = [d.memory_stats() for d in self.step_devices()]
        if not all(per_device):
            return None
        return {
            "bytes_in_use": max(
                int(s.get("bytes_in_use") or 0) for s in per_device
            ),
            "peak_bytes_in_use": max(
                int(s.get("peak_bytes_in_use") or s.get("bytes_in_use") or 0)
                for s in per_device
            ),
        }


@jax.jit
def _slot_row(part: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """One slot's row of a [slots, groups] partial: one program a shape,
    whichever slot (a static index would compile one a slot)."""
    return jax.lax.dynamic_index_in_dim(part, slot, 0, keepdims=False)


def _snapshot_partials(
    buf: WindowPartials, counter: int, since: Optional[int]
) -> Dict[str, object]:
    """Host copy of one view's partial aggregates: its head, and the rows
    of the live slots that batches ``since`` .. ``counter`` - 1 changed
    (a slot's generation is the counter of the last batch that changed
    it: an event-time state's own ``slot_gen``; of a processing-time
    window the batch of counter g wrote slot g mod K, once. Every live
    slot when ``since`` is None or not a counter this state has passed).
    ``rows`` names the slots brought, oldest change first."""
    k = buf.slots
    live = np.array(buf.slot_live, copy=True)
    if buf.slot_gen is None:
        gens = np.full(k, -1, np.int32)
        written = np.arange(max(0, counter - k), counter)
        gens[written % k] = written
    else:
        gens = np.array(buf.slot_gen, copy=True)
    first = since if since is not None and 0 <= since <= counter else 0
    rows = np.flatnonzero(live & (gens >= first))
    rows = rows[np.argsort(gens[rows], kind="stable")]
    if len(rows) == k:
        whole = {n: np.array(a, copy=True) for n, a in buf.parts.items()}
        parts = {n: a[rows] for n, a in whole.items()}
    else:
        parts = {
            n: np.stack([
                np.array(_slot_row(a, jnp.asarray(r, jnp.int32)), copy=True)
                for r in rows
            ]) if len(rows) else np.zeros((0, buf.groups), a.dtype)
            for n, a in buf.parts.items()
        }
    return {
        "slots": k, "groups": buf.groups,
        "keys": [np.array(a, copy=True) for a in buf.keys],
        "used": np.array(buf.used, copy=True),
        "slot_ts": np.array(buf.slot_ts, copy=True),
        "slot_live": live, "slot_gen": gens,
        "first_gen": first, "rows": rows, "parts": parts,
    }


def _restore_partials(
    like: WindowPartials, saved: Optional[Dict[str, object]]
) -> Optional[WindowPartials]:
    """A loaded checkpoint's partial aggregates (``parts`` whole, [slots,
    groups]) as device state shaped like ``like``; None when the flow's
    shapes have changed since. Copies: the state is the step's donated
    argument (see ``restore_window_state``)."""
    if saved is None:
        return None
    fields = [
        *zip(like.keys, saved.get("keys", ())),
        (like.used, saved.get("used")),
        (like.slot_ts, saved.get("slot_ts")),
        (like.slot_live, saved.get("slot_live")),
        *([(like.slot_gen, saved.get("slot_gen"))]
          if like.slot_gen is not None else []),
        *((a, saved.get("parts", {}).get(n)) for n, a in like.parts.items()),
    ]
    if len(saved.get("keys", ())) != len(like.keys) \
            or set(saved.get("parts", {})) != set(like.parts) or any(
        got is None or got.shape != a.shape or got.dtype != a.dtype
        for a, got in fields
    ):
        return None
    put = lambda a: jnp.array(a, copy=True)  # noqa: E731
    return WindowPartials.of(
        tuple(put(a) for a in saved["keys"]), put(saved["used"]),
        {n: put(a) for n, a in saved["parts"].items()},
        put(saved["slot_ts"]), put(saved["slot_live"]),
        None if like.slot_gen is None else put(saved["slot_gen"]),
    )


def _device_count(tree) -> int:
    """Fewest devices any jax array of ``tree`` is laid out over
    (``sharding.device_set``); 0 when the tree holds host arrays only."""
    return min(
        (len(x.sharding.device_set)
         for x in jax.tree_util.tree_leaves(tree)
         if isinstance(x, jax.Array)),
        default=0,
    )


def _host_sort(rows: List[dict], order: List[Tuple[str, bool]]) -> None:
    """Stable multi-key in-place sort matching SQL semantics: ascending
    puts NULLs first, descending puts them last (Spark defaults).
    Applied least-significant key first so significance composes."""
    for key, asc in reversed(order):
        def kf(r, k=key):
            v = r.get(k)
            # the second element only compares within equal null-flags,
            # so the placeholder never meets a real value
            return (v is not None, v if v is not None else 0)

        rows.sort(key=kf, reverse=not asc)


def _host_table_nbytes(t: TableData) -> int:
    return sum(a.nbytes for a in t.cols.values()) + t.valid.nbytes


@dataclass
class BatchCounts:
    """The parsed counts vector — everything the cheap blocking sync
    (``collect_counts``) learns about a batch: per-output valid row
    counts, the dropped-group/join overflow slots, and per-source
    projected input counts. A few hundred bytes on the wire; the output
    tables themselves stream in the background and resolve later via
    ``collect_tables``."""

    counts: np.ndarray  # the raw packed vector (nbytes = sync cost)
    dataset_counts: Dict[str, int]
    dropped_groups: Dict[str, int]
    dropped_joins: Dict[str, int]
    target_counts: Dict[str, int]


class PendingBatch:
    """An in-flight micro-batch: device work queued, results not yet
    fetched.

    One result path, on one chip as under a mesh: the packed
    ``counts_vec`` and the step's output tables, at their declared
    capacity, all start streaming device->host at dispatch;
    ``collect_counts()`` is the only BLOCKING device read — it resolves
    the counts vector (a few hundred bytes) and is the batch's sync
    point. ``collect_tables()`` then resolves the already-streaming
    table copies, slices each on the host to the count the sync
    learned, materializes rows and persists state — on the pipelined
    hosts' landing thread, so sinks ack out-of-band while the dispatch
    loop keeps feeding the device. ``collect()`` = counts + tables as
    plain row lists."""

    def __init__(
        self, proc: "FlowProcessor", pipeline, out_datasets, state,
        counts_vec, batch_time_ms: int, base_ms: int, t0: float,
        out_names: Optional[List[str]] = None,
        target_names: Optional[List[str]] = None,
    ):
        self.proc = proc
        # THIS batch's pipeline: a UDF onInterval refresh may rebuild
        # proc.pipeline before an in-flight batch collects; its outputs
        # must decode against the schemas of the step that produced them
        self.pipeline = pipeline
        # likewise the dataset-name order the step packed counts in — a
        # refresh can reorder/shrink proc.output_datasets mid-flight
        self.out_names = (
            list(out_names) if out_names is not None
            else list(proc.output_datasets)
        )
        self.target_names = (
            list(target_names) if target_names is not None
            else [s.target for s in proc.specs.values()]
        )
        self.out_datasets = out_datasets
        self.state = state  # THIS batch's state, for the A/B overwrite
        self.counts_vec = counts_vec
        self.batch_time_ms = batch_time_ms
        self.base_ms = base_ms
        self.t0 = t0
        # parsed counts vector, cached by collect_counts (the sync
        # point happens at most once per batch)
        self._counts: Optional[BatchCounts] = None
        # pooled ingest matrices this batch's raw inputs live in
        # (set by dispatch_batch); released exactly once, at landing or
        # abandon — the decode buffer pool's reuse gate
        self._ingest_buffers: List = []

    def _release_ingest(self) -> None:
        bufs, self._ingest_buffers = self._ingest_buffers, []
        for pool, mat in bufs:
            pool.release(mat)

    def abandon(self) -> None:
        """Mark a batch that will never be collected (window requeued
        after a failure): returns its pooled ingest matrices."""
        if self._ingest_buffers:
            # the step may still be consuming the zero-copied ingest
            # matrices; wait for device completion before the pool may
            # hand them to a new decode (failure path — rare, cheap)
            try:
                jax.block_until_ready(self.counts_vec)
            except Exception:  # noqa: BLE001 — a failed step frees its inputs
                pass
        self._release_ingest()

    def start_fetch(self) -> None:
        """Enqueue async device->host copies of everything collect()
        reads (counts + the output tables). Transport then overlaps the
        host's next-batch work instead of being paid as a blocking sync
        inside collect(). Transfer errors are NOT swallowed — they
        propagate to the batch loop for retry."""
        self.counts_vec.copy_to_host_async()
        for t in self.out_datasets.values():
            for a in (*t.cols.values(), t.valid):
                a.copy_to_host_async()

    def block_until_evaluated(self) -> None:
        """Wait for the device step to COMPLETE (rule evaluation done,
        state advanced) without transferring results — the honest
        'rules evaluated' timestamp, independent of result transport."""
        jax.block_until_ready(self.counts_vec)

    def collect_counts(self) -> BatchCounts:
        """The batch's ONLY blocking device read: resolve the packed
        counts vector (layout: input count, per-output counts,
        per-output overflow slots for groups then joins, per-source
        projected counts — a few hundred bytes, already streaming since
        dispatch) and parse it. Idempotent; the sync point is paid at
        most once per batch."""
        if self._counts is not None:
            return self._counts
        counts = np.asarray(self.counts_vec)
        # unpack in PACKING order (snapshotted at dispatch) — jax returns
        # dict pytrees with sorted keys, so iterating out_datasets may
        # not match the order the step packed counts in
        names = self.out_names
        tnames = self.target_names
        self._counts = BatchCounts(
            counts=counts,
            dataset_counts={
                n: int(counts[1 + i]) for i, n in enumerate(names)
            },
            dropped_groups={
                n: int(counts[1 + len(names) + i])
                for i, n in enumerate(names)
                if int(counts[1 + len(names) + i]) >= 0
            },
            dropped_joins={
                n: int(counts[1 + 2 * len(names) + i])
                for i, n in enumerate(names)
                if int(counts[1 + 2 * len(names) + i]) >= 0
            },
            target_counts={
                t: int(counts[1 + 3 * len(names) + i])
                for i, t in enumerate(tnames)
            },
        )
        return self._counts

    def collect(self) -> Tuple[Dict[str, List[dict]], Dict[str, float]]:
        """Synchronous result path: counts sync + table landing in one
        call, each ``collect_tables()`` batch as its plain row list."""
        datasets, metrics = self.collect_tables()
        return {n: b.rows() for n, b in datasets.items()}, metrics

    def collect_tables(self) -> Tuple[Dict[str, ColumnBatch], Dict[str, float]]:
        """Resolve the output tables streaming since dispatch and
        persist state; returns (one ColumnBatch an output, what sinks
        get; metrics). Every output crosses at its declared capacity and
        is sliced here to the count ``collect_counts`` learned (the
        device compacted the valid rows to the front).
        """
        proc = self.proc
        bc = self.collect_counts()
        counts = bc.counts
        dataset_counts = bc.dataset_counts
        dropped_groups = bc.dropped_groups
        dropped_joins = bc.dropped_joins
        target_counts = bc.target_counts
        names = self.out_names
        try:
            with _trace_span("device-fetch"):
                host_full = jax.device_get(self.out_datasets)
        finally:
            # host copies landed (or the fetch failed): this batch's
            # pooled ingest matrices (fully consumed by the step, which
            # completed at the counts sync) return to the pool
            self._release_ingest()
        # D2H accounting for this batch (Transfer_* metrics)
        d2h_bytes = counts.nbytes + sum(
            _host_table_nbytes(t) for t in host_full.values()
        )
        transferred_rows = sum(
            int(t.valid.shape[0]) for t in host_full.values()
        )
        host_tables: Dict[str, TableData] = {}
        for n, t in host_full.items():
            cnt = dataset_counts[n]
            host_tables[n] = TableData(
                {c: v[:cnt] if v.shape[:1] == t.valid.shape else v
                 for c, v in t.cols.items()},
                t.valid[:cnt],
            )

        # armed sanitizer: every landed host table is scanned for
        # sentinel leakage BEFORE materialization — a poisoned pool slot
        # showing through a sink payload is the use-after-release the
        # static pass (DX800/DX801) exists to prevent
        if proc.buffer_sanitizer is not None:
            for name, table in host_tables.items():
                proc.buffer_sanitizer.scan_table(name, table)

        datasets: Dict[str, ColumnBatch] = {}
        with _trace_span("materialize"):
            for name, table in host_tables.items():
                view = self.pipeline.view_by_name(name)
                finish = None
                if view is not None and view.host_order:
                    # ORDER BY over computed-string columns: the device
                    # has no id to sort by, so the ordering (and limit)
                    # applies to the materialized rows (planner
                    # host-order path), which the batch then builds
                    def finish(rows, order=view.host_order,
                               limit=view.host_limit):
                        _host_sort(rows, order)
                        return rows if limit is None else rows[:limit]
                datasets[name] = ColumnBatch(
                    table, self.pipeline.schema_of(name), proc.dictionary,
                    self.base_ms, finish=finish,
                )

        # persist state tables (A/B overwrite; persist() is the caller's
        # post-sink commit, see StreamingHost) — from THIS batch's state
        for sname, st in proc.state_tables.items():
            st.overwrite(self.state[sname], proc.dictionary)

        elapsed_ms = (time.time() - self.t0) * 1000.0
        metrics = {
            "Latency-Process": elapsed_ms,
            "BatchProcessedET": float(self.batch_time_ms),
        }
        for t, c in target_counts.items():
            metrics[f"Input_{t}_Events_Count"] = float(c)
        for n, c in dataset_counts.items():
            metrics[f"Output_{n}_Events_Count"] = float(c)
        for n, c in dropped_groups.items():
            metrics[f"Output_{n}_GroupsDropped"] = float(c)
        for n, c in dropped_joins.items():
            metrics[f"Output_{n}_JoinRowsDropped"] = float(c)
        # how often the columnar egress engages: rows handed to the sinks
        # as columns, and rows a schema sent through the per-row fallback
        fallback = sum(len(b) for b in datasets.values() if not b.columnar)
        metrics["Egress_Fallback_Rows"] = float(fallback)
        metrics["Egress_Columnar_Rows"] = float(
            sum(map(len, datasets.values())) - fallback
        )
        # drain host-side ingest counters accumulated since last collect
        if proc.ingest_stats:
            for k, v in proc.ingest_stats.items():
                if v:
                    metrics[f"Input_{k}_Count"] = float(v)
            proc.ingest_stats.clear()
        # ingest decode fast-path gauges (native/decoder.cpp): the
        # shard count in effect, the last measured decode rate, and
        # buffer-pool reuses since the last collect — the runtime face
        # of the BENCH decoder_rows_per_sec / shard-curve numbers
        if proc._decode_shards is not None:
            metrics["Decode_Shards"] = float(proc._decode_shards)
        if proc._decode_rows_per_sec is not None:
            metrics["Decode_RowsPerSec"] = float(proc._decode_rows_per_sec)
        if proc._ingest_pools:
            reuse = sum(
                p.take_reuse_count() for p in proc._ingest_pools.values()
            )
            if reuse:
                metrics["Decode_BufferReuse_Count"] = float(reuse)
        if proc.dictionary.overflow_count:
            metrics["Input_string_dictionary_overflow_Count"] = float(
                proc.dictionary.overflow_count
            )
            proc.dictionary.overflow_count = 0
        # on_interval hooks that threw since the last collect: their
        # refreshes were skipped (previous trace kept serving) — loud
        # in metrics, invisible to the batch loop
        if proc.udf_refresh_errors:
            metrics["UdfRefreshError"] = float(proc.udf_refresh_errors)
            proc.udf_refresh_errors = 0
        # jit re-traces since the last collect (refresh rebuilds +
        # cache-miss growth) — the conformance monitor's DX503 input
        retraces = proc.drain_retraces()
        if retraces:
            metrics["Retrace_Count"] = float(retraces)
        if proc.window_buffers:
            # window state on the device (rings and per-slot partial
            # aggregates, from the shapes) and, where a view keeps
            # partials, the slots inside its window this batch (the
            # counts vector's tail: one a view, the widest reported)
            metrics["Window_State_Bytes"] = float(proc.window_state_bytes())
            tail = bc.counts[1 + 3 * len(self.out_names)
                             + len(self.target_names):]
            n_live = len(proc.window_states)
            if n_live:
                metrics["Window_Slots_Live"] = float(max(tail[:n_live]))
            if proc.pipeline.event_tables:
                # event-time windows (runtime/timewindow.py): accepted
                # rows stamped over an interval before the batch's time,
                # rows the watermark refused, slots written
                late, too_late, wrote = tail[n_live:n_live + 3]
                metrics["Window_Late_Rows"] = float(late)
                metrics["Window_TooLate_Rows_Dropped"] = float(too_late)
                metrics["Window_Slots_Touched"] = float(wrote)
        if proc.mesh is not None:
            # the chips the step's own output lies on: a mesh conf that
            # silently stepped on one chip reads 1
            metrics["Mesh_Chips"] = float(_device_count(self.counts_vec))
        # observed mesh communication: the executed program's collective
        # census as per-batch series (the DX510/DX511 inputs). A
        # re-trace re-censuses — the new program may partition
        # differently, which is precisely the drift DX511 detects.
        if proc.mesh is not None and proc.mesh_observe:
            if proc.mesh_collectives is None or retraces:
                proc.refresh_mesh_collectives()
            mc = proc.mesh_collectives
            if mc:
                metrics["Mesh_ICI_Bytes"] = mc.wire_bytes(proc.mesh.size)
                metrics["Mesh_Reshard_Count"] = float(mc.op_count)
        # warm-start promise check (the DX604 input): the AOT warm left
        # the step's jit cache at _warm_step_mark; growth past it means
        # a dispatch compiled even though a warm start was promised
        if proc._aot_warmed and proc._warm_step_mark is not None:
            cur = proc._step_cache_size()
            if cur is not None and cur > proc._warm_step_mark:
                proc.compile_stats["WarmMiss_Count"] = (
                    proc.compile_stats.get("WarmMiss_Count", 0.0)
                    + float(cur - proc._warm_step_mark)
                )
                proc._warm_step_mark = cur
        # one-shot compile stats (cold-start ms, persistent-cache
        # hits/misses, warm misses)
        if proc._compile_cache is not None:
            hits, misses = proc._compile_cache.take_counts()
            if hits or misses:
                metrics["Compile_Cache_Hit_Count"] = float(hits)
                metrics["Compile_Cache_Miss_Count"] = float(misses)
            # ...and WHICH programs: one `compile` span each under the
            # batch that paid for them
            trace = _current_trace()
            for prog in proc._compile_cache.take_programs():
                if trace is not None:
                    trace.record(
                        "compile", prog["startTs"], prog["ms"],
                        fn=prog["fn"], cache=prog["cache"],
                    )
        if proc.compile_stats:
            for k, v in proc.compile_stats.items():
                metrics[f"Compile_{k}"] = float(v)
            proc.compile_stats.clear()
        # transfer accounting: bytes moved D2H for this batch and the
        # valid/transferred row ratio (1.0 = wire minimum)
        if names:
            valid_rows = sum(dataset_counts.values())
            metrics["Transfer_D2HBytes"] = float(d2h_bytes)
            metrics["Transfer_Efficiency"] = (
                valid_rows / transferred_rows if transferred_rows else 1.0
            )
        # partitioned-state accounting: the partition geometry this
        # replica runs (gauges) plus the deltas since the last collect
        # — load fallbacks (DX530/531), snapshot pushes/pulls through
        # the objstore mirror, the successor handoff cost, and rows the
        # key-routed ingest filter dropped as un-owned
        if proc.state_tables or proc.state_replica_count > 1:
            metrics["State_Partition_Count"] = float(proc.state_partitions)
            metrics["State_Partition_Owned"] = float(len(proc.state_owned))
        if proc.state_stats:
            for k, v in proc.state_stats.items():
                metrics[f"State_{k}"] = float(v)
            proc.state_stats.clear()
        # bytes the blocking counts-only sync moved — the whole
        # synchronous wire cost of the batch tail (everything else
        # streams in the background)
        metrics["Sync_CountsBytes"] = float(counts.nbytes)
        # sanitizer accounting: views guarded since the last collect,
        # and (only when nonzero — silence is health) poison hits
        if proc.buffer_sanitizer is not None:
            metrics.update(proc.buffer_sanitizer.drain_metric_deltas())
        return datasets, metrics
