"""Mesh-sharding analyzer: static SPMD partition plans with
runtime-validated ICI cost closed forms.

Sixth analysis tier (the ``--mesh [--chips=N]`` tier, DX7xx). The mesh
path runs the whole step as ONE GSPMD-partitioned program
(``dist/mesh.py``): rows shard over the ``data`` axis, window rings
shard their capacity dim, reference/state tables replicate, and
aggregation outputs replicate — XLA inserts the collectives. Nothing
until now *proved* a flow partitions under that layout or predicted
what the interconnect will cost. This tier does both, statically:

- it infers a **partition plan** from the production planner's
  ``StagePlan``/``JoinSite`` metadata: which axis every stage keeps its
  rows on (``data`` vs ``replicated``), where a resharding all-gather
  is forced (GROUP BY / JOIN / DISTINCT / ORDER BY / LIMIT stages pull
  their sharded inputs onto every chip; sharded OUTPUT views gather at
  the step boundary), and what each stage leaves resident per chip;
- it prices every reshard edge with **closed forms** (documented in
  ANALYSIS.md "Sharding model"): result bytes are exact functions of
  the static shapes (rows x column widths, group capacity G bounding
  grouped outputs, join fan-out F bounding join outputs), and wire
  bytes apply the ring-collective factors over chips N
  (``costmodel.allgather_wire_bytes`` et al.);
- it **cross-checks the model against a real lowering**: every stage
  body is lowered with ``jax.jit`` under a real ``Mesh`` +
  ``NamedSharding`` over ``jax.eval_shape`` avals and must contain ZERO
  collectives under its planned layout (sharded elementwise stages
  communicate nothing; collective stages with replicated inputs
  compute locally), and every reshard edge is lowered as an identity
  resharding kernel whose all-gather census must equal the closed form
  byte-for-byte — the DX2xx ``model == materialized bytes`` contract,
  applied to communication. A disagreement is DX790, an error.

The per-collective *result bytes* are chip-count-independent, so a
cross-check on an M-device mesh (M = min(chips, available devices))
validates the model at any requested ``--chips=N``; with fewer than two
devices the cross-check is skipped and DX791 says so.

The emitted **sharding-plan artifact** (``runtime_model()``) is
embedded into mesh jobs' generated confs by the S660 stage
(``datax.job.process.mesh.model``); at runtime the host's
``ConformanceMonitor`` compares it against the observed
``Mesh_ICI_Bytes`` / ``Mesh_Reshard_Count`` series (the census of the
actually-executed program's collectives, ``dist/mesh.py
collective_summary``) and fires DX510/DX511 ICI-drift events beside
the existing DX501-503.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..compile.planner import CompiledView, ViewSchema
from .costmodel import (
    allgather_wire_bytes,
    table_bytes,
    view_output_bytes,
)
from .deviceplan import (
    FlowDevicePlan,
    _ordered,
    _plan_from_gui,
    flow_plan_from_processor,
    table_struct,
)
from .diagnostics import Diagnostic, make
from .fleetcheck import DEFAULT_FLEET_CHIPS, FleetSpec

# default chip count for the mesh tier: the 8-device MULTICHIP slice
# the repo actually proves out (tier-1 cross-checks at --chips=8)
DEFAULT_MESH_CHIPS = DEFAULT_FLEET_CHIPS

# shard axes a stage's rows can live on (dist/mesh.py's 1-D data mesh)
AXIS_DATA = "data"
AXIS_REPLICATED = "replicated"

# compute-scaling classes for the DX704 cliff lint: "sharded" work
# shrinks 1/N, "collective" work shrinks 1/N plus wire cost, and
# "replicated" work is flat in N
SCALE_SHARDED = "sharded"
SCALE_COLLECTIVE = "collective"
SCALE_REPLICATED = "replicated"


# ---------------------------------------------------------------------------
# Report types
# ---------------------------------------------------------------------------
@dataclass
class ReshardEdge:
    """One forced layout transition: a ``data``-sharded table gathered
    onto every chip at a stage boundary."""

    table: str
    result_bytes: int  # full logical bytes of the gathered table
    wire_bytes: float  # ring all-gather wire cost at the plan's chips

    def to_dict(self) -> dict:
        return {
            "table": self.table,
            "collective": "all-gather",
            "resultBytes": self.result_bytes,
            "wireBytes": round(self.wire_bytes, 1),
        }


@dataclass
class MeshStage:
    """One stage of the partition plan."""

    name: str
    kind: str  # input | project | ring | window | state | refdata | group | union
    axis: str  # AXIS_DATA | AXIS_REPLICATED
    scaling: str  # SCALE_SHARDED | SCALE_COLLECTIVE | SCALE_REPLICATED
    rows: int
    hbm_bytes: int  # full logical residency (the DX2xx byte model)
    per_chip_bytes: int  # what one chip keeps resident at N chips
    reshards: List[ReshardEdge] = field(default_factory=list)
    # cross-check result: collective result bytes the real Mesh
    # lowering produced for this stage's edges (None = not lowered)
    lowered_bytes: Optional[int] = None
    detail: str = ""

    @property
    def ici_result_bytes(self) -> int:
        return sum(e.result_bytes for e in self.reshards)

    @property
    def ici_wire_bytes(self) -> float:
        return sum(e.wire_bytes for e in self.reshards)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "axis": self.axis,
            "scaling": self.scaling,
            "rows": self.rows,
            "hbmBytes": self.hbm_bytes,
            "perChipBytes": self.per_chip_bytes,
            "iciResultBytes": self.ici_result_bytes,
            "iciWireBytes": round(self.ici_wire_bytes, 1),
            "reshards": [e.to_dict() for e in self.reshards],
            "loweredBytes": self.lowered_bytes,
            "detail": self.detail,
        }


@dataclass
class MeshPlanReport:
    flow: str
    chips: int
    stages: List[MeshStage]
    diagnostics: List[Diagnostic]
    # True when every stage body and reshard edge was cross-checked
    # against a real Mesh lowering (>=2 devices were available)
    validated: bool = False

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.is_error]

    @property
    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if not d.is_error]

    @property
    def ok(self) -> bool:
        return not self.errors

    def codes(self) -> List[str]:
        return [d.code for d in self.diagnostics]

    def totals(self) -> dict:
        return {
            "iciResultBytesPerBatch": sum(
                s.ici_result_bytes for s in self.stages
            ),
            "iciWireBytesPerBatch": round(
                sum(s.ici_wire_bytes for s in self.stages), 1
            ),
            "reshardCount": sum(len(s.reshards) for s in self.stages),
            "perChipHbmBytes": sum(s.per_chip_bytes for s in self.stages),
            "chips": self.chips,
        }

    def mesh_dict(self) -> dict:
        """The sharding-plan portion (no diagnostics) — what the
        designer renders as the sharding table and the CLI's ``--json``
        report carries under ``mesh``."""
        return {
            "flow": self.flow,
            "chips": self.chips,
            "validated": self.validated,
            "stages": [s.to_dict() for s in self.stages],
            "totals": self.totals(),
            "latencyModel": self.latency_model(),
        }

    def latency_model(
        self, profile: Optional[dict] = None, source: str = "default",
    ) -> dict:
        """The wire-time axis of the sharding plan: the DX7xx collective
        wire bytes priced over the profile's ICI link bandwidth
        (per-stage and total ms). Like the device tier's latencyModel
        this is a roofline lower bound — the datasheet default profile
        unless a calibrated one is passed."""
        from .costmodel import transfer_time_ms

        if profile is None:
            from ..obs.calibrate import DEFAULT_PROFILE

            profile = DEFAULT_PROFILE.to_dict()
            source = "default"
        gbps = profile.get("ici_gbps")
        stages = [
            {
                "name": s.name,
                "iciMs": (
                    round(transfer_time_ms(s.ici_wire_bytes, gbps), 4)
                    if gbps else None
                ),
            }
            for s in self.stages
        ]
        total = transfer_time_ms(
            self.totals()["iciWireBytesPerBatch"], gbps
        )
        return {
            "profileSource": source,
            "iciGBps": gbps,
            "stages": stages,
            "totals": {
                "iciMs": round(total, 4) if total is not None else None,
            },
        }

    def to_dict(self) -> dict:
        from .diagnostics import REPORT_SCHEMA_VERSION

        return {
            "schemaVersion": REPORT_SCHEMA_VERSION,
            "ok": self.ok,
            "errorCount": len(self.errors),
            "warningCount": len(self.warnings),
            "diagnostics": [d.to_dict() for d in self.diagnostics],
            "mesh": self.mesh_dict(),
        }

    def runtime_model(self) -> dict:
        """The machine-readable sharding-plan artifact the S660
        generation stage embeds into mesh jobs' confs
        (``datax.job.process.mesh.model``) — the slice a running host
        checks its observed collective census against
        (``obs/conformance.py`` DX510/DX511)."""
        from .costmodel import mesh_runtime_model

        return mesh_runtime_model(
            self.totals(), [s.to_dict() for s in self.stages]
        )


# ---------------------------------------------------------------------------
# Partition-plan inference
# ---------------------------------------------------------------------------
def _is_collective_view(view: CompiledView) -> bool:
    """True when the stage's lowering needs its inputs whole on every
    chip: grouping/distinct sort, join gid sort or match matrix, a
    global ORDER BY / LIMIT prefix, a host-side finishing sort, a
    multi-branch union concat, or a Pallas-kernel UDF call (a custom
    call has no SPMD partitioning rule — the partitioner replicates
    it)."""
    p = view.plan
    if view.host_order:
        return True
    if p is None:
        return False
    return bool(
        p.grouped or p.joins or p.distinct or p.order_keys
        or p.limit is not None or p.union_branches > 1
        or p.unshardable_udfs
    )


def _replication_origin(view: CompiledView) -> Optional[str]:
    """The structural reason a stage cannot scale with N, if any: a
    global sort over the raw scope, a host-side finishing sort, or an
    unshardable custom-kernel UDF. Grouped sorts don't count — they
    sort the G-row group output, and the gather itself is modeled."""
    p = view.plan
    if view.host_order:
        return "host-side ORDER BY"
    if p is not None and p.order_keys and not p.grouped:
        return "device ORDER BY"
    if p is not None and p.unshardable_udfs:
        return (
            "Pallas kernel UDF "
            + "/".join(p.unshardable_udfs)
        )
    return None


def _view_model_bytes(view: CompiledView) -> int:
    return view_output_bytes(view.schema.types, view.plan, view.capacity)


def _per_chip(bytes_: int, axis: str, chips: int) -> int:
    if axis == AXIS_DATA and chips > 1:
        return int(math.ceil(bytes_ / chips))
    return int(bytes_)


@dataclass
class _EnvEntry:
    """One table visible to pipeline views: its schema, row capacity,
    planned axis and gatherable byte size."""

    schema: ViewSchema
    rows: int
    axis: str
    gather_bytes: int  # bytes an all-gather of this table moves


def _infer_plan(
    bundle: FlowDevicePlan, chips: int,
) -> Tuple[List[MeshStage], Dict[str, _EnvEntry]]:
    """Walk raw -> projections -> rings/windows -> state/refdata ->
    transform views, assigning each stage an axis and collecting the
    reshard edges the layout forces."""
    stages: List[MeshStage] = []
    env: Dict[str, _EnvEntry] = {}

    # raw ingest + per-source projection chains: rows shard end to end
    for source, views in bundle.projection_views.items():
        cap = bundle.raw_schemas[source][1]
        # a column a leaf, rows sharded, or the one packed matrix, its
        # capacity axis sharded: a chip holds its rows' share either way
        raw_bytes = bundle.raw_batch_bytes(source)
        stages.append(MeshStage(
            name=f"input:{source}", kind="input", axis=AXIS_DATA,
            scaling=SCALE_SHARDED, rows=cap, hbm_bytes=raw_bytes,
            per_chip_bytes=_per_chip(raw_bytes, AXIS_DATA, chips),
            detail="raw ingest batch (rows shard on arrival"
            + (", one packed matrix)" if bundle.raw_packed.get(source)
               else ")"),
        ))
        for v in views:
            b = _view_model_bytes(v)
            stages.append(MeshStage(
                name=v.name, kind="project", axis=AXIS_DATA,
                scaling=SCALE_SHARDED, rows=v.capacity, hbm_bytes=b,
                per_chip_bytes=_per_chip(b, AXIS_DATA, chips),
                detail="projection (elementwise, stays sharded)",
            ))
        target = bundle.target_of[source]
        schema = bundle.target_schemas[target]
        env[target] = _EnvEntry(
            schema, bundle.target_caps[target], AXIS_DATA,
            table_bytes(schema.types, bundle.target_caps[target]),
        )

    # window rings shard their capacity dim; the flattened window view
    # the pipeline reads inherits the data axis
    for table, slots in bundle.ring_slots.items():
        rows = slots * bundle.target_caps[table]
        schema = bundle.target_schemas[table]
        b = table_bytes(schema.types, rows)
        stages.append(MeshStage(
            name=f"ring:{table}", kind="ring", axis=AXIS_DATA,
            scaling=SCALE_SHARDED, rows=rows, hbm_bytes=b,
            per_chip_bytes=_per_chip(b, AXIS_DATA, chips),
            detail=f"{slots} slots x {bundle.target_caps[table]} rows, "
                   "capacity dim sharded",
        ))
    for vname, ws in bundle.pipeline.window_states.items():
        stages.append(MeshStage(
            name=f"window-state:{vname}", kind="ring", axis=AXIS_REPLICATED,
            scaling=SCALE_REPLICATED, rows=ws.groups,
            hbm_bytes=ws.state_bytes, per_chip_bytes=ws.state_bytes,
            detail=f"{ws.slots} slots x {ws.groups} groups of partial "
                   "aggregates (replicated)",
        ))
    for wname, (table, dur_s) in bundle.windows.items():
        if table not in bundle.ring_slots:
            continue
        rows = bundle.ring_slots[table] * bundle.target_caps[table]
        schema = bundle.target_schemas[table]
        b = table_bytes(schema.types, rows)
        env[wname] = _EnvEntry(schema, rows, AXIS_DATA, b)
        stages.append(MeshStage(
            name=wname, kind="window", axis=AXIS_DATA,
            scaling=SCALE_SHARDED, rows=rows, hbm_bytes=b,
            per_chip_bytes=_per_chip(b, AXIS_DATA, chips),
            detail=f"{dur_s:g}s window over {table} (sharded with the ring)",
        ))

    # state/refdata replicate (broadcast-join sides)
    for sname, (schema, cap) in bundle.state.items():
        b = table_bytes(schema.types, cap)
        env[sname] = _EnvEntry(schema, cap, AXIS_REPLICATED, b)
        stages.append(MeshStage(
            name=f"state:{sname}", kind="state", axis=AXIS_REPLICATED,
            scaling=SCALE_REPLICATED, rows=cap, hbm_bytes=b,
            per_chip_bytes=b,
            detail="accumulation table (replicated)",
        ))
    for rname, (schema, cap) in bundle.refdata.items():
        b = table_bytes(schema.types, cap)
        env[rname] = _EnvEntry(schema, cap, AXIS_REPLICATED, b)
        stages.append(MeshStage(
            name=f"refdata:{rname}", kind="refdata", axis=AXIS_REPLICATED,
            scaling=SCALE_REPLICATED, rows=cap, hbm_bytes=b,
            per_chip_bytes=b,
            detail="reference data (replicated)",
        ))

    # transform views
    for view in bundle.pipeline.views:
        p = view.plan
        kind = p.kind if p is not None else "project"
        sources = [s for s in (p.sources if p else ()) if s in env]
        collective = _is_collective_view(view)
        if collective:
            axis, scaling = AXIS_REPLICATED, SCALE_COLLECTIVE
            if _replication_origin(view):
                # a global sort over the raw scope or a custom-kernel
                # UDF has no sharded lowering: the stage runs whole on
                # every chip regardless of N (a grouped ORDER BY only
                # sorts the G-row group output — that stays collective)
                scaling = SCALE_REPLICATED
        elif sources and all(env[s].axis == AXIS_DATA for s in sources):
            axis, scaling = AXIS_DATA, SCALE_SHARDED
        else:
            # elementwise over replicated input(s): runs replicated
            axis, scaling = AXIS_REPLICATED, SCALE_REPLICATED
        edges = []
        if collective:
            for s in sources:
                if env[s].axis == AXIS_DATA:
                    edges.append(ReshardEdge(
                        s, env[s].gather_bytes,
                        allgather_wire_bytes(env[s].gather_bytes, chips),
                    ))
        b = _view_model_bytes(view)
        details = []
        if p is not None and p.grouped:
            details.append(f"group G<={p.groups_bound}")
        for site in (p.joins if p else ()):
            details.append(
                f"{site.algorithm}-join F<={site.out_rows} vs "
                f"{site.right_table}"
            )
        if p is not None and (p.order_keys or view.host_order):
            details.append("global sort")
        if edges:
            details.append(
                "gathers " + ", ".join(e.table for e in edges)
            )
        stage = MeshStage(
            name=view.name, kind=kind, axis=axis, scaling=scaling,
            rows=view.capacity, hbm_bytes=b,
            per_chip_bytes=_per_chip(b, axis, chips),
            reshards=edges, detail="; ".join(details),
        )
        # sharded OUTPUT views gather at the step boundary: the runtime
        # replicates every output dataset before the host reads it
        if view.name in bundle.output_datasets and axis == AXIS_DATA:
            stage.reshards.append(ReshardEdge(
                f"{view.name} (output boundary)", b,
                allgather_wire_bytes(b, chips),
            ))
            if not stage.detail:
                stage.detail = "sharded output: gathered at step boundary"
        stages.append(stage)
        env[view.name] = _EnvEntry(view.schema, view.capacity, axis, b)
    return stages, env


# ---------------------------------------------------------------------------
# Lowering cross-check: the model must equal the real Mesh lowering
# ---------------------------------------------------------------------------
def _overflow_struct(view: CompiledView) -> Dict[str, jax.ShapeDtypeStruct]:
    """The hidden __overflow columns a view's output table carries —
    part of the boundary-gather bytes, so part of the cross-check."""
    p = view.plan
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if p is None or p.kind == "union":
        return out
    rows = view.capacity
    if p.grouped:
        out["__overflow.groups"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    if p.joins:
        out["__overflow.joins"] = jax.ShapeDtypeStruct((rows,), jnp.int32)
    return out


def _lower_and_census(fn, avals, in_shardings, out_shardings):
    from ..dist.mesh import summarize_compiled

    lowered = jax.jit(
        fn, in_shardings=in_shardings, out_shardings=out_shardings
    ).lower(avals)
    return summarize_compiled(lowered.compile())


def _cross_check(
    bundle: FlowDevicePlan,
    stages: List[MeshStage],
    env: Dict[str, _EnvEntry],
    mesh,
    diags: List[Diagnostic],
) -> None:
    """Lower every stage body and reshard edge under the real mesh and
    assert the closed-form model matches the partitioner's output
    exactly. Disagreement is DX790 — the model may never silently
    drift from what XLA builds."""
    from ..dist.mesh import replicated, row_sharding

    row, rep = row_sharding(mesh), replicated(mesh)
    by_name = {s.name: s for s in stages}
    aux = bundle.aux_tables

    # 1. stage bodies: zero collectives under the planned layout
    for view in bundle.pipeline.views:
        stage = by_name[view.name]
        p = view.plan
        sources = [s for s in (p.sources if p else ()) if s in env]
        if not sources:
            continue
        collective = stage.scaling in (SCALE_COLLECTIVE, SCALE_REPLICATED)
        in_sh = {
            s: (rep if (collective or env[s].axis != AXIS_DATA) else row)
            for s in sources
        }
        avals = {s: table_struct(env[s].schema, env[s].rows) for s in sources}
        out_sh = rep if stage.axis != AXIS_DATA else row

        def body(tables, _view=view, _aux=aux):
            t = dict(tables)
            t["__aux"] = _aux
            zero = jnp.asarray(0, jnp.int32)
            ws = _view.window_state
            if ws is not None:
                # a decomposed windowed GROUP BY: the batch folded into
                # an empty state and combined is its stage body
                from ..compile.planner import WINDOW_PARTIALS_PREFIX

                st, window, rows, dropped, _wrote = ws.fold(
                    t[ws.table], ws.init(), zero, zero, zero, zero, _aux,
                    ws.event_rows(t[ws.table], zero, zero),
                )
                t[WINDOW_PARTIALS_PREFIX + _view.name] = ws.combine(
                    st, window, rows, dropped
                )
            return _view.fn(t, zero, zero)

        if p is not None and p.unshardable_udfs \
                and jax.default_backend() != "tpu":
            # Mosaic compiles for the TPU only, and an interpreter
            # build of the kernel is another program to the SPMD
            # partitioner: this stage is checked where jobs run, or
            # not at all — never against a stand-in
            diags.append(make(
                "DX791", view.name,
                f"stage body not validated: its Pallas kernel UDF "
                f"({'/'.join(p.unshardable_udfs)}) compiles on a TPU "
                f"backend only, this process has "
                f"{jax.default_backend()!r}",
            ))
            continue
        try:
            census = _lower_and_census(body, avals, (in_sh,), out_sh)
        except Exception as e:  # noqa: BLE001 — a lowering blowup is a finding
            diags.append(make(
                "DX790", view.name,
                f"mesh lowering of stage body failed under the planned "
                f"layout ({stage.axis}): {e}",
            ))
            continue
        if census.op_count:
            diags.append(make(
                "DX790", view.name,
                f"sharding model mismatch: stage body planned as "
                f"communication-free ({stage.axis} layout) but the SPMD "
                f"partitioner inserted {census.op_count} collective(s) "
                f"moving {census.result_bytes} result bytes "
                f"({census.to_dict()}) — the closed-form model no longer "
                f"describes this lowering",
            ))

    # 2. reshard edges: the identity resharding kernel's all-gather
    #    census must equal the closed form byte-for-byte
    checked: Dict[Tuple, int] = {}
    for stage in stages:
        total = 0
        for edge in stage.reshards:
            src = edge.table.split(" ")[0]
            if src in env and not edge.table.endswith("(output boundary)"):
                struct = table_struct(env[src].schema, env[src].rows)
                extra: Dict[str, jax.ShapeDtypeStruct] = {}
            else:
                # output-boundary edge: the view's own table, overflow
                # columns included
                view = next(
                    v for v in bundle.pipeline.views if v.name == src
                )
                struct = table_struct(view.schema, view.capacity)
                extra = _overflow_struct(view)
            key = (
                src, struct.valid.shape, tuple(sorted(struct.cols)),
                tuple(sorted(extra)),
            )
            if key not in checked:
                if extra:
                    cols = dict(struct.cols)
                    cols.update(extra)
                    from ..compile.planner import TableData

                    struct = TableData(cols, struct.valid)
                try:
                    census = _lower_and_census(
                        lambda t: t, struct,
                        (jax.tree_util.tree_map(lambda _: row, struct),),
                        rep,
                    )
                except Exception as e:  # noqa: BLE001
                    diags.append(make(
                        "DX790", stage.name,
                        f"mesh lowering of the {src} reshard edge "
                        f"failed: {e}",
                    ))
                    checked[key] = -1
                    continue
                gathered = census.ops.get("all-gather", (0, 0))[1]
                others = {
                    k: v for k, v in census.ops.items() if k != "all-gather"
                }
                if others:
                    diags.append(make(
                        "DX790", stage.name,
                        f"reshard edge {src} lowered to non-all-gather "
                        f"collectives {others} — the model prices "
                        f"gathers only",
                    ))
                checked[key] = gathered
            lowered = checked[key]
            if lowered >= 0 and lowered != edge.result_bytes:
                diags.append(make(
                    "DX790", stage.name,
                    f"sharding model mismatch on the {edge.table} "
                    f"reshard: closed form says {edge.result_bytes} "
                    f"all-gather result bytes, the Mesh lowering moved "
                    f"{lowered} — the byte model must match the "
                    f"lowering exactly",
                ))
            if lowered >= 0:
                total += lowered
        stage.lowered_bytes = total if stage.reshards else 0


# ---------------------------------------------------------------------------
# DX7xx lints over the partition plan
# ---------------------------------------------------------------------------
def _lint(
    bundle: FlowDevicePlan,
    stages: List[MeshStage],
    chips: int,
    spec: FleetSpec,
    diags: List[Diagnostic],
) -> None:
    batch_scale = max(bundle.target_caps.values(), default=0)

    # DX700: structurally unshardable stages (global sorts over the raw
    # scope, Pallas-kernel UDF calls) replicate everything regardless
    # of N (a grouped ORDER BY only sorts the G-row output)
    for view in bundle.pipeline.views:
        p = view.plan
        origin = _replication_origin(view)
        if origin:
            rows = p.input_rows if p is not None else view.capacity
            diags.append(make(
                "DX700", view.name,
                f"unshardable stage forces full replication: the "
                f"{origin} materializes all {rows} input rows on every "
                f"chip at any chip count — this stage cannot shard",
            ))

    # DX701: the same sharded table gathered at 2+ stage boundaries
    gathers: Dict[str, List[str]] = {}
    for s in stages:
        for e in s.reshards:
            if not e.table.endswith("(output boundary)"):
                gathers.setdefault(e.table, []).append(s.name)
    for table, consumers in sorted(gathers.items()):
        if len(consumers) > 1:
            diags.append(make(
                "DX701", table,
                f"resharding between adjacent stages: {table} is "
                f"gathered onto every chip at {len(consumers)} stage "
                f"boundaries ({', '.join(consumers)}) — each pays the "
                f"all-gather again; fold the consumers or share a "
                f"gathered intermediate",
            ))

    # DX702: per-chip residency vs chip HBM at the requested N
    per_chip = sum(s.per_chip_bytes for s in stages)
    budget = spec.hbm_per_chip_bytes * spec.headroom_fraction
    if per_chip > budget:
        diags.append(make(
            "DX702", "",
            f"per-chip shard exceeds chip HBM at {chips} chips: "
            f"{per_chip} bytes resident per chip (sharded shards + "
            f"replicated tables) vs the {spec.hbm_per_chip_bytes}-byte "
            f"chip at {spec.headroom_fraction:.0%} headroom "
            f"({int(budget)} usable)",
        ))

    # DX703: ICI wire demand vs the fleet-spec interconnect budget
    wire = sum(s.ici_wire_bytes for s in stages)
    interval = bundle.interval_s or 1.0
    ici_budget = spec.ici_bytes_per_sec_per_chip * chips * interval
    if wire > ici_budget:
        diags.append(make(
            "DX703", "",
            f"predicted ICI traffic {wire:.0f} bytes/batch exceeds the "
            f"fleet-spec budget ({spec.ici_bytes_per_sec_per_chip:.0f} "
            f"B/s/chip x {chips} chips x {interval:g}s interval = "
            f"{ici_budget:.0f}) — collectives will dominate the step",
        ))

    # DX704: stages flat or worse in N (replicated compute at batch
    # scale: doubling the chips doubles the fleet's work, not the
    # speed). Only replication ORIGINS fire — a stage that merely
    # inherits a replicated input is the origin's symptom, not a second
    # finding.
    origins = {
        v.name for v in bundle.pipeline.views if _replication_origin(v)
    }
    for s in stages:
        if (
            s.scaling == SCALE_REPLICATED
            and s.name in origins
            and batch_scale
            and s.rows >= batch_scale
        ):
            diags.append(make(
                "DX704", s.name,
                f"scaling cliff: stage runs replicated over {s.rows} "
                f"rows on every chip — its modeled per-chip cost is "
                f"flat in the chip count, so the flow stops scaling "
                f"here (first {chips}-chip victim)",
            ))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def _analyze(
    bundle: Optional[FlowDevicePlan],
    diags: List[Diagnostic],
    name: str,
    chips: int,
    spec: Optional[FleetSpec],
    lower: Optional[bool],
) -> MeshPlanReport:
    if bundle is None:
        return MeshPlanReport(name, chips, [], _ordered(diags))
    spec = spec or FleetSpec()
    try:
        stages, env = _infer_plan(bundle, chips)
    except Exception as e:  # noqa: BLE001 — plan inference blowup is a finding
        diags.append(make("DX790", "", f"partition-plan inference failed: {e}"))
        return MeshPlanReport(bundle.name, chips, [], _ordered(diags))
    _lint(bundle, stages, chips, spec, diags)

    validated = False
    n_dev = len(jax.devices())
    want_lower = lower if lower is not None else n_dev >= 2
    if want_lower and n_dev >= 2:
        from ..dist.mesh import make_mesh

        mesh = make_mesh(min(chips, n_dev))
        _cross_check(bundle, stages, env, mesh, diags)
        validated = True
    elif want_lower or lower is None:
        diags.append(make(
            "DX791", "",
            f"mesh lowering cross-check skipped: {n_dev} device(s) "
            f"available, need >= 2 — the collective byte model is "
            f"emitted unvalidated (run under a multi-device backend; "
            f"the CLI virtualizes CPU devices)",
        ))
    return MeshPlanReport(
        bundle.name, chips, stages, _ordered(diags), validated=validated
    )


def _resolve_chips(chips: Optional[int], jobconf: Dict[str, object]) -> int:
    if chips is not None:
        return chips
    from .deviceplan import _jobconf_int

    return (
        _jobconf_int(jobconf, "jobNumChips", "jobNumExecutors")
        or DEFAULT_MESH_CHIPS
    )


def analyze_flow_mesh(
    flow: dict,
    chips: Optional[int] = None,
    spec: Optional[FleetSpec] = None,
    lower: Optional[bool] = None,
) -> MeshPlanReport:
    """Mesh-sharding analysis of a flow config (gui JSON or full flow
    document). Compiles with the production planner, infers the SPMD
    partition plan, prices the collectives, and (when >= 2 devices are
    available, or ``lower=True``) cross-checks the byte model against a
    real ``Mesh`` lowering. ``lower=False`` skips the cross-check (the
    fast model-only path config generation uses)."""
    gui = flow.get("gui") if isinstance(flow.get("gui"), dict) else flow
    jobconf = ((gui.get("process") or {}).get("jobconfig") or {})
    n_chips = _resolve_chips(chips, jobconf)
    diags: List[Diagnostic] = []
    plan_diags: List[Diagnostic] = []
    bundle = _plan_from_gui(gui, plan_diags, n_chips)
    # the bundle builder reports in DX2xx; re-code for this tier
    for d in plan_diags:
        code = "DX790" if d.code == "DX290" else "DX791"
        diags.append(make(code, d.table, d.message, d.span))
    return _analyze(
        bundle, diags, gui.get("name") or "", n_chips, spec, lower
    )


def analyze_processor_mesh(
    proc,
    chips: Optional[int] = None,
    spec: Optional[FleetSpec] = None,
    lower: Optional[bool] = None,
) -> MeshPlanReport:
    """Mesh-sharding analysis of an already-built ``FlowProcessor`` —
    the exact compiled views the (possibly mesh-sharded) jitted step
    runs (the MULTICHIP cross-validation path, mirroring
    ``deviceplan.analyze_processor``)."""
    diags: List[Diagnostic] = []
    n_chips = chips or (proc.mesh.size if proc.mesh is not None else None)
    bundle = flow_plan_from_processor(proc, n_chips)
    n_chips = n_chips or DEFAULT_MESH_CHIPS
    return _analyze(bundle, diags, bundle.name, n_chips, spec, lower)
