"""Device-plan analyzer: abstract interpretation of the compiled plan.

Second analysis tier (the ``--device`` tier). Where ``analyzer.py``
checks a flow's *meaning* (references, types, legality), this tier
checks what the compiled plan will *cost*: it reuses the production
lowering — the same ``SelectCompiler``/``PipelineCompiler`` the runtime
jits — then derives every stage's static shapes with ``jax.eval_shape``
(no device execution, no allocation) and emits

- a **cost report**: per-stage HBM footprint, FLOP estimate and
  expected ICI bytes/batch (closed forms over group cardinality and
  join fan-out; see ``costmodel.py`` and ANALYSIS.md "Scaling model"),
- the **DX2xx lint family**: capacity risk (group/join/dictionary
  bounds vs declared cardinality), O(n*m) match-matrix joins at window
  scale, recompilation hazards, and int32 ring-rebase proximity.

Two byte numbers per stage keep the model honest: ``hbm_bytes`` comes
from ``jax.eval_shape`` over the production lowering (ground truth
shapes), ``model_bytes`` from the closed forms. A tier-1 test asserts
they match the arrays a real batch materializes — the static model can
never silently drift from reality.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..compile.codegen import CodegenEngine, RulesCode
from ..compile.pipeline import (
    Pipeline,
    PipelineCompiler,
    parse_state_table_schema,
)
from ..compile.planner import (
    CompiledView,
    PlannerConfig,
    SelectCompiler,
    TableData,
    ViewSchema,
)
from ..constants import ColumnName, DatasetName
from ..core.config import EngineException, parse_duration_seconds
from ..core.schema import Schema, StringDictionary
from ..runtime.processor import (
    default_projection,
    event_time_table,
    projection_select,
    schema_to_view,
    source_raw_form,
    window_inputs,
    window_target,
)
from ..runtime.timewindow import num_slots
from ..serve.flowbuilder import RuleDefinitionGenerator
from .costmodel import (
    DEFAULT_MATCH_MATRIX_BUDGET,
    d2h_transfer_bytes,
    packed_raw_bytes,
    row_bytes,
    stage_flops,
    stage_ici_bytes,
    stage_transient_bytes,
    table_bytes,
    view_output_bytes,
)
from .diagnostics import AnalysisReport, Diagnostic, make

# the north-star slice (v5e-16): default chip count for the ICI model
DEFAULT_CHIPS = 16

# int32 relative-millis horizon for ring timestamps (~24.8 days); DX205
# fires when retention crosses a quarter of it
INT32_MS_HORIZON = 2 ** 31
REBASE_PROXIMITY_FRACTION = 0.25

# DX206 fires when an OUTPUT view's static capacity exceeds the modeled
# row count (declared group-key cardinality) by this factor — the sync
# stage would transfer >98% padding on a full-capacity fetch
D2H_OVERSIZE_FACTOR = 64

_STRUCT_DTYPES = {"double": jnp.float32, "boolean": jnp.bool_}

# stage kinds that persist across batches (device-resident state) vs
# materialized per batch
PERSISTENT_KINDS = ("ring", "state", "refdata")


def table_struct(schema: ViewSchema, rows: int) -> TableData:
    """Abstract TableData (ShapeDtypeStructs) for one input table —
    the exact dtypes the runtime encodes (core/schema.py)."""
    cols = {
        c: jax.ShapeDtypeStruct((rows,), _STRUCT_DTYPES.get(t, jnp.int32))
        for c, t in schema.types.items()
    }
    return TableData(cols, jax.ShapeDtypeStruct((rows,), jnp.bool_))


def _real_table(schema: ViewSchema, rows: int) -> TableData:
    cols = {
        c: jnp.zeros((rows,), _STRUCT_DTYPES.get(t, jnp.int32))
        for c, t in schema.types.items()
    }
    return TableData(cols, jnp.zeros((rows,), jnp.bool_))


def _leaf_bytes(a) -> int:
    return int(math.prod(a.shape)) * a.dtype.itemsize


def _table_data_bytes(td: TableData) -> int:
    return sum(_leaf_bytes(a) for a in td.cols.values()) + _leaf_bytes(td.valid)


# ---------------------------------------------------------------------------
# Report types
# ---------------------------------------------------------------------------
@dataclass
class StageCost:
    name: str
    kind: str  # input | project | ring | window | state | refdata | group | union | sync
    rows: int
    hbm_bytes: int  # from eval_shape over the production lowering
    model_bytes: int  # closed-form prediction (costmodel.py)
    transient_bytes: int = 0  # peak in-stage intermediates (match matrix)
    flops: float = 0.0
    ici_bytes: float = 0.0  # expected interconnect bytes/batch at `chips`
    # device->host bytes this stage moves per batch — non-zero only for
    # OUTPUT views, fetched at their capacity, and the counts vector
    d2h_bytes: int = 0
    detail: str = ""

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "rows": self.rows,
            "hbmBytes": self.hbm_bytes,
            "modelBytes": self.model_bytes,
            "transientBytes": self.transient_bytes,
            "flops": round(self.flops, 1),
            "iciBytes": round(self.ici_bytes, 1),
            "d2hBytes": self.d2h_bytes,
            "detail": self.detail,
        }


@dataclass
class DevicePlanReport:
    flow: str
    chips: int
    stages: List[StageCost]
    diagnostics: List[Diagnostic]
    # OUTPUT dataset -> {"rows": modeled cardinality, "capacity": padded
    # static capacity} — the occupancy side of the runtime conformance
    # model (obs/conformance.py DX502)
    outputs: Dict[str, dict] = field(default_factory=dict)

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.is_error]

    @property
    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if not d.is_error]

    @property
    def ok(self) -> bool:
        return not self.errors

    def totals(self) -> dict:
        persistent = sum(
            s.hbm_bytes for s in self.stages if s.kind in PERSISTENT_KINDS
        )
        per_batch = sum(
            s.hbm_bytes for s in self.stages if s.kind not in PERSISTENT_KINDS
        )
        return {
            "hbmBytes": persistent + per_batch,
            "persistentBytes": persistent,
            "perBatchBytes": per_batch,
            "modelBytes": sum(s.model_bytes for s in self.stages),
            "transientBytes": sum(s.transient_bytes for s in self.stages),
            "flops": round(sum(s.flops for s in self.stages), 1),
            "iciBytesPerBatch": round(
                sum(s.ici_bytes for s in self.stages), 1
            ),
            "d2hBytesPerBatch": sum(s.d2h_bytes for s in self.stages),
        }

    def plan_dict(self) -> dict:
        """The cost-report portion (no diagnostics) — what the designer
        renders beside the diagnostics list. Includes the roofline
        ``latencyModel`` (closed-form milliseconds under a machine
        profile — the datasheet default here; a *calibrated* profile
        replaces it wherever one is available: the host's DX520
        predictions)."""
        return {
            "flow": self.flow,
            "chips": self.chips,
            "stages": [s.to_dict() for s in self.stages],
            "totals": self.totals(),
            "latencyModel": self.latency_model(),
        }

    def latency_model(
        self, profile: Optional[dict] = None, source: str = "default",
    ) -> dict:
        """The time axis of this report: per-stage roofline ms + the
        deviceStep/d2h/ici decomposition (costmodel.latency_model)
        under ``profile`` (a ``MachineProfile.to_dict()``; the static
        datasheet default when None)."""
        from .costmodel import latency_model

        if profile is None:
            from ..obs.calibrate import DEFAULT_PROFILE

            profile = DEFAULT_PROFILE.to_dict()
            source = "default"
        return latency_model(
            [s.to_dict() for s in self.stages], self.totals(),
            profile, profile_source=source,
        )

    def to_dict(self) -> dict:
        from .diagnostics import REPORT_SCHEMA_VERSION

        return {
            "schemaVersion": REPORT_SCHEMA_VERSION,
            "ok": self.ok,
            "errorCount": len(self.errors),
            "warningCount": len(self.warnings),
            "diagnostics": [d.to_dict() for d in self.diagnostics],
            "device": self.plan_dict(),
        }

    def runtime_model(self) -> dict:
        """The machine-readable conformance artifact config generation
        embeds into the flow's conf
        (``datax.job.process.conformance.model``) — the slice of this
        report a running host checks itself against
        (``obs/conformance.py``)."""
        from .costmodel import runtime_conformance_model

        return runtime_conformance_model(
            self.totals(),
            [s.to_dict() for s in self.stages],
            self.outputs,
        )


def _ordered(diags: List[Diagnostic]) -> List[Diagnostic]:
    return sorted(
        diags, key=lambda d: (d.severity != "error", d.span.line, d.code)
    )


def combined_report_dict(
    base: AnalysisReport, device: Optional[DevicePlanReport] = None,
    udfs=None, fleet=None, compile_surface=None, mesh=None, race=None,
    protocol=None, conf=None,
) -> dict:
    """Merge the semantic tier with the optional device, UDF, fleet,
    compile, mesh, race and protocol tiers into one response: a
    superset of ``AnalysisReport.to_dict()`` plus a ``device`` cost
    report, a ``udfs`` summary, a ``fleet`` placement plan, a
    ``compile`` surface+manifest, a ``mesh`` sharding plan, a ``race``
    engine buffer-lifetime gate and/or a ``protocol`` exactly-once
    delivery gate — what ``flow/validate`` returns with ``device:
    true`` / ``udfs: true`` / ``fleet: true`` / ``compile: true`` /
    ``mesh: true`` / ``race: true`` / ``protocol: true`` (or ``all:
    true``) and what the CLI's tier flags (or ``--all``) ``--json``
    print: one ``schemaVersion``, one merged diagnostics list, one
    exit contract."""
    from .diagnostics import REPORT_SCHEMA_VERSION

    diags = list(base.diagnostics)
    if device is not None:
        diags += list(device.diagnostics)
    if udfs is not None:
        diags += list(udfs.diagnostics)
    if fleet is not None:
        diags += list(fleet.diagnostics)
    if compile_surface is not None:
        diags += list(compile_surface.diagnostics)
    if mesh is not None:
        diags += list(mesh.diagnostics)
    if race is not None:
        diags += list(race.diagnostics)
    if protocol is not None:
        diags += list(protocol.diagnostics)
    if conf is not None:
        diags += list(conf.diagnostics)
    diags = _ordered(diags)
    errors = [d for d in diags if d.is_error]
    out = {
        "schemaVersion": REPORT_SCHEMA_VERSION,
        "ok": not errors,
        "errorCount": len(errors),
        "warningCount": len(diags) - len(errors),
        "diagnostics": [d.to_dict() for d in diags],
    }
    if device is not None:
        out["device"] = device.plan_dict()
    if udfs is not None:
        out["udfs"] = udfs.udfs_dict()
    if fleet is not None:
        out["fleet"] = fleet.fleet_dict()
    if compile_surface is not None:
        out["compile"] = compile_surface.compile_dict()
    if mesh is not None:
        out["mesh"] = mesh.mesh_dict()
    if race is not None:
        out["race"] = race.race_dict()
    if protocol is not None:
        out["protocol"] = protocol.protocol_dict()
    if conf is not None:
        out["conf"] = conf.conf_dict()
    return out


# ---------------------------------------------------------------------------
# The compiled flow bundle both entry points produce
# ---------------------------------------------------------------------------
@dataclass
class FlowDevicePlan:
    """Everything the evaluator/linter needs, built from either a flow
    config (``analyze_flow_device``) or a live ``FlowProcessor``
    (``analyze_processor`` — the test path)."""

    name: str
    pipeline: Pipeline
    projection_views: Dict[str, List[CompiledView]]  # source -> views
    raw_schemas: Dict[str, Tuple[ViewSchema, int]]  # source -> (schema, cap)
    target_of: Dict[str, str]  # source -> projected table
    target_schemas: Dict[str, ViewSchema]
    target_caps: Dict[str, int]
    ring_slots: Dict[str, int]  # windowed table -> slots
    windows: Dict[str, Tuple[str, float]]  # window name -> (table, dur_s)
    state: Dict[str, Tuple[ViewSchema, int]]
    refdata: Dict[str, Tuple[ViewSchema, int]]
    aux_tables: Dict[str, object]
    dict_max_size: Optional[int] = None
    declared_cardinality: Dict[str, int] = field(default_factory=dict)
    declared_strings: int = 0
    udf_refresh_names: List[str] = field(default_factory=list)
    uses_string_ops: bool = False
    watermark_s: float = 0.0
    interval_s: float = 1.0
    chips: int = DEFAULT_CHIPS
    # datasets routed to sinks — the views whose tables cross the
    # device->host boundary every batch (the D2H term + DX206 surface)
    output_datasets: List[str] = field(default_factory=list)
    # source -> its raw batch comes as the one packed matrix, not a
    # leaf a column (``source_raw_form`` of its input type): what the
    # byte models price and the step's trace signature carries
    raw_packed: Dict[str, bool] = field(default_factory=dict)

    def raw_batch_bytes(self, source: str) -> int:
        """Device bytes of a source's raw batch in the form it is
        shipped in."""
        raw_schema, cap = self.raw_schemas[source]
        if self.raw_packed.get(source):
            return packed_raw_bytes(raw_schema.types, cap)
        return table_bytes(raw_schema.types, cap)


def _declared_cardinality(schema: Schema) -> Tuple[Dict[str, int], int]:
    """Per-leaf-column declared value cardinality from schema metadata
    ``allowedValues`` (written by hand or by schema inference from
    samples — the 'sampled cardinality' surface), plus the total count
    of distinct declared string values (the dictionary-pressure bound).
    Keyed by the leaf name because projections alias nested fields to
    their leaves (``deviceDetails.deviceId AS deviceId``)."""
    cards: Dict[str, int] = {}
    n_strings = 0
    for col in schema.columns:
        vals = (col.metadata or {}).get("allowedValues")
        if not isinstance(vals, list) or not vals:
            continue
        leaf = col.name.rsplit(".", 1)[-1]
        cards[leaf] = len(vals)
        cards.setdefault(col.name, len(vals))
        if col.ctype.value == "string":
            n_strings += len(set(map(str, vals)))
    return cards, n_strings


# ---------------------------------------------------------------------------
# Builder: from a designer flow config (gui JSON / full flow document)
# ---------------------------------------------------------------------------
def _jobconf_int(jobconf: dict, *names: str) -> Optional[int]:
    for n in names:
        v = jobconf.get(n)
        if v in (None, ""):
            continue
        try:
            return int(v)
        except (TypeError, ValueError):
            return None
    return None


def _plan_from_gui(
    gui: dict, diags: List[Diagnostic], chips: Optional[int]
) -> Optional[FlowDevicePlan]:
    name = gui.get("name") or ""
    iprops = (gui.get("input") or {}).get("properties") or {}
    proc = gui.get("process") or {}
    jobconf = proc.get("jobconfig") or {}

    batch_capacity = _jobconf_int(jobconf, "jobBatchCapacity") or 65536
    try:
        interval_s = float(
            iprops.get("windowDuration") or iprops.get("intervalInSeconds") or 1
        )
    except (TypeError, ValueError):
        interval_s = 1.0
    watermark = proc.get("watermark") or (
        f"{iprops.get('watermarkValue', 0)} "
        f"{iprops.get('watermarkUnit', 'second')}"
    )
    try:
        watermark_s = parse_duration_seconds(watermark)
    except Exception:  # noqa: BLE001 — malformed watermark: keep 0
        watermark_s = 0.0
    ts_col = proc.get("timestampColumn") or ""

    # planner capacities from the flow config (conf process.maxgroups /
    # process.joincapacity analogs in the designer's jobconfig)
    pc_kwargs = {}
    maxgroups = _jobconf_int(jobconf, "maxGroups", "maxgroups")
    if maxgroups is not None and maxgroups >= 1:
        pc_kwargs["max_group_capacity"] = maxgroups
    joincap = _jobconf_int(jobconf, "joinCapacity", "joincapacity")
    if joincap is not None and joincap >= 1:
        pc_kwargs["join_capacity"] = joincap
    planner_config = PlannerConfig(**pc_kwargs)
    dict_max = _jobconf_int(
        jobconf, "stringDictionaryMaxSize", "stringdictionarymaxsize"
    )

    # -- sources ---------------------------------------------------------
    sources: List[Tuple[str, dict, str]] = []  # (source, props, target)
    # input type per source: decides the raw transfer form (the one
    # packed matrix or a leaf a column), which the byte models price
    # and the step's trace signature carries (``source_raw_form``)
    raw_packed: Dict[str, bool] = {}
    if iprops.get("inputSchemaFile"):
        sources.append(("default", iprops, DatasetName.DataStreamProjection))
        raw_packed["default"] = source_raw_form(
            (gui.get("input") or {}).get("type")) == "packed"
    for src in (gui.get("input") or {}).get("sources") or []:
        sname = src.get("id") or src.get("name")
        if not sname:
            continue
        sprops = src.get("properties") or {}
        sources.append((sname, sprops, sprops.get("target") or sname))
        raw_packed[sname] = source_raw_form(src.get("type")) == "packed"
    if not sources:
        diags.append(make(
            "DX291", "",
            "device analysis needs a concrete input schema "
            "(gui.input.properties.inputSchemaFile)",
        ))
        return None

    schemas: Dict[str, Schema] = {}
    raw_schemas: Dict[str, Tuple[ViewSchema, int]] = {}
    target_of: Dict[str, str] = {}
    snippets: Dict[str, Optional[str]] = {}
    for sname, sprops, target in sources:
        try:
            schema = Schema.from_spark_json(sprops.get("inputSchemaFile"))
        except (TypeError, ValueError, KeyError) as e:
            diags.append(make(
                "DX291", target,
                f"device analysis skipped: input schema for source "
                f"'{sname}' does not parse ({e})",
            ))
            return None
        schemas[sname] = schema
        raw_types = dict(schema_to_view(schema).types)
        raw_types.setdefault(ColumnName.RawPropertiesColumn, "string")
        raw_types.setdefault(ColumnName.RawSystemPropertiesColumn, "string")
        raw_schemas[sname] = (ViewSchema(raw_types), batch_capacity)
        target_of[sname] = target
        snippets[sname] = sprops.get("normalizationSnippet")
    targets = list(target_of.values())

    # -- UDFs (design-time reflection load, the JarUDFHandler path) ------
    udfs: Dict[str, object] = {}
    for fn in proc.get("functions") or []:
        ftype = (fn.get("type") or "udf").lower()
        if ftype not in ("udf", "udaf", "jarudf", "jarudaf", "pythonudf"):
            continue  # azure functions are a sink tier, not compiled
        props = fn.get("properties") or {}
        path = props.get("module") or props.get("class") or ""
        fid = fn.get("id") or ""
        if not fid or not path:
            continue
        try:
            from ..udf.api import _import_attr

            obj = _import_attr(path)
            if isinstance(obj, type) or not hasattr(obj, "compile_call"):
                obj = obj()
        except Exception as e:  # noqa: BLE001 — reflection load
            diags.append(make(
                "DX291", "",
                f"device analysis skipped: UDF '{fid}' ({path}) is not "
                f"loadable at design time ({e})",
            ))
            return None
        obj.name = fid
        udfs[fid.lower()] = obj

    # -- codegen (the S450 pass the runtime also consumes) ---------------
    queries = proc.get("queries") or []
    code = "\n".join(q if isinstance(q, str) else str(q) for q in queries)
    rules_json = RuleDefinitionGenerator().generate(gui.get("rules") or [], name)
    try:
        rc: RulesCode = CodegenEngine().generate_code(
            code, rules_json, name, windowable_tables=set(targets)
        )
    except Exception as e:  # noqa: BLE001 — base tier owns codegen findings
        diags.append(make(
            "DX291", "", f"device analysis skipped: codegen failed ({e})"
        ))
        return None

    dictionary = StringDictionary()
    pc = PipelineCompiler(dictionary, udfs, config=planner_config)

    try:
        # per-source projection lowering (the FlowProcessor path)
        projection_views: Dict[str, List[CompiledView]] = {}
        target_schemas: Dict[str, ViewSchema] = {}
        target_caps: Dict[str, int] = {}
        for sname, _sprops, target in sources:
            raw_schema, cap = raw_schemas[sname]
            snippet = snippets[sname]
            steps = [snippet] if snippet else [
                default_projection(schemas[sname], ts_col)
            ]
            proj_catalog = {
                "Raw": raw_schema, DatasetName.DataStreamRaw: raw_schema,
            }
            proj_caps = {"Raw": cap, DatasetName.DataStreamRaw: cap}
            cur = "Raw"
            views: List[CompiledView] = []
            for i, step in enumerate(steps):
                sel = projection_select(step, cur)
                compiler = SelectCompiler(
                    proj_catalog, proj_caps, dictionary, udfs,
                    planner_config, aux=pc.aux,
                )
                vname = target if i == len(steps) - 1 else f"__proj{i}"
                view = compiler.compile_select(vname, sel)
                views.append(view)
                proj_catalog[vname] = view.schema
                proj_caps[vname] = view.capacity
                cur = vname
            projection_views[sname] = views
            target_schemas[target] = proj_catalog[target]
            target_caps[target] = cap

        # windows over projected tables (ring retention model)
        projections: Dict[str, List[List[str]]] = {}
        for sname, _sprops, target in sources:
            projections.setdefault(target, []).append(
                [snippets[sname]] if snippets[sname]
                else [default_projection(schemas[sname], ts_col)]
            )
        windows: Dict[str, Tuple[str, float]] = {}
        table_slots: Dict[str, int] = {}
        for wname, duration in rc.time_windows.items():
            table = window_target(wname, targets)
            if table not in target_schemas:
                raise EngineException(
                    f"timewindow {wname} targets unknown table {table!r}"
                )
            dur_s = parse_duration_seconds(duration)
            if ts_col not in target_schemas[table].types:
                raise EngineException(
                    f"timewindow {wname} requires timestamp column "
                    f"{ts_col!r} in table {table}"
                )
            windows[wname] = (table, dur_s)
            slots = num_slots(
                dur_s, watermark_s, interval_s,
                event_time_table(projections, table, ts_col),
            )
            table_slots[table] = max(table_slots.get(table, 1), slots)

        # accumulation tables
        state: Dict[str, Tuple[ViewSchema, int]] = {}
        for sname_, ddl in rc.accumulation_tables.items():
            state[sname_] = (
                parse_state_table_schema(ddl), batch_capacity * 4
            )

        inputs: Dict[str, Tuple[ViewSchema, int]] = {
            t: (sch, target_caps[t]) for t, sch in target_schemas.items()
        }
        for wname, (table, _d) in windows.items():
            inputs[wname] = (
                target_schemas[table],
                table_slots[table] * target_caps[table],
            )
        pipeline = pc.compile_transform(
            rc.code, inputs, state,
            windows=window_inputs(
                windows, table_slots, projections, ts_col,
                interval_s=interval_s, watermark_s=watermark_s,
            ),
        )
        # a window the planner holds as partial aggregates keeps no ring
        ring_slots = {
            table: table_slots[table] for wname, (table, _d) in windows.items()
            if wname not in pipeline.partial_windows
        }
    except EngineException as e:
        diags.append(make("DX290", "", str(e)))
        return None
    except Exception as e:  # noqa: BLE001 — any lowering blowup is a finding
        diags.append(make("DX290", "", f"device lowering failed: {e}"))
        return None

    from ..compile.stringops import AuxTableBuilder

    aux = AuxTableBuilder(pc.aux, dictionary).tables()

    cards: Dict[str, int] = {}
    n_strings = 0
    for sname in schemas:
        c, ns = _declared_cardinality(schemas[sname])
        for k, v in c.items():
            cards.setdefault(k, v)
        n_strings += ns

    refresh = [
        u.name for u in udfs.values()
        if getattr(u, "_on_interval", None) is not None
    ]

    # OUTPUT statements name the datasets that cross D2H every batch
    view_names = {v.name for v in pipeline.views}
    out_datasets: List[str] = []
    for tables, _sink in rc.outputs:
        for t in tables.split(","):
            t = t.strip()
            if t in view_names and t not in out_datasets:
                out_datasets.append(t)

    return FlowDevicePlan(
        name=name,
        pipeline=pipeline,
        projection_views=projection_views,
        raw_schemas=raw_schemas,
        target_of=target_of,
        target_schemas=target_schemas,
        target_caps=target_caps,
        ring_slots=ring_slots,
        windows=windows,
        state=state,
        refdata={},
        aux_tables=aux,
        dict_max_size=dict_max,
        declared_cardinality=cards,
        declared_strings=n_strings,
        udf_refresh_names=refresh,
        uses_string_ops=not pc.aux.empty,
        watermark_s=watermark_s,
        interval_s=interval_s,
        chips=chips
        or _jobconf_int(jobconf, "jobNumChips", "jobNumExecutors")
        or DEFAULT_CHIPS,
        output_datasets=out_datasets,
        raw_packed=raw_packed,
    )


# ---------------------------------------------------------------------------
# Builder: from a live FlowProcessor (tier-1 drift test path)
# ---------------------------------------------------------------------------
def flow_plan_from_processor(proc, chips: Optional[int] = None) -> FlowDevicePlan:
    """Bundle an already-built ``FlowProcessor``'s compiled plan — the
    exact views the jitted step runs — for cost analysis."""
    cards: Dict[str, int] = {}
    n_strings = 0
    for spec in proc.specs.values():
        c, ns = _declared_cardinality(spec.schema)
        for k, v in c.items():
            cards.setdefault(k, v)
        n_strings += ns
    conf_chips = None
    try:
        conf_chips = proc.process_conf.get_int_option("numchips")
    except Exception:  # noqa: BLE001 — malformed conf: fall back
        pass
    return FlowDevicePlan(
        name=proc.dict.get("datax.job.name") or "",
        pipeline=proc.pipeline,
        projection_views=dict(proc.projection_views),
        raw_schemas={
            s.name: (s.raw_schema, s.capacity) for s in proc.specs.values()
        },
        target_of={s.name: s.target for s in proc.specs.values()},
        target_schemas=dict(proc.target_schemas),
        target_caps={s.target: s.capacity for s in proc.specs.values()},
        ring_slots=dict(proc.ring_slots),
        windows=dict(proc.windows),
        state={
            n: (st.schema, st.capacity)
            for n, st in proc.state_tables.items()
        },
        refdata={
            n: (sch, t.capacity) for n, (sch, t) in proc.refdata.items()
        },
        aux_tables=proc.aux_tables.tables(),
        dict_max_size=proc.dictionary.max_size,
        declared_cardinality=cards,
        declared_strings=n_strings,
        udf_refresh_names=[
            u.name for u in proc.udfs.values()
            if getattr(u, "_on_interval", None) is not None
        ],
        uses_string_ops=not proc.aux_registry.empty,
        watermark_s=proc.watermark_s,
        interval_s=proc.interval_s,
        chips=chips or conf_chips or DEFAULT_CHIPS,
        output_datasets=list(proc.output_datasets),
        raw_packed={
            s.name: proc._source_raw_form(s) == "packed"
            for s in proc.specs.values()
        },
    )


# ---------------------------------------------------------------------------
# Evaluator: abstract-interpret every stage of the compiled plan
# ---------------------------------------------------------------------------
def _view_stage(
    view: CompiledView,
    out_bytes: int,
    plan: FlowDevicePlan,
    catalog: Dict[str, ViewSchema],
) -> StageCost:
    p = view.plan
    kind = p.kind if p is not None else "project"
    details = []
    if p is not None:
        for s in p.joins:
            details.append(
                f"{s.kind.lower()}-join[{s.algorithm}] "
                f"{s.left_rows}x{s.right_rows}->{s.out_rows}"
            )
        if p.grouped:
            details.append(
                f"group keys={p.group_keys} aggs={p.n_aggregates} "
                f"bound={p.groups_bound}"
            )
        if p.union_branches > 1:
            details.append(f"union x{p.union_branches}")
        if p.limit is not None:
            details.append(f"limit {p.limit}")
    right_rb = {
        t: row_bytes(sch.types) for t, sch in catalog.items()
    }
    return StageCost(
        name=view.name,
        kind=kind,
        rows=view.capacity,
        hbm_bytes=out_bytes,
        model_bytes=view_output_bytes(view.schema.types, p, view.capacity),
        transient_bytes=stage_transient_bytes(p),
        flops=stage_flops(p, len(view.schema.types)),
        ici_bytes=stage_ici_bytes(
            p, row_bytes(view.schema.types), plan.chips, right_rb
        ),
        detail="; ".join(details),
    )


def _stage_walk(
    plan: FlowDevicePlan,
    make_table: Callable[[ViewSchema, int], TableData],
    eval_view: Callable[[CompiledView, Dict[str, TableData]], TableData],
) -> List[StageCost]:
    """Walk raw -> projection -> rings/windows -> state/refdata ->
    transform views, building stage costs. ``make_table`` and
    ``eval_view`` select abstract (eval_shape) or concrete evaluation —
    the same walk serves the analyzer and the drift test."""
    stages: List[StageCost] = []
    env: Dict[str, object] = {"__aux": plan.aux_tables}

    for source, views in plan.projection_views.items():
        raw_schema, cap = plan.raw_schemas[source]
        raw = make_table(raw_schema, cap)
        packed = plan.raw_packed.get(source)
        model = plan.raw_batch_bytes(source)
        # a packed batch is one int32 matrix whatever evaluates the
        # walk: the step splits it into the table the projections read
        stages.append(StageCost(
            name=f"input:{source}", kind="input", rows=cap,
            hbm_bytes=model if packed else _table_data_bytes(raw),
            model_bytes=model,
            detail="raw ingest batch"
            + (" (one packed matrix)" if packed else ""),
        ))
        penv: Dict[str, object] = {
            "Raw": raw, DatasetName.DataStreamRaw: raw,
            "__aux": plan.aux_tables,
        }
        proj_catalog = {"Raw": raw_schema}
        for v in views:
            out = eval_view(v, penv)
            penv[v.name] = out
            stages.append(_view_stage(
                v, _table_data_bytes(out), plan, proj_catalog
            ))
            proj_catalog[v.name] = v.schema
        target = plan.target_of[source]
        env[target] = penv[target]

    for table, slots in plan.ring_slots.items():
        rows = slots * plan.target_caps[table]
        schema = plan.target_schemas[table]
        stages.append(StageCost(
            name=f"ring:{table}", kind="ring", rows=rows,
            hbm_bytes=table_bytes(schema.types, rows),
            model_bytes=table_bytes(schema.types, rows),
            detail=f"{slots} slots x {plan.target_caps[table]} rows "
                   "(device-resident window state)",
        ))
    for vname, ws in plan.pipeline.window_states.items():
        # a windowed GROUP BY the planner decomposed: K slots of per-group
        # partial aggregates instead of the rows (the combined groups are
        # what the view's select reads, under the prefixed name)
        from types import SimpleNamespace

        from ..compile.planner import WINDOW_PARTIALS_PREFIX

        def combined(_env, _base, now, ws=ws):
            state = ws.init()
            return ws.combine(
                state, state.slot_live, state.parts["n"][0], now
            )

        env[WINDOW_PARTIALS_PREFIX + vname] = eval_view(
            SimpleNamespace(fn=combined), env
        )
        stages.append(StageCost(
            name=f"window-state:{vname}", kind="ring", rows=ws.groups,
            hbm_bytes=ws.state_bytes, model_bytes=ws.state_bytes,
            detail=f"{ws.slots} slots x {ws.groups} groups x "
                   f"{len(ws.parts)} partial aggregates over {ws.window} "
                   "(device-resident window state)",
        ))
    for wname, (table, dur_s) in plan.windows.items():
        if table not in plan.ring_slots:
            continue
        rows = plan.ring_slots[table] * plan.target_caps[table]
        schema = plan.target_schemas[table]
        t = make_table(schema, rows)
        env[wname] = t
        stages.append(StageCost(
            name=wname, kind="window", rows=rows,
            hbm_bytes=_table_data_bytes(t),
            model_bytes=table_bytes(schema.types, rows),
            detail=f"{dur_s:g}s window over {table}",
        ))
    for sname, (schema, cap) in plan.state.items():
        t = make_table(schema, cap)
        env[sname] = t
        # display names are prefixed: an accumulation table is BOTH a
        # state input and (by the same name) a pipeline view output
        stages.append(StageCost(
            name=f"state:{sname}", kind="state", rows=cap,
            hbm_bytes=_table_data_bytes(t),
            model_bytes=table_bytes(schema.types, cap),
            detail="accumulation table",
        ))
    for rname, (schema, cap) in plan.refdata.items():
        t = make_table(schema, cap)
        env[rname] = t
        stages.append(StageCost(
            name=f"refdata:{rname}", kind="refdata", rows=cap,
            hbm_bytes=_table_data_bytes(t),
            model_bytes=table_bytes(schema.types, cap),
            detail="reference data (replicated)",
        ))

    for view in plan.pipeline.views:
        out = eval_view(view, env)
        env[view.name] = out
        stage = _view_stage(
            view, _table_data_bytes(out), plan, plan.pipeline.catalog
        )
        if view.name in plan.output_datasets:
            # the sync-stage wire cost: this output's table crosses
            # the device->host boundary at its capacity, every batch
            stage.d2h_bytes = d2h_transfer_bytes(
                view.schema.types, view.plan, view.capacity
            )
        stages.append(stage)
    # the counts vector the step packs (runtime/processor.py
    # build_step_fn: the input count, per output its row count and two
    # overflow slots, per source target its projected count): the
    # batch's one blocking read, and part of every batch's D2H bytes
    n = 1 + 3 * len(plan.output_datasets) + len(plan.target_of)
    stages.append(StageCost(
        name="sync:counts", kind="sync", rows=n,
        hbm_bytes=4 * n, model_bytes=4 * n, d2h_bytes=4 * n,
        detail="packed int32 counts vector (the batch's sync point)",
    ))
    return stages


def _abstract_eval(plan: FlowDevicePlan) -> List[StageCost]:
    base = jax.ShapeDtypeStruct((), jnp.int32)
    now = jax.ShapeDtypeStruct((), jnp.int32)

    def eval_view(view, env):
        return jax.eval_shape(view.fn, env, base, now)

    return _stage_walk(plan, table_struct, eval_view)


def materialized_stage_bytes(plan: FlowDevicePlan) -> Dict[str, int]:
    """Ground truth for the drift test: run every compiled view ONCE on
    real (zero-filled) tables and return actual bytes per stage name.
    CPU-sized capacities only — this executes the plan."""
    base = jnp.asarray(0, jnp.int32)
    now = jnp.asarray(0, jnp.int32)

    def eval_view(view, env):
        return view.fn(env, base, now)

    return {
        s.name: s.hbm_bytes
        for s in _stage_walk(plan, _real_table, eval_view)
    }


# ---------------------------------------------------------------------------
# DX2xx lints over the compiled plan
# ---------------------------------------------------------------------------
def _lint(
    plan: FlowDevicePlan,
    diags: List[Diagnostic],
    match_matrix_budget: int,
) -> None:
    for view in plan.pipeline.views:
        p = view.plan
        if p is None:
            continue
        if p.grouped and p.group_key_cols:
            cards = [
                plan.declared_cardinality.get(c) for c in p.group_key_cols
            ]
            if cards and all(c is not None for c in cards):
                product = 1
                for c in cards:
                    product *= c
                if product > p.groups_bound:
                    diags.append(make(
                        "DX200", view.name,
                        f"group keys {list(p.group_key_cols)} have declared "
                        f"cardinality {product} but the static group "
                        f"capacity is {p.groups_bound} (process.maxgroups); "
                        f"overflow groups drop and surface only as "
                        f"Output_{view.name}_GroupsDropped",
                    ))
                elif (
                    view.name in plan.output_datasets
                    and view.capacity > D2H_OVERSIZE_FACTOR * product
                ):
                    per_batch = d2h_transfer_bytes(
                        view.schema.types, p, view.capacity
                    )
                    diags.append(make(
                        "DX206", view.name,
                        f"output capacity {view.capacity} exceeds the "
                        f"modeled group count {product} by more than "
                        f"{D2H_OVERSIZE_FACTOR}x: every batch moves "
                        f"{per_batch} D2H bytes of mostly padding "
                        f"through the sync stage; a tighter "
                        f"process.maxgroups shrinks it toward the wire "
                        f"minimum",
                    ))
        for s in p.joins:
            if s.out_rows < s.left_rows:
                diags.append(make(
                    "DX201", view.name,
                    f"join output capacity {s.out_rows} is below the left "
                    f"input capacity {s.left_rows} "
                    f"(vs {s.right_table}): even a 1:1 match overflows, "
                    f"dropped pairs surface only as "
                    f"Output_{view.name}_JoinRowsDropped",
                ))
            pairs = s.left_rows * s.right_rows
            if s.algorithm == "match-matrix" and pairs > match_matrix_budget:
                diags.append(make(
                    "DX203", view.name,
                    f"non-equi ON terms force the O(n*m) match matrix: "
                    f"{s.left_rows} x {s.right_rows} = {pairs} pair "
                    f"evaluations per batch (budget "
                    f"{match_matrix_budget}); the sort-merge path needs "
                    f"a pure equality ON",
                ))
    if (
        plan.dict_max_size is not None
        and plan.declared_strings > plan.dict_max_size
    ):
        diags.append(make(
            "DX202", "",
            f"string dictionary capacity {plan.dict_max_size} is below "
            f"the declared/sampled distinct string-value count "
            f"{plan.declared_strings}; over-capacity keys collapse to "
            f"NULL (watch Input_string_dictionary_overflow_Count)",
        ))
    if plan.udf_refresh_names:
        diags.append(make(
            "DX204", "",
            f"UDF(s) {sorted(plan.udf_refresh_names)} declare interval "
            "refresh: every state change re-traces and re-compiles the "
            "whole jitted step",
        ))
    if plan.uses_string_ops and plan.dict_max_size is None:
        diags.append(make(
            "DX204", "",
            "device string ops with an unbounded dictionary: dictionary "
            "growth past the aux-table capacity re-traces the jitted "
            "step; set process.stringdictionary.maxsize",
        ))
    for wname, (_table, dur_s) in plan.windows.items():
        retention_ms = (dur_s + plan.watermark_s) * 1000.0
        if retention_ms > INT32_MS_HORIZON * REBASE_PROXIMITY_FRACTION:
            diags.append(make(
                "DX205", wname,
                f"window retention {retention_ms / 86_400_000.0:.1f} days "
                f"is past {int(REBASE_PROXIMITY_FRACTION * 100)}% of the "
                "int32 relative-millis horizon (~24.8 days); ring "
                "timestamps approach the rebase overflow guard",
            ))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def _output_model(bundle: FlowDevicePlan) -> Dict[str, dict]:
    """Per-OUTPUT modeled row occupancy: the declared-cardinality bound
    for grouped views (capped by the static group capacity), the padded
    capacity otherwise. The DX502 baseline."""
    out: Dict[str, dict] = {}
    for view in bundle.pipeline.views:
        if view.name not in bundle.output_datasets:
            continue
        p = view.plan
        rows = view.capacity
        if p is not None and p.grouped:
            rows = p.groups_bound
            cards = [
                bundle.declared_cardinality.get(c)
                for c in (p.group_key_cols or ())
            ]
            if cards and all(c is not None for c in cards):
                product = 1
                for c in cards:
                    product *= c
                rows = min(rows, product)
        out[view.name] = {
            "rows": int(rows), "capacity": int(view.capacity),
        }
    return out


def _analyze(
    bundle: Optional[FlowDevicePlan],
    diags: List[Diagnostic],
    name: str,
    chips: Optional[int],
    match_matrix_budget: int,
) -> DevicePlanReport:
    if bundle is None:
        return DevicePlanReport(
            name, chips or DEFAULT_CHIPS, [], _ordered(diags)
        )
    # lints read only the recorded plan — run them before abstract eval
    # so a plan that cannot even trace (e.g. a match matrix past the
    # int32 index space) still gets its capacity/cliff diagnostics
    _lint(bundle, diags, match_matrix_budget)
    try:
        stages = _abstract_eval(bundle)
    except Exception as e:  # noqa: BLE001 — abstract eval blowup is a finding
        diags.append(make("DX290", "", f"device plan evaluation failed: {e}"))
        return DevicePlanReport(bundle.name, bundle.chips, [], _ordered(diags))
    return DevicePlanReport(
        bundle.name, bundle.chips, stages, _ordered(diags),
        outputs=_output_model(bundle),
    )


def analyze_flow_device(
    flow: dict,
    chips: Optional[int] = None,
    match_matrix_budget: int = DEFAULT_MATCH_MATRIX_BUDGET,
) -> DevicePlanReport:
    """Device-plan analysis of a flow config (gui JSON or full flow
    document). Pure abstract interpretation: compiles with the
    production planner, derives shapes with ``jax.eval_shape``, touches
    no device."""
    gui = flow.get("gui") if isinstance(flow.get("gui"), dict) else flow
    diags: List[Diagnostic] = []
    bundle = _plan_from_gui(gui, diags, chips)
    return _analyze(
        bundle, diags, gui.get("name") or "", chips, match_matrix_budget
    )


def analyze_processor(
    proc,
    chips: Optional[int] = None,
    match_matrix_budget: int = DEFAULT_MATCH_MATRIX_BUDGET,
) -> DevicePlanReport:
    """Device-plan analysis of an already-built ``FlowProcessor`` — the
    exact compiled views the jitted step runs."""
    diags: List[Diagnostic] = []
    bundle = flow_plan_from_processor(proc, chips)
    return _analyze(bundle, diags, bundle.name, chips, match_matrix_budget)
