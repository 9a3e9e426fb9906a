"""Compile-surface analyzer: prove a flow's trace surface closed, then
ship it precompiled.

Fifth analysis tier (the ``--compile`` tier, DX6xx). Every job start,
preemption recovery and restart today pays a full XLA trace+compile at
first dispatch. Shipping serialized compiles ahead of time is only safe
if the set of jit entry points a flow will ever dispatch is **finite
and statically known** — which is exactly what this tier proves:

- it enumerates every entry point the runtime can dispatch — one: the
  fused step function (``runtime/processor.py build_step_fn``; every
  output crosses to the host at its declared capacity, so no other
  program runs on the device),
- derives each entry's trace signature over ``jax.eval_shape`` avals
  and lowers it with ``jax.jit(...).lower()`` — tracing only, no device
  execution, no allocation,
- emits a **compile manifest**: entry -> aval signature, static args,
  donation pattern, lowering digest, and a cache key
  (flow-hash x chip count x entry) — the deployable artifact
  config generation embeds into the conf
  (``datax.job.process.compile.manifest``) and ``FlowProcessor``
  AOT-warms at init instead of first dispatch.

The byte-exactness contract (DX603): the analyzer builds the step with
the SAME ``build_step_fn`` the runtime jits and describes the entry with
the SAME ``step_compile_entry`` the runtime's
``FlowProcessor.derive_compile_entries`` uses — so the emitted manifest
can only disagree with the real lowering when the flow itself changed.

DX6xx codes: DX600 open trace surface (unbounded signature set), DX602
manifest donation/aliasing
mismatch, DX603 manifest-vs-lowering drift, DX690 lowering failure,
DX691 analysis unavailable. DX604 (warm start promised but missed) is
the *runtime* counterpart, surfaced as ``Compile_WarmMiss_Count``
(OBSERVABILITY.md).

LiveQuery kernels are deliberately NOT manifest entries: their query
text is user input, so their trace surface is open by design. They warm
through the persistent compilation cache every ``FlowProcessor`` arms
(``compile/aotcache.py``) instead.
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.config import SettingDictionary, SettingNamespace
from ..core.schema import StringDictionary
from ..runtime.processor import (
    STEP_DONATE_ARGNUMS,
    build_step_fn,
    load_reference_data_tables,
    packed_raw_struct,
    step_compile_entry,
)
from .deviceplan import (
    FlowDevicePlan,
    _ordered,
    _plan_from_gui,
    _STRUCT_DTYPES,
    table_struct,
)
from .diagnostics import Diagnostic, make

# manifest document version; bump when the entry shape changes so a
# runtime can reject a manifest it does not understand
MANIFEST_VERSION = 1


def _aval(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def flow_config_hash(gui: dict) -> str:
    """Stable content hash of a flow config — the flow component of
    every manifest entry's cache key. Canonical JSON so key order and
    whitespace cannot fake a drift."""
    return hashlib.sha256(
        json.dumps(gui, sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()


def lowering_digest(fn, avals, donate: Tuple[int, ...] = ()) -> str:
    """sha256 of the entry's lowered StableHLO text — the ground truth
    a shipped manifest is checked against (DX603). Tracing only: no
    compile, no device execution. Lowered FOR the TPU, the platform
    jobs run on, whatever platform this (design-time, usually
    CPU-only) process has: a Pallas UDF lowers through Mosaic there
    and has no CPU lowering at all."""
    lowered = jax.jit(fn, donate_argnums=tuple(donate)).trace(*avals).lower(
        lowering_platforms=("tpu",)
    )
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


# ---------------------------------------------------------------------------
# Report type
# ---------------------------------------------------------------------------
@dataclass
class CompileSurfaceReport:
    flow: str
    chips: int
    entries: List[dict]
    manifest: Optional[dict]
    diagnostics: List[Diagnostic]
    stable: bool = True

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

    def compile_dict(self) -> dict:
        """The compile-surface portion (no diagnostics) — what the
        designer renders beside the diagnostics list and the CLI's
        ``--json`` report carries under ``compile``."""
        return {
            "flow": self.flow,
            "chips": self.chips,
            "entries": len(self.entries),
            "stable": self.stable,
            "manifest": self.manifest,
        }

    def to_dict(self) -> dict:
        from .diagnostics import REPORT_SCHEMA_VERSION

        return {
            "schemaVersion": REPORT_SCHEMA_VERSION,
            "ok": self.ok,
            "errorCount": len(self.errors),
            "warningCount": len(self.warnings),
            "diagnostics": [d.to_dict() for d in self.diagnostics],
            "compile": self.compile_dict(),
        }


# ---------------------------------------------------------------------------
# Static step-input avals (the analyzer's mirror of
# FlowProcessor._step_input_avals, derived from the flow config alone)
# ---------------------------------------------------------------------------
def _refdata_avals(gui: dict) -> Dict[str, object]:
    """Reference-data table avals: the CSVs load through the SAME
    ``load_reference_data_tables`` the runtime uses (their row count is
    part of the step's trace signature, so there is no abstract
    shortcut). Raises when a declared file is unreadable — surfaced as
    DX691 by the caller."""
    entries = (gui.get("input") or {}).get("referenceData") or []
    if not entries:
        return {}
    conf: Dict[str, str] = {}
    ns = SettingNamespace.JobInputPrefix + "referencedata."
    for rd in entries:
        name = rd.get("id")
        props = rd.get("properties") or {}
        if not name or not props.get("path"):
            continue
        conf[f"{ns}{name}.path"] = props["path"]
        if props.get("delimiter"):
            conf[f"{ns}{name}.delimiter"] = props["delimiter"]
        if props.get("header") is not None:
            conf[f"{ns}{name}.header"] = str(props["header"])
    tables = load_reference_data_tables(
        SettingDictionary(conf), StringDictionary()
    )
    return {
        n: jax.tree_util.tree_map(_aval, t) for n, (_s, t) in tables.items()
    }


def _step_input_avals(bundle: FlowDevicePlan, gui: dict) -> tuple:
    """The 9-argument aval tuple of the fused step, built statically —
    the same structure ``FlowProcessor._step_input_avals`` derives from
    its live device state."""
    raw: Dict[str, object] = {}
    for sname, (raw_schema, cap) in bundle.raw_schemas.items():
        # the raw transfer form is part of the step's trace signature:
        # the bundle has it from ``source_raw_form``, as the runtime does
        if bundle.raw_packed.get(sname):
            raw[sname] = jax.tree_util.tree_map(
                _aval, packed_raw_struct(dict(raw_schema.types), cap)
            )
        else:
            raw[sname] = table_struct(raw_schema, cap)
    from ..runtime.timewindow import WindowBuffers

    rings: Dict[str, object] = {}
    for table, slots in bundle.ring_slots.items():
        schema = bundle.target_schemas[table]
        cap = bundle.target_caps[table]
        rings[table] = WindowBuffers(
            {
                c: jax.ShapeDtypeStruct(
                    (slots, cap), _STRUCT_DTYPES.get(t, jnp.int32)
                )
                for c, t in schema.types.items()
            },
            jax.ShapeDtypeStruct((slots, cap), jnp.bool_),
        )
    for vname, ws in bundle.pipeline.window_states.items():
        # per-slot partial aggregates of a decomposed windowed GROUP BY
        rings[vname] = jax.eval_shape(ws.init)
    state = {
        n: table_struct(schema, cap) for n, (schema, cap) in bundle.state.items()
    }
    refdata = _refdata_avals(gui)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    aux = jax.tree_util.tree_map(_aval, bundle.aux_tables)
    return (raw, rings, state, refdata, scalar, scalar, scalar, scalar, aux)


def _build_step(bundle: FlowDevicePlan, gui: dict):
    """The exact fused step the runtime jits, built from the compiled
    bundle via the shared ``build_step_fn``."""
    proc = gui.get("process") or {}
    targets = list(bundle.target_of.values())
    primary = (
        bundle.target_of.get("default")
        or (targets[0] if targets else "")
    )
    return build_step_fn(
        ts_col=proc.get("timestampColumn") or None,
        windows=dict(bundle.windows),
        output_datasets=list(bundle.output_datasets),
        state_names=list(bundle.state),
        refdata_names=sorted(_source_refdata_names(gui)),
        ring_tables=list(bundle.ring_slots),
        pipeline=bundle.pipeline,
        source_targets=[
            (s, t) for s, t in bundle.target_of.items()
        ],
        proj_views=dict(bundle.projection_views),
        primary_target=primary,
        window_states=dict(bundle.pipeline.window_states),
    )


def _source_refdata_names(gui: dict) -> List[str]:
    return [
        rd.get("id")
        for rd in (gui.get("input") or {}).get("referenceData") or []
        if rd.get("id") and (rd.get("properties") or {}).get("path")
    ]


# ---------------------------------------------------------------------------
# Digests per entry
# ---------------------------------------------------------------------------
def attach_digests(entries: List[dict], step_fn, step_avals: tuple) -> None:
    """Lower every enumerated entry and record its StableHLO digest —
    the manifest side of the DX603 drift contract. Mutates in place."""
    for e in entries:
        e["loweringDigest"] = lowering_digest(
            step_fn, step_avals, tuple(e["donate"])
        )


def build_manifest(
    flow_name: str,
    flow_hash: str,
    entries: List[dict],
    chips: int,
    stable: bool,
) -> dict:
    """Assemble the deployable manifest. Each entry's ``cacheKey`` is
    flow-hash x chip count x entry x aval signature — the coordinate a
    persistent compile cache or a fleet of replicas can dedupe compiled
    executables on."""
    for e in entries:
        e["cacheKey"] = hashlib.sha256(
            f"{flow_hash}|chips={chips}|{e['entry']}|"
            f"{json.dumps(e['avals'], sort_keys=True)}".encode()
        ).hexdigest()[:16]
    return {
        "manifestVersion": MANIFEST_VERSION,
        "flow": flow_name,
        "flowHash": flow_hash,
        "chips": chips,
        "stable": stable,
        "entries": entries,
    }


# ---------------------------------------------------------------------------
# Lints
# ---------------------------------------------------------------------------
def _lint_surface(bundle: FlowDevicePlan, diags: List[Diagnostic]) -> bool:
    """DX600 over the flow's trace surface. Returns ``stable``:
    whether the manifest covers every signature the flow can EVER
    dispatch (False = the initial surface only)."""
    stable = True
    if bundle.udf_refresh_names:
        stable = False
        diags.append(make(
            "DX600", "",
            f"open trace surface: UDF(s) {sorted(bundle.udf_refresh_names)} "
            f"declare interval refresh — every state change rebuilds the "
            f"pipeline and re-traces the fused step with a NEW signature, "
            f"so the signature set is unbounded over the job's lifetime; "
            f"the manifest covers the initial surface only and AOT warm "
            f"degrades to best-effort (runtime re-traces surface as "
            f"Retrace_Count / Compile_WarmMiss_Count)",
        ))
    if bundle.uses_string_ops and bundle.dict_max_size is None:
        stable = False
        diags.append(make(
            "DX600", "",
            "open trace surface: device string ops with an unbounded "
            "dictionary — dictionary growth past the aux-table capacity "
            "re-traces the fused step at a new aux shape per growth "
            "step, so the signature set (and the jit cache) grows "
            "without bound; set process.stringdictionary.maxsize to "
            "close the surface",
        ))
    return stable


def check_manifest(
    manifest: dict, derived: List[dict], diags: List[Diagnostic],
) -> None:
    """Compare a shipped manifest against the freshly derived surface:
    donation disagreements are DX602 (an aliasing bug waiting to donate
    a live buffer), any other entry/aval/lowering disagreement is DX603
    (the manifest no longer describes this flow — re-generate it)."""
    shipped = {
        e.get("entry"): e for e in manifest.get("entries", [])
        if isinstance(e, dict)
    }
    fresh = {e["entry"]: e for e in derived}
    missing = sorted(set(fresh) - set(shipped))
    extra = sorted(set(shipped) - set(fresh))
    if missing or extra:
        diags.append(make(
            "DX603", "",
            f"manifest drift: entry sets disagree with the lowering "
            f"(missing from manifest: {missing or 'none'}; stale in "
            f"manifest: {extra or 'none'}) — regenerate the manifest",
        ))
    for name in sorted(set(shipped) & set(fresh)):
        m, d = shipped[name], fresh[name]
        if list(m.get("donate") or []) != list(d["donate"]):
            diags.append(make(
                "DX602", name,
                f"donation/aliasing mismatch: manifest records donated "
                f"argnums {m.get('donate')} but the runtime contract is "
                f"{d['donate']} — an AOT compile honoring the manifest "
                f"would alias (or fail to alias) buffers the dispatch "
                f"path still reads",
            ))
        drift = []
        if m.get("avals") != d["avals"]:
            drift.append("aval signature")
        if (
            d.get("loweringDigest")
            and m.get("loweringDigest")
            and m["loweringDigest"] != d["loweringDigest"]
        ):
            drift.append("lowering digest")
        if m.get("static") != d["static"]:
            drift.append("static args")
        if drift:
            diags.append(make(
                "DX603", name,
                f"manifest drift on {', '.join(drift)}: the shipped "
                f"manifest no longer matches this flow's lowering — a "
                f"warm start from it would compile anyway (DX604 at "
                f"runtime); regenerate the manifest",
            ))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def analyze_flow_compile(
    flow: dict,
    chips: Optional[int] = None,
    manifest: Optional[dict] = None,
    digests: bool = True,
) -> CompileSurfaceReport:
    """Compile-surface analysis of a flow config (gui JSON or full flow
    document). Pure tracing: compiles with the production planner,
    builds the SAME fused step the runtime jits, lowers every entry
    over ``jax.eval_shape`` avals — no device execution.

    ``manifest``: a previously emitted manifest to check for drift
    (DX602/DX603). ``digests=False`` skips the per-entry StableHLO
    lowering (enumeration + lints only — faster, used by callers that
    only need the signature set)."""
    gui = flow.get("gui") if isinstance(flow.get("gui"), dict) else flow
    name = gui.get("name") or ""
    diags: List[Diagnostic] = []
    plan_diags: List[Diagnostic] = []
    n_chips = chips or 1
    bundle = _plan_from_gui(gui, plan_diags, chips)
    # the bundle builder reports in DX2xx; re-code for this tier
    for d in plan_diags:
        code = "DX690" if d.code == "DX290" else "DX691"
        diags.append(make(code, d.table, d.message, d.span))
    if bundle is None:
        return CompileSurfaceReport(
            name, n_chips, [], None, _ordered(diags), stable=False,
        )
    try:
        step_avals = _step_input_avals(bundle, gui)
    except Exception as e:  # noqa: BLE001 — e.g. unreadable refdata CSV
        diags.append(make(
            "DX691", "",
            f"compile surface unavailable: step input avals cannot be "
            f"derived at design time ({e})",
        ))
        return CompileSurfaceReport(
            name, n_chips, [], None, _ordered(diags), stable=False,
        )
    try:
        step_fn = _build_step(bundle, gui)
        # the trace itself is the check: a step that cannot be traced
        # over these avals is DX690 whether or not digests are wanted
        jax.eval_shape(step_fn, *step_avals)
        entries = [step_compile_entry(step_avals)]
        if digests:
            attach_digests(entries, step_fn, step_avals)
    except Exception as e:  # noqa: BLE001 — any lowering blowup is a finding
        diags.append(make(
            "DX690", "", f"compile-surface lowering failed: {e}"
        ))
        return CompileSurfaceReport(
            name, n_chips, [], None, _ordered(diags), stable=False,
        )
    stable = _lint_surface(bundle, diags)
    if manifest is not None:
        check_manifest(manifest, entries, diags)
    doc = build_manifest(
        name, flow_config_hash(gui), entries, n_chips, stable,
    )
    return CompileSurfaceReport(
        name, n_chips, entries, doc, _ordered(diags), stable=stable,
    )


def analyze_processor_compile(
    proc, manifest: Optional[dict] = None, digests: bool = True,
) -> CompileSurfaceReport:
    """Compile-surface analysis of an already-built ``FlowProcessor`` —
    the exact step function and device state the runtime dispatches
    (the drift-test cross-validation path, mirroring
    ``deviceplan.analyze_processor``)."""
    diags: List[Diagnostic] = []
    entries = proc.derive_compile_entries()
    if digests:
        attach_digests(entries, proc._step_fn, proc._step_input_avals())
    name = proc.dict.get("datax.job.name") or ""
    from .deviceplan import flow_plan_from_processor

    bundle = flow_plan_from_processor(proc)
    stable = _lint_surface(bundle, diags)
    if manifest is not None:
        check_manifest(manifest, entries, diags)
    doc = build_manifest(name, "", entries, 1, stable)
    return CompileSurfaceReport(
        name, 1, entries, doc, _ordered(diags), stable=stable,
    )
