"""Flow static analyzer CLI.

    python -m data_accelerator_tpu.analysis flow.json [flow2.json ...]
        [--json] [--device] [--chips=N] [--udfs]
        [--fleet] [--fleet-spec=spec.json]
        [--compile] [--manifest=m.json] [--manifest-out=m.json]
        [--mesh] [--race] [--protocol] [--conf] [--all]

Each argument is a flow config file: either a designer gui JSON or a
full flow document (``{"gui": {...}}``). Prints one line per diagnostic
(or, with ``--json``, a machine-readable report per file) and exits
non-zero when any file has error-severity diagnostics — the CI
self-lint contract.

``--device`` additionally runs the device-plan tier
(``analysis/deviceplan.py``): abstract interpretation of the compiled
plan under ``JAX_PLATFORMS=cpu`` — no device execution — printing the
per-stage HBM/FLOP/ICI cost report and the DX2xx lints. Exit codes
cover the device tier identically: its error diagnostics fail the run
the same way the semantic tier's do. ``--chips=N`` sets the chip count
for the ICI model (default 16, the v5e-16 north-star slice).

``--udfs`` additionally runs the UDF tier (``analysis/udfcheck.py``):
every declared UDF/UDAF resolves through the production loader and its
device functions' ASTs are abstract-interpreted under a taint lattice,
emitting the DX3xx tracing-safety/purity/determinism lints. Same exit
contract.

``--fleet`` runs the fleet tier (``analysis/fleetcheck.py``) over ALL
given flows AS A SET: first-fit-decreasing placement of each flow's
DX2xx HBM total onto the fleet's chips plus the DX4xx capacity/
interference lints, printing the placement plan (chip -> flows ->
packed HBM/headroom). ``--fleet-spec=<file.json>`` overrides the
default fleet (8 chips x 16 GiB, the MULTICHIP slice); keys: chips,
hbmPerChipBytes, headroomFraction, d2hBytesPerSecPerChip,
iciBytesPerSecPerChip, iciTopology. With ``--json`` the report gains a
``fleet`` section carrying the placement plan. Same exit contract.

``--compile`` runs the compile-surface tier
(``analysis/compilecheck.py``): every jit entry point the flow will
ever dispatch — the fused step plus one transfer helper per reachable
(output x pow2 capacity bucket) — is enumerated and lowered over
``jax.eval_shape`` avals (tracing only, no device execution), the
DX6xx finiteness/stability lints run, and the AOT **compile manifest**
is emitted (in ``--json`` under ``compile.manifest``;
``--manifest-out=<file>`` writes it standalone — single flow only).
``--manifest=<file>`` additionally checks a previously emitted manifest
for drift against the fresh lowering (DX602 donation mismatch, DX603
aval/digest drift). Same exit contract.

``--mesh`` runs the mesh-sharding tier (``analysis/meshcheck.py``):
the flow's static SPMD partition plan — per-stage shard axis, forced
reshard edges, closed-form collective bytes — with the DX7xx lints,
cross-checked EXACTLY against a real ``Mesh``+``NamedSharding``
lowering (the CLI virtualizes CPU devices for the check when the
backend has fewer than the requested chips). ``--chips=N`` sets the
mesh size (default 8, the MULTICHIP slice); the one ``--chips`` flag
feeds the device tier's ICI model and the mesh tier alike, and a
non-positive or non-integer value exits 2. Same exit contract.

``--race`` runs the buffer-lifetime/concurrency tier
(``analysis/racecheck.py``): unlike the flow tiers its subject is the
ENGINE the flow deploys onto — every ``runtime/``, ``lq/`` and
``pilot/`` module is abstract-interpreted under a buffer-provenance
lattice (donated ring / pool slot / plain), emitting
the DX8xx lints: escaped donated/pooled views (DX800), unannotated
zero-copy ``asarray`` (DX801), lockset/lock-ordering violations
(DX802), and blocking
syncs on non-blocking threads (DX804). A clean report certifies the
runtime for ANY flow, so the result is cached per engine-source state.
Same exit contract — this is the standing CI race gate.

``--protocol`` runs the exactly-once delivery-protocol tier
(``analysis/protocheck.py``): like ``--race`` its subject is the
ENGINE — every ``runtime/``, ``lq/`` and ``pilot/`` module plus the
rescale handoff in ``serve/jobs.py`` — per entry point a typed effect
trace of protocol events (sink emit, durable write, pointer flip,
FIFO ack, offset commit, state push, requeue, drain) is extracted and
checked against the declared ordering spec
(``analysis/protospec.py``), emitting the DX90x lints: ack before
durability (DX900), pointer flip before sink emit (DX901), double ack
(DX902), uncovered requeue window (DX903), effects outside the
requeue scope (DX904) and a successor dispatched before its handoff
pull (DX905). Cached per engine-source state; same exit contract —
this is the CI gate the exchange-plane and drain-protocol work builds
behind.

``--conf`` runs the configuration-lattice tier
(``analysis/confcheck.py``): both sides of the flow's conf contract —
the ENGINE side (every ``conf.get`` site in the runtime/serving
packages) and the GENERATION side (S400 gui tokens, S640 knob tables,
S650 flat keys, the flattener template) — are scanned and checked
against the ONE typed registry in ``analysis/confspec.py``, emitting
the DX10xx lints: runtime reads nothing can produce (DX1000),
generated-but-never-read dead conf (DX1001), broken designer
knob→token→key chains (DX1002), default-value drift between layers
(DX1003), plus type/bounds violations (DX1004) and incompatible-knob
combinations (DX1005) in THIS flow's effective conf. Cached per
engine-source state; same exit contract — the runtime half of the
same registry is the host's ``ConfAudit`` (DX1006).

``--all`` runs every tier in one invocation (semantic + device + udfs
+ fleet + compile + mesh + race + protocol + conf) with one merged
``--json`` report (single ``schemaVersion``, combined diagnostics,
same 0/1/2 exit contract) — one CI call instead of nine flags.

Unknown ``--`` flags are rejected with exit 2 (a typo like ``--devcie``
must not silently skip a tier and report a false clean pass).

Exit codes: 0 clean (warnings allowed) · 1 errors found · 2 usage/IO.
"""

from __future__ import annotations

import json
import os
import sys
from typing import List, Optional


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


def _fmt_count(n: float) -> str:
    for unit in ("", "k", "M", "G"):
        if abs(n) < 1000.0 or unit == "G":
            return f"{n:.1f}{unit}" if unit else f"{int(n)}"
        n /= 1000.0
    return f"{n:.1f}G"


def _print_device_plan(path: str, device) -> None:
    totals = device.totals()
    print(
        f"{path}: device plan ({device.chips} chips): "
        f"{len(device.stages)} stage(s), "
        f"HBM {_fmt_bytes(totals['hbmBytes'])} "
        f"(persistent {_fmt_bytes(totals['persistentBytes'])}, "
        f"per-batch {_fmt_bytes(totals['perBatchBytes'])}), "
        f"~{_fmt_count(totals['flops'])} FLOP/batch, "
        f"ICI {_fmt_bytes(totals['iciBytesPerBatch'])}/batch"
    )
    lm = device.latency_model()
    lt = lm["totals"]
    ici = f" + ICI {lt['iciMs']:.3f} ms" if lt["iciMs"] else ""
    print(
        f"{path}: roofline latency ({lm['profileSource']} profile): "
        f"device step {lt['deviceStepMs']:.3f} ms "
        f"+ D2H {lt['d2hMs'] or 0:.3f} ms{ici} = "
        f"{lt['batchMs']:.3f} ms/batch lower bound"
    )
    for s in device.stages:
        line = (
            f"{path}:   [{s.kind}] {s.name} rows={s.rows} "
            f"hbm={_fmt_bytes(s.hbm_bytes)}"
        )
        if s.flops:
            line += f" flops={_fmt_count(s.flops)}"
        if s.ici_bytes:
            line += f" ici={_fmt_bytes(s.ici_bytes)}"
        if s.transient_bytes:
            line += f" transient={_fmt_bytes(s.transient_bytes)}"
        if s.detail:
            line += f" ({s.detail})"
        print(line)


def _print_mesh_plan(path: str, mesh) -> None:
    t = mesh.totals()
    state = "validated" if mesh.validated else "UNVALIDATED"
    print(
        f"{path}: mesh plan ({mesh.chips} chips, {state}): "
        f"{len(mesh.stages)} stage(s), "
        f"ICI {_fmt_bytes(t['iciWireBytesPerBatch'])}/batch wire "
        f"({_fmt_bytes(t['iciResultBytesPerBatch'])} result, "
        f"{t['reshardCount']} reshard(s)), "
        f"per-chip HBM {_fmt_bytes(t['perChipHbmBytes'])}"
    )
    for s in mesh.stages:
        line = (
            f"{path}:   [{s.kind}] {s.name} axis={s.axis} rows={s.rows} "
            f"per-chip={_fmt_bytes(s.per_chip_bytes)}"
        )
        if s.ici_wire_bytes:
            line += f" ici={_fmt_bytes(s.ici_wire_bytes)}"
        if s.detail:
            line += f" ({s.detail})"
        print(line)


def _print_fleet_plan(fleet) -> None:
    spec = fleet.spec
    plan = fleet.placement
    state = "feasible" if plan.feasible else "INFEASIBLE"
    print(
        f"fleet: {len(fleet.footprints)} flow(s) on {spec.chips} chip(s) "
        f"x {_fmt_bytes(spec.hbm_per_chip_bytes)} HBM "
        f"({spec.ici_topology}): {state}"
    )
    for chip in plan.chips:
        if not chip.flows:
            continue
        util = chip.utilization(spec)
        print(
            f"fleet:   chip {chip.chip}: {', '.join(chip.flows)} — "
            f"HBM {_fmt_bytes(chip.hbm_bytes)} ({util:.1%} used, "
            f"headroom {1 - util:.1%})"
        )
    for name in plan.oversized:
        print(f"fleet:   oversized (no chip fits): {name}")
    for name in plan.unplaced:
        print(f"fleet:   unplaced (fleet oversubscribed): {name}")
    for name in plan.unanalyzed:
        print(f"fleet:   unanalyzed (no device footprint): {name}")


# flags the CLI understands; anything else --prefixed is a usage error
# (a typo like --devcie must not silently skip a tier)
KNOWN_FLAGS = {"--json", "--device", "--udfs", "--fleet", "--compile",
               "--mesh", "--race", "--protocol", "--conf", "--all"}
KNOWN_VALUE_FLAGS = ("--chips=", "--fleet-spec=", "--manifest=",
                     "--manifest-out=")


def main(argv: List[str]) -> int:
    # the device tier must never touch an accelerator: force abstract
    # eval on the CPU backend before any jax import
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    as_json = "--json" in argv
    all_tiers = "--all" in argv
    device_tier = "--device" in argv or all_tiers
    udf_tier = "--udfs" in argv or all_tiers
    fleet_tier = "--fleet" in argv or all_tiers
    compile_tier = "--compile" in argv or all_tiers
    mesh_tier = "--mesh" in argv or all_tiers
    race_tier = "--race" in argv or all_tiers
    protocol_tier = "--protocol" in argv or all_tiers
    conf_tier = "--conf" in argv or all_tiers
    chips: Optional[int] = None
    fleet_spec_path: Optional[str] = None
    manifest_path: Optional[str] = None
    manifest_out: Optional[str] = None
    for a in argv:
        if not a.startswith("--"):
            continue
        if a in KNOWN_FLAGS:
            continue
        if a.startswith("--chips="):
            # one shared, typed chip-count parser for every tier that
            # consumes N (device ICI model, mesh plan, fleet spec) — a
            # --chips=0 typo exits 2 instead of modeling nothing
            from .chipcount import ChipCountError, parse_chip_count

            try:
                chips = parse_chip_count(a.split("=", 1)[1], "--chips")
            except ChipCountError as e:
                print(str(e), file=sys.stderr)
                return 2
        elif a.startswith("--fleet-spec="):
            fleet_spec_path = a.split("=", 1)[1]
        elif a.startswith("--manifest="):
            manifest_path = a.split("=", 1)[1]
        elif a.startswith("--manifest-out="):
            manifest_out = a.split("=", 1)[1]
        else:
            print(f"unknown flag: {a}", file=sys.stderr)
            print(__doc__.strip(), file=sys.stderr)
            return 2
    paths = [a for a in argv if not a.startswith("--")]
    if not paths:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    if manifest_out and len(paths) > 1:
        print("--manifest-out accepts a single flow", file=sys.stderr)
        return 2

    if mesh_tier and "xla_force_host_platform_device_count" not in (
        os.environ.get("XLA_FLAGS", "")
    ):
        # the mesh cross-check lowers under a real Mesh: virtualize
        # enough CPU devices (capped — result bytes are N-independent,
        # so an 8-device check validates any --chips). Must happen
        # before the first jax import below.
        n = min(chips or 8, 8)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        ).strip()

    from .analyzer import analyze_flow
    from .compilecheck import analyze_flow_compile
    from .confcheck import analyze_flow_conf
    from .deviceplan import analyze_flow_device, combined_report_dict
    from .diagnostics import REPORT_SCHEMA_VERSION
    from .meshcheck import analyze_flow_mesh
    from .protocheck import analyze_flow_protocol
    from .racecheck import analyze_flow_race
    from .udfcheck import analyze_flow_udfs

    shipped_manifest = None
    if manifest_path is not None:
        try:
            with open(manifest_path, "r", encoding="utf-8") as f:
                shipped_manifest = json.load(f)
        except (OSError, ValueError) as e:
            print(
                f"{manifest_path}: cannot read manifest: {e}",
                file=sys.stderr,
            )
            return 2

    fleet_spec = None
    if fleet_spec_path is not None:
        from .fleetcheck import load_fleet_spec

        try:
            fleet_spec = load_fleet_spec(fleet_spec_path)
        except (OSError, ValueError, KeyError) as e:
            print(
                f"{fleet_spec_path}: cannot read fleet spec: {e}",
                file=sys.stderr,
            )
            return 2

    any_errors = False
    json_out = []
    flows: List[dict] = []
    for path in paths:
        try:
            with open(path, "r", encoding="utf-8") as f:
                flow = json.load(f)
        except (OSError, ValueError) as e:
            print(f"{path}: cannot read flow config: {e}", file=sys.stderr)
            return 2
        flows.append(flow)
        report = analyze_flow(flow)
        device = analyze_flow_device(flow, chips=chips) if device_tier else None
        udfs = analyze_flow_udfs(flow) if udf_tier else None
        comp = (
            analyze_flow_compile(flow, manifest=shipped_manifest)
            if compile_tier else None
        )
        mesh = analyze_flow_mesh(flow, chips=chips) if mesh_tier else None
        race = analyze_flow_race(flow) if race_tier else None
        protocol = (
            analyze_flow_protocol(flow) if protocol_tier else None
        )
        conf = analyze_flow_conf(flow) if conf_tier else None
        any_errors |= not report.ok
        if device is not None:
            any_errors |= not device.ok
        if udfs is not None:
            any_errors |= not udfs.ok
        if comp is not None:
            any_errors |= not comp.ok
            if manifest_out and comp.manifest is not None:
                with open(manifest_out, "w", encoding="utf-8") as f:
                    json.dump(comp.manifest, f, indent=1)
        if mesh is not None:
            any_errors |= not mesh.ok
        if race is not None:
            any_errors |= not race.ok
        if protocol is not None:
            any_errors |= not protocol.ok
        if conf is not None:
            any_errors |= not conf.ok
        if as_json:
            if (
                device is not None or udfs is not None
                or comp is not None or mesh is not None
                or race is not None or protocol is not None
                or conf is not None
            ):
                json_out.append({
                    "file": path,
                    **combined_report_dict(
                        report, device, udfs, compile_surface=comp,
                        mesh=mesh, race=race, protocol=protocol,
                        conf=conf,
                    ),
                })
            else:
                json_out.append({"file": path, **report.to_dict()})
        else:
            diags = list(report.diagnostics) + (
                list(device.diagnostics) if device is not None else []
            ) + (list(udfs.diagnostics) if udfs is not None else []) + (
                list(comp.diagnostics) if comp is not None else []
            ) + (list(mesh.diagnostics) if mesh is not None else []) + (
                list(race.diagnostics) if race is not None else []
            ) + (
                list(protocol.diagnostics) if protocol is not None else []
            ) + (list(conf.diagnostics) if conf is not None else [])
            for d in diags:
                print(f"{path}: {d.render()}")
            n_e = len([d for d in diags if d.is_error])
            n_w = len(diags) - n_e
            print(f"{path}: {n_e} error(s), {n_w} warning(s)")
            if device is not None and device.stages:
                _print_device_plan(path, device)
            if udfs is not None and udfs.udfs:
                for u in udfs.udfs:
                    roles = ",".join(u.analyzed) or "none"
                    print(
                        f"{path}: udf {u.name} [{u.tier}] "
                        f"{u.kind or 'unloadable'} ({u.path}) "
                        f"analyzed={roles}"
                    )
            if comp is not None and comp.entries:
                cd = comp.compile_dict()
                print(
                    f"{path}: compile surface: {cd['entries']} program "
                    f"(the step), "
                    f"{'stable' if cd['stable'] else 'OPEN'}"
                )
            if mesh is not None and mesh.stages:
                _print_mesh_plan(path, mesh)
            if race is not None:
                rd = race.race_dict()
                print(
                    f"{path}: race gate: {rd['analyzedFiles']} engine "
                    f"module(s) analyzed, "
                    f"{rd['allowedZeroCopySites']} pinned zero-copy "
                    f"site(s), {rd['ownerHandoffSites']} owner "
                    f"handoff(s)"
                )
            if protocol is not None:
                pd = protocol.protocol_dict()
                print(
                    f"{path}: protocol gate: {pd['analyzedFiles']} "
                    f"engine module(s) analyzed, "
                    f"{pd['effectEvents']} effect event(s), "
                    f"{pd['postCommitSites']} pinned post-commit "
                    f"site(s), {pd['requeueUpstreamSites']} "
                    f"requeue-upstream site(s)"
                )
            if conf is not None:
                cf = conf.conf_dict()
                print(
                    f"{path}: conf gate: {cf['analyzedFiles']} "
                    f"module(s) scanned, {cf['readSites']} read "
                    f"site(s) / {cf['readKeys']} key(s), "
                    f"{cf['producedKeys']} produced key(s), "
                    f"{cf['registryKeys']} registry row(s)"
                )

    fleet = None
    if fleet_tier:
        from .fleetcheck import analyze_fleet_flows

        fleet = analyze_fleet_flows(flows, spec=fleet_spec)
        any_errors |= not fleet.ok
        if not as_json:
            for d in fleet.diagnostics:
                print(f"fleet: {d.render()}")
            print(
                f"fleet: {len(fleet.errors)} error(s), "
                f"{len(fleet.warnings)} warning(s)"
            )
            _print_fleet_plan(fleet)

    if as_json:
        if fleet is not None:
            print(json.dumps({
                "schemaVersion": REPORT_SCHEMA_VERSION,
                "files": json_out,
                **fleet.to_dict(),
            }, indent=2))
        else:
            print(json.dumps(json_out if len(json_out) > 1 else json_out[0],
                             indent=2))
    return 1 if any_errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
