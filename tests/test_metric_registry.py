"""Tier-1 self-check: every metric name the engine emits at runtime is
registered in constants.MetricName (RUNTIME_METRIC_PATTERNS), and the
registry is documented in OBSERVABILITY.md — so a renamed/added metric
cannot silently orphan a dashboard tile or the docs (the
ANALYSIS.md-registry sync pattern from the analyzer PR)."""

import json
import os

import pytest

from data_accelerator_tpu.compile.codegen import CodegenEngine
from data_accelerator_tpu.constants import MetricName
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.obs.metrics import MetricLogger
from data_accelerator_tpu.obs.store import MetricStore
from data_accelerator_tpu.runtime.host import StreamingHost

INPUT_SCHEMA = json.dumps({
    "type": "struct",
    "fields": [
        {"name": "deviceDetails", "type": {"type": "struct", "fields": [
            {"name": "deviceId", "type": "long", "nullable": False,
             "metadata": {"allowedValues": [1, 2, 3]}},
            {"name": "deviceType", "type": "string", "nullable": False,
             "metadata": {"allowedValues": ["DoorLock", "Heating"]}},
            {"name": "status", "type": "long", "nullable": False,
             "metadata": {"allowedValues": [0, 1]}},
        ]}, "nullable": False, "metadata": {}},
    ],
})

# aggregation + plain select so the run emits Output_* counts and the
# GroupsDropped overflow slot; outputs go to a console sink (NOT the
# metric sink — metric-table names are data, not registry members)
QUERIES = (
    "--DataXQuery--\n"
    "DoorEvents = SELECT deviceDetails.deviceId, deviceDetails.status, "
    "eventTimeStamp FROM DataXProcessedInput "
    "WHERE deviceDetails.deviceType = 'DoorLock';\n"
    "--DataXQuery--\n"
    "DoorCounts = SELECT deviceId, COUNT(*) AS Cnt FROM DoorEvents "
    "GROUP BY deviceId;\n"
)


@pytest.fixture
def running_flow_store(tmp_path):
    rc = CodegenEngine().generate_code(QUERIES, "[]", "registry")
    transform_path = tmp_path / "flow.transform"
    transform_path.write_text(rc.code)
    conf = SettingDictionary({
        "datax.job.name": "RegistryCheck",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": INPUT_SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "40",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.input.default.eventhub.checkpointdir": str(tmp_path / "ck"),
        "datax.job.input.default.eventhub.checkpointinterval": "1 second",
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.transform": str(transform_path),
        "datax.job.process.projection": (
            "current_timestamp() AS eventTimeStamp\nRaw.*"
        ),
        "datax.job.output.DoorEvents.console.maxrows": "1",
        "datax.job.output.DoorCounts.console.maxrows": "1",
    })
    store = MetricStore()
    host = StreamingHost(conf)
    host.metric_logger = MetricLogger("DATAX-RegistryCheck", store=store)
    from data_accelerator_tpu.runtime.sinks import (
        OutputDispatcher,
        build_output_operators,
    )

    host.dispatcher = OutputDispatcher(
        build_output_operators(
            conf, host.metric_logger,
            {"DoorEvents": ["DoorEvents"], "DoorCounts": ["DoorCounts"]},
        ),
        host.metric_logger,
    )
    host.run(max_batches=2)
    yield store
    host.stop()


def test_every_runtime_metric_is_registered(running_flow_store):
    store = running_flow_store
    keys = store.keys("DATAX-RegistryCheck:")
    assert keys, "flow emitted no metrics"
    unregistered = sorted(
        k.partition(":")[2]
        for k in keys
        if not MetricName.is_runtime_metric(k.partition(":")[2])
    )
    assert not unregistered, (
        f"unregistered runtime metric names {unregistered} — add them to "
        "constants.MetricName.RUNTIME_METRIC_PATTERNS and document them "
        "in OBSERVABILITY.md"
    )
    # the interesting families actually showed up (the check bites)
    metrics = {k.partition(":")[2] for k in keys}
    assert "Latency-Batch" in metrics
    assert any(m.startswith("Latency-Decode-p") for m in metrics)
    assert any(m.startswith("Input_") for m in metrics)
    assert any(m.startswith("Output_") for m in metrics)
    assert any(m.startswith("Sink_") for m in metrics)
    # what every batch says of itself, whatever its source
    assert {"Batch_Unspanned_Ms", "Host_Preempted_Count",
            "Loop_Late_Ms"} <= metrics


def test_stage_names_round_trip_to_registered_metrics():
    for stage in MetricName.STAGES:
        stem = MetricName.stage_metric(stage)
        for q in (50, 95, 99):
            assert MetricName.is_runtime_metric(f"{stem}-p{q}"), stage


def test_registry_patterns_documented_in_observability_md():
    doc = open(
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "OBSERVABILITY.md"),
        encoding="utf-8",
    ).read()
    for pattern in MetricName.RUNTIME_METRIC_PATTERNS:
        assert pattern in doc, (
            f"registry pattern {pattern!r} missing from OBSERVABILITY.md"
        )
    for stage in MetricName.STAGES:
        assert stage in doc, f"stage {stage!r} missing from OBSERVABILITY.md"


def test_fleet_placement_metrics_are_registered():
    """The Fleet_*/Placement_* names the admission gate and re-planner
    emit (serve/jobs.py FleetAdmissionGate, serve/scheduler.py
    PlacementReplanner) are registry members; emission-side coverage is
    tests/test_fleetcheck.py::test_admission_gate_exports_fleet_metrics."""
    for m in (
        "Fleet_Chips",
        "Fleet_FlowsPlaced",
        "Fleet_FlowsUnplaced",
        "Fleet_MaxChipUtilization",
        "Fleet_Chip0_HbmBytes",
        "Fleet_Chip7_Utilization",
        "Fleet_AdmissionRejected_Count",
        "Placement_Replans_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Fleet_Bogus")
    assert not MetricName.is_runtime_metric("Placement_Chip")


def test_conformance_and_alert_metrics_are_registered():
    """Every Conformance_*/Alerts_* series name the conformance monitor
    and alert engine emit (obs/conformance.py, obs/alerts.py — wired in
    runtime/host.py) resolves through the registry."""
    for m in (
        "Conformance_D2HBytes_Ratio",
        "Conformance_Occupancy_DoorCounts_Ratio",
        "Conformance_Drift_Count",
        "Retrace_Count",
        "Alerts_Firing",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Conformance_Bogus")
    assert not MetricName.is_runtime_metric("Alerts_Bogus")


def test_timemodel_metrics_are_registered():
    """The PR 12 roofline/time-model series resolve through the
    registry: the calibrated machine profile (Calib_*), the live HBM
    watermark sampler, the on-demand profiler counter, and the
    DX520/DX522 conformance ratio gauges."""
    for m in (
        "Calib_HbmReadGBps",
        "Calib_HbmWriteGBps",
        "Calib_FlopsGFlops",
        "Calib_DispatchOverheadUs",
        "Calib_D2HGBps",
        "Calib_IciGBps",
        "Hbm_BytesInUse",
        "Hbm_PeakBytes",
        "Profiler_Captures_Count",
        "Conformance_StageTime_DeviceStep_Ratio",
        "Conformance_StageTime_Collect_Ratio",
        "Conformance_Hbm_Ratio",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Calib_Bogus")
    assert not MetricName.is_runtime_metric("Hbm_Bogus")
    assert not MetricName.is_runtime_metric("Conformance_StageTime_Ratio")


def test_background_transfer_metrics_are_registered():
    """The device-resident result path's series (runtime/processor.py
    collect_counts/collect_tables + runtime/host.py background landing)
    resolve through the registry: the counts-only sync's wire bytes,
    the landing backlog/latency gauges; the counters of the deleted
    sized-transfer lattice no longer resolve."""
    for m in (
        "Sync_CountsBytes",
        "Transfer_D2HBytes",
        "Transfer_Efficiency",
        "Transfer_Background_Pending",
        "Transfer_Background_LandMs",
    ):
        assert MetricName.is_runtime_metric(m), m
    for m in (
        "Transfer_SlotContended_Count",
        "Transfer_Overflow_Count",
        "Compile_JitCacheEvict_Count",
    ):
        assert not MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Transfer_Background_Bogus")
    assert not MetricName.is_runtime_metric("Sync_Bogus")


def test_event_wait_metrics_are_registered():
    """The event's wait and what no span holds (runtime/sources.py
    arrival stamps; runtime/host.py _traced_poll, _finish_tail, run)
    resolve through the registry; emission-side coverage is
    tests/test_decode_ahead.py (d) and the store check above, whose
    local-source flow emits the three that need no arrival."""
    for m in (
        "Source_Wait_P50_Ms",
        "Source_Wait_P95_Ms",
        "Source_Wait_Max_Ms",
        "Event_Landing_P50_Ms",
        "Event_Landing_P95_Ms",
        "Batch_Unspanned_Ms",
        "Loop_Late_Ms",
        "Host_Preempted_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    for m in ("Source_Wait_P99_Ms", "Event_Landing_Max_Ms",
              "Batch_Unspanned", "Loop_Late"):
        assert not MetricName.is_runtime_metric(m), m


def test_state_partition_metrics_are_registered():
    """CI satellite: every State_* series the partitioned-state layer
    emits (runtime/statetable.py + runtime/statepartition.py drained at
    collect; State_Partition_Reassigned_Count from JobOperation.rescale
    under DATAX-Fleet) resolves through the registry; emission-side
    coverage is tests/test_statepartition.py and the rescale chaos
    drill (tests/test_chaos.py)."""
    for m in (
        "State_Partition_Count",
        "State_Partition_Owned",
        "State_Partition_Reassigned_Count",
        "State_Handoff_Ms",
        "State_LoadFallback_Count",
        "State_Snapshot_Push_Count",
        "State_Snapshot_Pull_Count",
        "State_IngestFiltered_Count",
        "State_WindowRows_Dropped_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("State_Bogus")
    assert not MetricName.is_runtime_metric("State_Partition_Bogus")


def test_sanitizer_metrics_are_registered():
    """The buffer sanitizer's series (runtime/sanitizer.py, drained at
    collect and by the host checkpoint guard) resolve through the
    registry; emission-side coverage is tests/test_racecheck.py."""
    for m in (
        "Sanitizer_GuardedViews_Count",
        "Sanitizer_PoisonHit_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Sanitizer_Bogus")


def test_protocol_monitor_metrics_are_registered():
    """The protocol monitor's series (runtime/protocolmonitor.py,
    drained into each batch's metric bundle) resolve through the
    registry; emission-side coverage is tests/test_protocheck.py and
    the seeded regression in tests/test_recovery.py."""
    for m in (
        "Protocol_Events_Count",
        "Protocol_Violation_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Protocol_Bogus")


def test_conf_audit_metrics_are_registered():
    """The boot-time conf audit's series (runtime/confaudit.py, emitted
    once at host/LQ-service init) resolve through the registry;
    emission-side coverage is tests/test_confcheck.py."""
    for m in (
        "Conf_Audited_Count",
        "Conf_Unknown_Count",
        "Conf_OutOfBounds_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Conf_Bogus")


def test_lq_serving_metrics_are_registered():
    """Every LQ_* / Latency-LQExec series the LiveQuery serving plane
    emits (lq/service.py export_metrics under DATAX-LiveQuery) resolves
    through the registry; emission-side coverage is
    tests/test_lq.py::TestObservability."""
    for m in (
        "LQ_Sessions",
        "LQ_Tenants",
        "LQ_Qps",
        "LQ_Backlog",
        "LQ_CoalesceFanin",
        "LQ_Dispatch_Count",
        "LQ_Coalesced_Count",
        "LQ_KernelBytes",
        "LQ_KernelEvict_Count",
        "LQ_Admission_Rejected_Count",
        "Latency-LQExec-p50",
        "Latency-LQExec-p95",
        "Latency-LQExec-p99",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("LQ_Bogus")
    assert not MetricName.is_runtime_metric("Latency-LQExec-p42")
    # the serving-plane stage round-trips like every engine stage
    assert "lq-exec" in MetricName.STAGES
    assert MetricName.stage_metric("lq-exec") == "Latency-LQExec"


def test_fleet_telemetry_metrics_are_registered():
    """The fleet telemetry plane's series (obs/publisher.py self-metrics
    under the publishing host's app, obs/fleetview.py aggregator stats)
    and the DX54x delivery-conservation audit counters resolve through
    the registry; emission-side coverage is tests/test_fleetview.py and
    the rescale chaos drill's assert_fleet_view step."""
    for m in (
        "Fleet_Frames_Count",
        "Fleet_Frame_Bytes",
        "Fleet_FramePublish_Ms",
        "Fleet_FramePublishError_Count",
        "Fleet_FrameDecodeError_Count",
        "Fleet_MergeLatency_Ms",
        "Fleet_Replicas_Count",
        "Fleet_StaleReplicas_Count",
        "Conformance_Delivery_Loss_Count",
        "Conformance_Delivery_Duplicate_Count",
        "Conformance_Delivery_StaleReplica_Count",
    ):
        assert MetricName.is_runtime_metric(m), m
    assert not MetricName.is_runtime_metric("Fleet_Bogus")
    assert not MetricName.is_runtime_metric("Fleet_Frame_Bogus")
    assert not MetricName.is_runtime_metric("Conformance_Delivery_Bogus")
    # the named constants stay in lockstep with the pattern table
    assert MetricName.FLEET_FRAMES == "Fleet_Frames_Count"
    assert MetricName.FLEET_FRAME_DECODE_ERROR == "Fleet_FrameDecodeError_Count"
    assert MetricName.DELIVERY_LOSS == "Conformance_Delivery_Loss_Count"


def test_default_alert_rules_validate_and_resolve_for_shipped_flows():
    """CI satellite: the default-generated alert rules are
    schema-valid, and every threshold rule's series name resolves
    through constants.MetricName — for every shipped scenario flow
    (serve/scenarios.py) a generated dashboard/conf would carry them."""
    from data_accelerator_tpu.obs.alerts import default_rules, validate_rules
    from data_accelerator_tpu.serve.scenarios import shipped_flow_guis

    flows = shipped_flow_guis()
    assert flows
    for gui in flows:
        rules = default_rules(gui.get("name"))
        assert validate_rules(rules) == [], gui.get("name")
        for rule in rules:
            metric = rule.get("metric")
            if metric is None:
                continue  # burn-rate rules read health counters
            assert MetricName.is_runtime_metric(metric), (
                f"default rule {rule['name']!r} watches unregistered "
                f"series {metric!r}"
            )


def test_generated_conf_alert_rules_validate(tmp_path):
    """The rules config generation actually writes into a conf parse
    back and pass the schema (the full S620 -> conf -> host round
    trip, on the shipped probe flow)."""
    from data_accelerator_tpu.core.config import parse_conf_lines
    from data_accelerator_tpu.obs.alerts import validate_rules
    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.scenarios import probe_deploy_gui
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    fo = FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "d")),
        LocalRuntimeStorage(str(tmp_path / "r")),
        fleet_admission=False,
    )
    fo.save_flow(probe_deploy_gui())
    res = fo.generate_configs("probe-deploy")
    assert res.ok, res.errors
    props = parse_conf_lines(
        open(res.conf_paths[0], encoding="utf-8").readlines()
    )
    rules = json.loads(props["datax.job.process.alerts.rules"])
    assert validate_rules(rules) == []
    for rule in rules:
        if rule.get("metric"):
            assert MetricName.is_runtime_metric(rule["metric"]), rule
