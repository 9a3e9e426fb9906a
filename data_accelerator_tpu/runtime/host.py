"""StreamingHost: the micro-batch driver loop.

reference: datax-host host/StreamingHost.scala:22-97 — build config,
create the processor, wire the input stream, then per batch: process,
emit metrics, checkpoint offsets every checkpointInterval; per-batch
failures log + rethrow so the batch retries (at-least-once,
CommonProcessorFactory.scala:382-398).

Run one-box:
    python -m data_accelerator_tpu.runtime.host conf=<flow>.conf batches=10
"""

from __future__ import annotations

import logging
import os
import resource
import sys
import tempfile
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import jax

from ..constants import MetricName
from ..core.config import SettingDictionary, SettingNamespace
from ..core.confmanager import ConfigManager
from ..native import packed_shard_bytes
from ..obs import telemetry, tracing
from ..obs.exposition import HealthState, ObservabilityServer
from ..obs.histogram import HISTOGRAMS
from ..obs.metrics import MetricLogger
from ..obs.tracing import Tracer
from .checkpoint import OffsetCheckpointer, WindowStateCheckpointer
from .processor import FlowProcessor
from .sinks import OutputDispatcher, build_output_operators
from .sources import LocalSource, StreamingSource, make_source, row_waits_ms

logger = logging.getLogger(__name__)

# The paced wait (``StreamingHost._pace``) wakes this often to decode the
# lines that have arrived since its last pass.
_AHEAD_SLICE_S = 0.02

# The root's children that make up a batch's chain up to its ``emit``:
# what of that stretch none of them holds is ``Batch_Unspanned_Ms``.
_CHAIN_SPANS = ("decode", "dispatch", "device-step", "collect", "sinks")


def _preemptions() -> int:
    """Involuntary context switches of this process so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw


class StreamingHost:
    def __init__(
        self,
        dict_: SettingDictionary,
        source: Optional[StreamingSource] = None,
        udfs: Optional[dict] = None,
        table_sink_map: Optional[Dict[str, list]] = None,
    ):
        self.dict = dict_
        self.processor = FlowProcessor(dict_, udfs=udfs)
        self.metric_logger = MetricLogger.from_conf(dict_)
        # lifecycle telemetry (AppInsightLogger analog): batch begin/end
        # events + exceptions with app context (AppInsightLogger.scala:18-108)
        self.telemetry = telemetry.from_conf(dict_)
        # batch-granular span tracing + per-stage latency histograms
        # (obs/tracing.py, obs/histogram.py): every stage boundary of
        # every micro-batch is a span in the telemetry fan-out and a
        # sample in the stage's live latency distribution. Span emission
        # is conf-gated (process.telemetry.tracing, default on — the
        # overhead is a handful of clock reads per batch); histograms
        # always observe, they are the /metrics + percentile source.
        # cross-process propagation: when the control plane spawned this
        # host it passed `telemetry.parenttrace=<trace>:<span>` — every
        # batch trace then JOINS the control-plane request's trace, so
        # the flight recorder's span tree for any batch roots in the
        # REST submit that launched the job (obs/tracing.py)
        tele_conf0 = dict_.get_sub_dictionary("datax.job.process.telemetry.")
        self.tracer = Tracer(
            self.telemetry,
            histograms=HISTOGRAMS,
            flow=dict_.get_job_name(),
            enabled=(
                tele_conf0.get_or_else("tracing", "true") or ""
            ).lower() != "false",
            parent=tele_conf0.get("parenttrace"),
        )
        # model-vs-observed conformance: config generation embeds the
        # DX2xx cost-model report (process.conformance.model); the
        # monitor compares windowed observations against it and emits
        # Conformance_* gauges + DX5xx drift events (obs/conformance.py)
        from ..obs.conformance import ConformanceMonitor

        self.conformance = ConformanceMonitor.from_conf(
            dict_, flow=dict_.get_job_name()
        )
        # process.debug.protocolmonitor arms the dynamic half of the
        # DX9xx exactly-once defense (runtime/protocolmonitor.py): the
        # batch tail records its actual protocol-event sequence and
        # every sealed batch's linearization is validated against the
        # declared spec; violations fire runtime DX906
        from .protocolmonitor import from_conf as _protomon_from_conf

        self.protocol_monitor = _protomon_from_conf(
            self.processor.process_conf.get_sub_dictionary("debug.")
        )
        # boot-time conf audit (runtime/confaudit.py): the concrete conf
        # this host started with, replayed through the DX10xx lattice
        # validator — unknown/out-of-bounds keys flight-record DX1006
        # (conf/violation events + Conf_* gauges) instead of being
        # silently ignored. Advisory: never blocks boot.
        from .confaudit import from_conf as _confaudit_from_conf

        self.conf_audit = _confaudit_from_conf(
            dict_,
            subject="host",
            telemetry=self.telemetry,
            metric_logger=self.metric_logger,
        )

        input_conf = dict_.get_sub_dictionary(SettingNamespace.JobInputPrefix)
        # one StreamingSource per declared input source (multi-source
        # flows poll them all each batch; the injected ``source`` arg
        # binds to the primary for back-compat / tests)
        self.sources: Dict[str, StreamingSource] = {}
        for name, spec in self.processor.specs.items():
            if name == self.processor.primary and source is not None:
                self.sources[name] = source
            else:
                self.sources[name] = make_source(
                    spec.conf, spec.schema, source=name
                )
        self.source = self.sources[self.processor.primary]
        if any(hasattr(s, "poll_raw") for s in self.sources.values()):
            # raw-bytes sources decode natively and nothing else: build
            # and load the library NOW so a missing toolchain fails the
            # start with the compiler's stderr (NativeBuildError), not
            # the first batch
            from ..native import load_library

            load_library()
        self.interval_s = self.processor.interval_s
        self.max_rate = int(input_conf.get_or_else("eventhub.maxrate", "1000"))
        # backpressure: when a batch overruns the interval, shrink the
        # next poll; recover multiplicatively when batches are fast
        # (the role maxRate plays statically in the reference — here the
        # effective rate adapts between maxrate/8 and maxrate)
        self._rate_scale = 1.0
        # the sources whose lines the paced wait decodes as they arrive:
        # those that can show them before the poll (``arrived_lines``:
        # the socket source); the step takes the packed matrix the
        # passes fill, on one chip as under a mesh
        self._ahead_sources = {
            name: src for name, src in self.sources.items()
            if hasattr(src, "arrived_lines")
        }
        # (bytes, seconds) of the latest passes: how long the next takes
        self._ahead_passes: deque = deque(maxlen=8)
        # a pass waits for the bytes from which the packed decoder
        # shards, so that the passes stay multi-threaded
        self._ahead_pass_bytes = (
            packed_shard_bytes() if self._ahead_sources else 0
        )

        # offset checkpointing (EventhubCheckpointer semantics)
        ckpt_dir = input_conf.get("eventhub.checkpointdir") or input_conf.get(
            "streaming.checkpointdir"
        )
        self.checkpointer = (
            OffsetCheckpointer(ckpt_dir) if ckpt_dir else None
        )
        # window-state checkpointing (SURVEY §5.4): the offsets file only
        # replays the last batch; ring buffers hold up to window+watermark
        # of history that a restart would silently zero. Persist them on
        # the same cadence and restore on start (the role the Spark
        # StreamingContext checkpoint plays at StreamingHost.scala:83-89).
        self.window_checkpointer = (
            WindowStateCheckpointer(ckpt_dir)
            if ckpt_dir and self.processor.window_buffers
            else None
        )
        self.checkpoint_interval_s = (
            input_conf.get_duration_option("eventhub.checkpointinterval") or 60.0
        )
        self._last_checkpoint = 0.0

        # health/readiness state + the Prometheus/health HTTP surface
        # (/metrics, /healthz, /readyz — obs/exposition.py), served when
        # process.observability.port is set (0 = ephemeral port, useful
        # for tests and one-box)
        obs_conf = dict_.get_sub_dictionary(
            SettingNamespace.JobProcessPrefix + "observability."
        )
        stall_fail = obs_conf.get_double_option("stallfailms")
        # conf'd stall-EWMA half-life (observability.stallewmams): the
        # SAME smoothed gauge feeds /readyz and the pilot's stall
        # signal, so readiness probes and the controller agree on
        # "stalled" by construction
        stall_ewma = obs_conf.get_double_option("stallewmams")
        self.health = HealthState(
            flow=dict_.get_job_name(),
            checkpoint_interval_s=(
                self.checkpoint_interval_s if self.checkpointer else None
            ),
            batch_interval_s=self.interval_s,
            stall_fail_ms=stall_fail,
            stall_ewma_half_life_ms=stall_ewma,
        )
        # declarative alert rules from the generated conf
        # (process.alerts.rules, obs/alerts.py): evaluated every batch
        # finish and on every /alerts-/metrics request over the same
        # store/histogram/health surfaces the dashboards read
        from ..obs.alerts import AlertEngine

        self.alerts = AlertEngine.from_conf(
            dict_,
            flow=dict_.get_job_name(),
            store=self.metric_logger.store,
            histograms=HISTOGRAMS,
            health=self.health,
        )
        # fleet telemetry plane (obs/publisher.py): when
        # process.fleet.publishurl is conf'd, every batch finish folds
        # into a windowed frame shipped to the shared objstore for the
        # control plane's FleetView rollup. None = per-process only.
        from ..obs.publisher import TelemetryFramePublisher

        self.fleet_publisher = TelemetryFramePublisher.from_conf(
            dict_,
            flow=dict_.get_job_name(),
            metric_logger=self.metric_logger,
            histograms=HISTOGRAMS,
        )
        # machine-profile calibration (obs/calibrate.py): ~100 ms of
        # jit micro-probes, process-cached and persisted/shared like
        # the compile cache (observability.calibrationfile /
        # calibrationurl). The profile prices the conf-embedded
        # byte+FLOP model into the DX520/DX521 roofline predictions and
        # exports as the Calib_* series on every batch. Off with
        # observability.calibration=false (the monitor's latency checks
        # then stay disarmed unless conformance.latency pins them).
        self._calib_metrics: Dict[str, float] = {}
        if (obs_conf.get_or_else("calibration", "true") or "").lower() \
                != "false":
            from ..obs.calibrate import get_profile

            try:
                profile = get_profile(
                    cache_file=obs_conf.get("calibrationfile"),
                    share_url=obs_conf.get("calibrationurl"),
                )
                self._calib_metrics = profile.metrics()
                if self.conformance is not None \
                        and not self.conformance.latency_pinned:
                    preds, compute_ms, overhead_ms = (
                        self.conformance.model.latency_predictions(
                            profile.to_dict()
                        )
                    )
                    self.conformance.set_latency(
                        preds, compute_ms, overhead_ms
                    )
            except Exception:  # noqa: BLE001 — calibration is optional
                logger.exception(
                    "machine-profile calibration failed; "
                    "DX52x latency checks disarmed"
                )

        # live HBM watermark sampling (observability.hbmsample, default
        # on): each batch finish samples the device allocator
        # (memory_stats) into Hbm_BytesInUse/Hbm_PeakBytes — the DX522
        # observation. Silently absent on backends that don't report
        # (CPU), exactly like a missing conformance prediction.
        self.hbm_sample = (
            (obs_conf.get_or_else("hbmsample", "true") or "").lower()
            != "false"
        )

        # on-demand profiler surface (obs/profiler.py): POST
        # /profile?seconds=N on the observability port arms a
        # jax.profiler capture that lands beside the flight recorder
        # (observability.profilerdir overrides). Off with
        # observability.profiler=false.
        self.profiler = None
        if (obs_conf.get_or_else("profiler", "true") or "").lower() \
                != "false":
            from ..obs.profiler import ProfilerSurface

            prof_dir = obs_conf.get("profilerdir")
            if not prof_dir:
                tracefile = dict_.get_sub_dictionary(
                    "datax.job.process.telemetry."
                ).get("tracefile")
                base = (
                    os.path.dirname(os.path.abspath(tracefile))
                    if tracefile else tempfile.gettempdir()
                )
                prof_dir = os.path.join(
                    base, f"profiler-{dict_.get_job_name() or 'flow'}"
                )
            self.profiler = ProfilerSurface(
                prof_dir, flow=dict_.get_job_name()
            )

        self.obs_server: Optional[ObservabilityServer] = None
        obs_port = obs_conf.get_int_option("port")
        if obs_port is not None:
            self.obs_server = ObservabilityServer(
                self.health,
                histograms=HISTOGRAMS,
                store=self.metric_logger.store,
                port=obs_port,
                alerts=self.alerts,
                profiler=self.profiler,
            )
            self.obs_server.start()

        if self.checkpointer:
            positions = self.checkpointer.starting_positions()
            for s in self.sources.values():
                s.start(positions)
        # window restore, local first: a plain restart reloads its own
        # window.npz; a RESCALE SUCCESSOR (fresh dirs, objstore mirror
        # configured) pulls only the window partitions its replica
        # index owns and merges them — the handoff path
        self.window_restored_from: Optional[str] = None
        if self.window_checkpointer:
            t_load = time.time()
            snap = self.window_checkpointer.load()
            if snap is not None:
                if self.processor.restore_window_state(snap):
                    self.window_restored_from = "local"
                    logger.info(
                        "restored window state from checkpoint in %.2f s "
                        "(slot counter %d)", time.time() - t_load,
                        snap["slot_counter"],
                    )
                else:
                    # nothing on disk this process builds on: its first
                    # snapshot is whole
                    self.window_checkpointer.forget()
                    logger.warning(
                        "window-state checkpoint incompatible with current "
                        "flow config; starting with empty windows"
                    )
        if (
            self.window_restored_from is None
            and self.processor.window_buffers
            and self.processor.state_mirror is not None
        ):
            try:
                if self.processor.restore_window_partitions():
                    self.window_restored_from = "partitions"
                    logger.info(
                        "restored window state from %d assigned partitions",
                        len(self.processor.state_owned),
                    )
            except Exception:  # noqa: BLE001 — empty windows beat a dead init
                logger.exception(
                    "window partition handoff failed; starting empty"
                )
        self._drain_state_events()

        # sink routing: dataset -> output names; default: each conf output
        # name routes its same-named dataset (S500 contract)
        if table_sink_map is None:
            conf_outputs = dict_.get_sub_dictionary(
                SettingNamespace.JobOutputPrefix
            ).group_by_sub_namespace()
            table_sink_map = {name: [name] for name in conf_outputs}
        operators = build_output_operators(dict_, self.metric_logger, table_sink_map)
        self.dispatcher = OutputDispatcher(operators, self.metric_logger)

        self.batches_processed = 0
        self._stop = False
        # how far past its interval's end the paced loop began the pass
        # that is about to start its batch (``run`` -> ``_start_batch``)
        self._loop_late_ms: Optional[float] = None
        # the process's involuntary context switches when the latest
        # batch reported itself (Host_Preempted_Count)
        self._preempted = _preemptions()

        # background result landing (the device-resident result path):
        # in the pipelined loop the only BLOCKING device read per batch
        # is the packed counts vector; the output tables stream D2H in
        # the background and the batch tail (collect_tables -> sinks ->
        # commit -> ack -> metrics -> checkpoint) runs on this dedicated
        # single-thread landing executor — one worker, so landings stay
        # strictly FIFO while the dispatch loop keeps feeding the
        # device. Off under a mesh, where the tail runs inline.
        self._landing_pool = (
            ThreadPoolExecutor(1, thread_name_prefix="landing")
            if self.processor.mesh is None else None
        )
        self._landings = deque()  # futures of submitted landings, FIFO
        self._landing_failed: Optional[BaseException] = None

        # live pipeline depth: starts at the conf'd depth; the pilot's
        # DepthActuator retargets it (request_depth) and run_pipelined
        # applies the change at a window boundary by draining the
        # in-flight FIFO down to the new depth first, so strict-FIFO
        # commit and whole-window requeue invariants are untouched by a
        # resize
        self._live_depth = max(1, self.processor.pipeline_depth)
        self._depth_target: Optional[int] = None

        # the autopilot (pilot/controller.py, conf
        # datax.job.process.pilot.*, default on): once per evaluation
        # window it maps the observability surface — the stall EWMA
        # /readyz judges, landing backlog, poll saturation, malformed
        # rate, alert-rule action votes — to bounded actuations
        # (pipeline depth, source backpressure, replica count) through
        # typed actuators, every decision a pilot/decide span
        from ..pilot.controller import PilotController

        self.pilot = PilotController.from_conf(dict_, host=self)

    def _drain_state_events(self) -> None:
        """Flight-record the DX53x events the state loaders queued
        (DX530 active-side fallback, DX531 both-sides-bad -> empty):
        typed events beside conformance drift, so a corrupted snapshot
        handoff is visible in `obs trace` output and the recorder."""
        events, self.processor.state_events = (
            self.processor.state_events, []
        )
        for ev in events:
            try:
                self.telemetry.track_event("state/fallback", dict(ev))
            except Exception:  # noqa: BLE001 — telemetry never fails state
                logger.exception("state event emit failed")

    # -- pilot actuation surface ------------------------------------------
    def live_depth(self) -> int:
        """The commanded pipeline depth: the pending pilot target when
        one exists, else the depth the dispatch loop is running (==
        conf'd depth until the pilot retargets it)."""
        return (
            self._depth_target if self._depth_target is not None
            else self._live_depth
        )

    def request_depth(self, depth: int) -> None:
        """Ask the dispatch loop to resize the in-flight window; the
        change applies at the next loop iteration, draining the window
        down to the new depth first (FIFO) when shrinking."""
        self._depth_target = max(1, int(depth))

    def _current_depth(self, depth: int) -> int:
        """Apply a pending pilot depth retarget (loop thread only)."""
        if self._depth_target is not None and self._depth_target != depth:
            logger.info(
                "pilot depth change: %d -> %d", depth, self._depth_target
            )
            depth = self._depth_target
        self._depth_target = None
        self._live_depth = depth
        return depth

    # -- loop -------------------------------------------------------------
    def _poll_limit(self, name: str) -> int:
        """The most rows a poll asks the source for, before the pilot's
        admission has its say."""
        return min(
            self.processor.specs[name].capacity,
            max(1, int(self.max_rate * self.interval_s * self._rate_scale)),
        )

    def _poll_and_encode(self):
        """Poll every source and encode one device batch per source;
        returns (raw dict, consumed offsets, batch_time_ms, t0)."""
        t0 = time.time()
        batch_time_ms = int(t0 * 1000)
        raw: Dict[str, object] = {}
        consumed: Dict = {}
        for name, src in self.sources.items():
            max_events = self._poll_limit(name)
            if self.pilot is not None:
                # source backpressure: the pilot's token bucket is the
                # admission point — at full rate it grants pass-through,
                # under sink/landing pressure it shrinks the poll
                max_events = max(1, self.pilot.admit_events(max_events))
            received = max_events
            malformed0 = self.processor.malformed_rows_total
            if isinstance(src, LocalSource):
                with tracing.span("source-poll"):
                    cols, now_ms, c = src.poll_columns(
                        max_events, self.processor.dictionary
                    )
                raw[name] = self.processor.encode_columns(
                    cols, max_events, source=name
                )
                if len(self.sources) == 1:
                    # single-source fast path: the generator's clock IS
                    # the batch time. Multi-source keeps the one t0 base
                    # computed above — every stream must encode against
                    # the SAME base the dispatch will use, or relative
                    # timestamps shift across a second boundary
                    batch_time_ms = now_ms
            elif hasattr(src, "poll_raw"):
                # native ingest: raw wire bytes -> C++ decoder (newline
                # JSON, or whole Kafka v2 record batches when the
                # source declares raw_format="kafka-v2"); the packed
                # matrix stays numpy (to_device=False) so the
                # decode-ahead worker never touches jax off-thread —
                # the jitted step's call transfers it (under a mesh the
                # encode puts it, a block a chip: ``shard-put``)
                with tracing.span("source-poll"):
                    blob, _n, c = src.poll_raw(max_events)
                received = _n
                # what the wait decoded counts as far as the blob begins
                # with it; the poll's cut is the batch either way
                ahead_bytes = self.processor.decode_ahead_cursor(name)[0] \
                    if getattr(src, "polled_arrived", False) else 0
                raw[name] = self.processor.encode_json_bytes(
                    blob, (batch_time_ms // 1000) * 1000, source=name,
                    to_device=False,
                    fmt=getattr(src, "raw_format", "jsonl"),
                    ahead_bytes=ahead_bytes,
                )
            else:
                with tracing.span("source-poll"):
                    rows, c = src.poll(max_events)
                received = len(rows)
                raw[name] = self.processor.encode_rows(
                    rows, (batch_time_ms // 1000) * 1000, source=name
                )
            # source-side ingest counters (e.g. KafkaSource's malformed
            # record values on the client-library poll path, the wire
            # client's CRC-skipped corrupt batches) merge into the same
            # ingest_stats/malformed_rows_total surface the decoder
            # feeds, so the pilot's flood signal and the Input_*_Count
            # metrics cover Kafka flows too
            take = getattr(src, "take_ingest_stats", None)
            if take is not None:
                for k, v in take().items():
                    if not v:
                        continue
                    self.processor.ingest_stats[k] = (
                        self.processor.ingest_stats.get(k, 0) + v
                    )
                    if k == "malformed_rows":
                        self.processor.malformed_rows_total += v
            if self.pilot is not None:
                # saturation + malformed-rate signals for the window
                self.pilot.observe_poll(
                    max_events, received,
                    self.processor.malformed_rows_total - malformed0,
                )
            consumed.update(c)
        return raw, consumed, batch_time_ms, t0

    def _finish(
        self, handle, consumed, batch_time_ms, t0, trace,
        inflight_depth: int = 1,
        background: bool = False,
    ) -> Optional[Dict[str, float]]:
        """Finish a batch. The CALLING thread pays only the counts-only
        sync (``collect_counts`` — the packed counts vector, a few
        hundred bytes already streaming since dispatch); the tail
        (collect tables -> sinks -> commit -> ack -> metrics ->
        checkpoint) runs inline by default, or — with ``background`` —
        on the dedicated landing thread so the dispatch loop keeps
        feeding the device while results land and sinks ack
        out-of-band. Landings are strictly FIFO (one worker), so
        state-table commits, acks and offset checkpoints keep dispatch
        order at every depth. Failures requeue un-acked source batches
        and rethrow so the batch retries, at-least-once
        (CommonProcessorFactory.scala:382-398); a background landing
        failure is recorded and re-raised on the dispatch loop, which
        then requeues the whole window. ``inflight_depth``: how many
        batches (this one included) were in flight when the window
        forced this finish — the live pipeline depth gauge. Returns the
        batch metrics inline, or None when the tail went to the
        landing thread."""
        stall_ms = 0.0
        try:
            with trace.activate(), tracing.span("sync"):
                # the batch's ONLY blocking device read: the counts
                # vector. The trace separates "rules evaluated"
                # (device-step ends here) from result transport +
                # materialization (collect, backgrounded below).
                sync_t0 = time.time()
                handle.collect_counts()
                # time the dispatch loop actually stalled waiting
                # for the window's oldest batch to leave the device
                stall_ms = (time.time() - sync_t0) * 1000.0
            trace.record_since("device-step", "dispatch-done")
        except Exception as e:
            self.telemetry.track_exception(
                e, {"event": "error/streaming/process", "batchTime": batch_time_ms}
            )
            self.health.record_batch(
                batch_time_ms, ok=False, error=f"{type(e).__name__}: {e}"
            )
            trace.end(status="error")
            handle.abandon()
            if background:
                # let already-queued (earlier, independent) landings ack
                # before the requeue, so the un-acked FIFO can't race
                self._settle_landings()
            for s in self.sources.values():
                s.requeue_unacked()
            logger.exception("batch sync failed; rethrowing for retry")
            raise
        if background and self._landing_pool is not None:
            backlog = self._prune_landings()
            self._landings.append(self._landing_pool.submit(
                self._landing_run, handle, consumed, batch_time_ms, t0,
                trace, inflight_depth, stall_ms, backlog,
            ))
            return None
        return self._finish_tail(
            handle, consumed, batch_time_ms, t0, trace, inflight_depth,
            stall_ms, None, requeue_on_error=True,
        )

    def _landing_run(
        self, handle, consumed, batch_time_ms, t0, trace,
        inflight_depth, stall_ms, backlog,
    ) -> Optional[Dict[str, float]]:
        """One queued landing on the background transfer thread. After
        a recorded failure the rest of the queue drains as no-ops —
        later batches stay un-acked, and the dispatch loop (which
        re-raises the failure) requeues the whole window."""
        if self._landing_failed is not None:
            handle.abandon()
            trace.end(status="aborted")
            return None
        try:
            return self._finish_tail(
                handle, consumed, batch_time_ms, t0, trace, inflight_depth,
                stall_ms, backlog, requeue_on_error=False,
            )
        except Exception as e:  # noqa: BLE001 — re-raised on the loop thread
            self._landing_failed = e
            handle.abandon()
            return None

    def _prune_landings(self) -> int:
        """Drop completed landings from the FIFO; returns the number
        still pending (the background-transfer backlog gauge)."""
        while self._landings and self._landings[0].done():
            self._landings.popleft()
        return len(self._landings)

    def _wait_landing_backlog(self, depth: int) -> None:
        """Backpressure: never let pending landings outgrow the
        pipeline window — a landing thread that can't keep up must
        stall the dispatch loop, not grow an unbounded queue."""
        while self._prune_landings() > depth and self._landing_failed is None:
            try:
                self._landings[0].result(timeout=60)
            except Exception:  # noqa: BLE001 — failures surface via the flag
                pass

    def _check_landing_failure(self) -> None:
        if self._landing_failed is not None:
            raise self._landing_failed

    def _drain_landings(self) -> None:
        """Wait out every queued landing (FIFO), then surface any
        recorded failure on the calling thread."""
        while self._landings:
            self._landings.popleft().result()
        self._check_landing_failure()

    def _settle_landings(self) -> None:
        """Cleanup path: wait for queued landings without raising."""
        while self._landings:
            try:
                self._landings.popleft().result(timeout=60)
            except Exception:  # noqa: BLE001 — cleanup must not mask the cause
                pass

    def _finish_tail(
        self, handle, consumed, batch_time_ms, t0, trace,
        inflight_depth, stall_ms, backlog,
        requeue_on_error: bool = True,
    ) -> Dict[str, float]:
        """The batch tail behind the counts sync: land the
        background-streamed tables, run sinks, commit state, ack
        sources, emit metrics/conformance/alerts, checkpoint."""
        pm = self.protocol_monitor
        try:
            with trace.activate():
                with tracing.span("collect"):
                    datasets, metrics = handle.collect_tables()
                with tracing.span("sinks"):
                    self.dispatcher.dispatch(datasets, batch_time_ms)
                    # when a reader could see the batch's rows: the
                    # instant their age is taken at (Event_Landing_*)
                    landed_ts = time.time()
                if pm is not None:
                    pm.record("SINK_EMIT", detail="dispatcher.dispatch")
                self.processor.commit()
                if pm is not None:
                    pm.record("POINTER_FLIP", detail="processor.commit")
                for name, s in self.sources.items():
                    s.ack()
                    if pm is not None:
                        pm.record("FIFO_ACK", source=name)
        except Exception as e:
            self.telemetry.track_exception(
                e, {"event": "error/streaming/process", "batchTime": batch_time_ms}
            )
            self.health.record_batch(
                batch_time_ms, ok=False, error=f"{type(e).__name__}: {e}"
            )
            trace.end(status="error")
            if requeue_on_error:
                for name, s in self.sources.items():
                    s.requeue_unacked()
                    if pm is not None:
                        pm.record("REQUEUE", source=name)
            if pm is not None:
                pm.seal_batch(batch_time_ms, failed=True)
            logger.exception("batch processing failed; rethrowing for retry")
            raise

        # everything the batch reports about itself, on its critical
        # path: metrics, percentiles, conformance, the recorder's end
        # event, the store flush, alerts, the fleet publisher, the log line
        with trace.activate(), tracing.span("emit"):
            metrics["Latency-Batch"] = (time.time() - t0) * 1000.0
            metrics["IngestRateScale"] = self._rate_scale
            metrics["Pipeline_Depth"] = float(inflight_depth)
            metrics["Pipeline_Stall_Ms"] = stall_ms
            if self.window_checkpointer \
                    and self.window_checkpointer.last_bytes:
                # bytes the last window checkpoint wrote: the head and
                # the slots written since the one before, not the state
                metrics["Checkpoint_Window_Bytes"] = float(
                    self.window_checkpointer.last_bytes
                )
                if self.processor.window_states:
                    # and the slot rows among them (a late row changes
                    # a slot an earlier checkpoint wrote: it is written
                    # again)
                    metrics["Checkpoint_Window_Slots"] = float(
                        self.window_checkpointer.last_slots
                    )
            metrics.update(trace.counters)
            polled_ts = trace.prop("polledTs")
            if polled_ts is not None:
                # the program's own alert latency: every row of the
                # batch landed at one instant, so its age there is its
                # wait at the poll's cut plus the chain from the cut
                chain_ms = (landed_ts - polled_ts) * 1000.0
                for q in ("P50", "P95"):
                    metrics[f"Event_Landing_{q}_Ms"] = \
                        metrics[f"Source_Wait_{q}_Ms"] + chain_ms
            # the time between the chain's spans (commit, acks, the
            # recorder's begin event; a pause that fell between two)
            metrics["Batch_Unspanned_Ms"] = trace.unspanned_ms(_CHAIN_SPANS)
            # since the batch before reported itself, its wait included
            preempted = _preemptions()
            metrics["Host_Preempted_Count"] = float(
                preempted - self._preempted
            )
            self._preempted = preempted
            if backlog is not None:
                # background landing accounting: landings still queued when
                # this one was submitted (sustained > pipeline depth is the
                # default backlog alert), and the ms this batch's streamed
                # tables took to resolve on the landing thread
                metrics["Transfer_Background_Pending"] = float(backlog)
                metrics["Transfer_Background_LandMs"] = \
                    trace.child_ms["collect"]
            self.health.record_stall(stall_ms)
            # the calibrated machine profile rides every batch as Calib_*
            # gauges (constant per process — dashboards see the machine
            # model their roofline ratios are judged against)
            if self._calib_metrics:
                metrics.update(self._calib_metrics)
            # live HBM watermark (DX522's observation): the device
            # allocator's in-use/peak bytes, absent on backends that don't
            # report memory stats
            if self.hbm_sample:
                hbm = self.processor.device_memory_stats()
                if hbm is not None:
                    metrics["Hbm_BytesInUse"] = float(
                        hbm.get("bytes_in_use") or 0.0
                    )
                    metrics["Hbm_PeakBytes"] = float(
                        hbm.get("peak_bytes_in_use") or 0.0
                    )
            # per-stage latency percentiles from the live histograms — the
            # DATAX-<flow>:Latency-<Stage>-pNN series the dashboard's stat
            # tiles and stage timechart read (obs/histogram.py keeps these
            # exact over a bounded recent-sample window). Merged BEFORE the
            # conformance pass: the DX520 stage-time check judges the same
            # p50 series the dashboards render.
            for stage in MetricName.STAGES:
                stem = MetricName.stage_metric(stage)
                for q in (50, 95, 99):
                    v = HISTOGRAMS.percentile(self.health.flow, stage, q)
                    if v is not None:
                        metrics[f"{stem}-p{q}"] = v
            # model-vs-observed conformance: ratio gauges join this batch's
            # metrics; drift transitions become typed flight-recorder events
            # and store rows (obs/conformance.py)
            if self.conformance is not None:
                gauges, drift_events = self.conformance.observe(
                    metrics, batch_time_ms
                )
                metrics.update(gauges)
                for ev in drift_events:
                    props = ev.to_props()
                    self.telemetry.track_event("conformance/drift", props)
                    self.metric_logger.send_metric_events(
                        "Conformance_Drift", [props], batch_time_ms
                    )
                    logger.warning(
                        "conformance drift %s: %s", ev.code, ev.message
                    )
            # finished profiler captures stitch into THIS batch's trace as
            # span events (the capture path is then one `obs trace` away
            # from the batches it overlapped) and bump the capture counter
            if self.profiler is not None:
                for cap in self.profiler.drain_finished():
                    trace.record(
                        "profiler/capture", cap["startedTs"],
                        cap.get("durationMs") or 0.0, path=cap["path"],
                    )
                if self.profiler.captures_count:
                    metrics["Profiler_Captures_Count"] = float(
                        self.profiler.captures_count
                    )
            if pm is not None:
                # Protocol_Events_Count for this batch's recorded prefix
                # (the post-ack checkpoint trio drains on the next batch)
                metrics.update(pm.drain_metric_deltas())
            if self.batches_processed == 0:
                # what this job actually runs on, flight-recorded with its
                # first batch: a reader of the recorder (chip_smoke.py, an
                # operator) learns platform, placement and decode engine
                # from the process that holds the chip, not from its own
                self.telemetry.track_event(
                    "host/devices", self._device_report()
                )
            # the batch's whole metric set rides the end event, so the
            # flight recorder alone reconstructs per-batch counts
            self.telemetry.batch_end(batch_time_ms, metrics)
            self.metric_logger.send_batch_metrics(metrics, batch_time_ms)
            # alert evaluation AFTER the store flush so window aggregates
            # include this batch; the firing set rides the health payload
            # (readyz) and the Alerts_Firing series
            firing: List[dict] = []
            if self.alerts is not None:
                firing = self.alerts.evaluate()
                self.health.record_alerts(firing)
                self.metric_logger.send_metric(
                    "Alerts_Firing", float(len(firing)), batch_time_ms
                )
            # fleet telemetry frame accumulation (obs/publisher.py): the
            # acked batch's metric deltas + consumed offset ranges fold
            # into the open window; record_batch is fail-open and
            # thread-safe (this tail may run on the landing thread)
            if self.fleet_publisher is not None:
                self.fleet_publisher.record_batch(
                    metrics, consumed, batch_time_ms,
                    health=self.health.health(), alerts=firing,
                )
            logger.info(
                "batch %d: %s",
                self.batches_processed + 1,
                " ".join(f"{k}={v:.1f}" for k, v in sorted(metrics.items())),
            )
            # DX53x state events (load fallback / both-sides-bad) land in
            # the flight recorder like conformance drift — typed, greppable
            self._drain_state_events()
            # runtime DX805: buffer-sanitizer poison hits join the recorder
            # the same way (and the Sanitizer_PoisonHit metric event stream)
            san = self.processor.buffer_sanitizer
            if san is not None:
                for ev in san.drain_events():
                    try:
                        self.telemetry.track_event("sanitizer/poison", ev)
                        self.metric_logger.send_metric_events(
                            "Sanitizer_PoisonHit", [ev], batch_time_ms
                        )
                    except Exception:  # noqa: BLE001 — telemetry never kills a batch
                        logger.exception("sanitizer event emit failed")
                    logger.warning("buffer sanitizer %s", ev.get("message"))
            # runtime DX906: protocol-monitor ordering violations from
            # previously sealed batches join the recorder the same way
            if pm is not None:
                for ev in pm.drain_events():
                    try:
                        self.telemetry.track_event("protocol/violation", ev)
                        self.metric_logger.send_metric_events(
                            "Protocol_Violation", [ev], batch_time_ms
                        )
                    except Exception:  # noqa: BLE001 — telemetry never kills a batch
                        logger.exception("protocol event emit failed")
                    logger.warning("protocol monitor %s", ev.get("message"))
        # dx-proto: post-commit at-least-once replay cursor: the window
        # snapshot + offset commit run AFTER the ack on purpose — a
        # crash between ack and checkpoint replays from the previous
        # offsets into rings that already hold the events (duplicates,
        # never loss)
        if self.checkpointer and (
            t0 - self._last_checkpoint >= self.checkpoint_interval_s
        ):
            with trace.activate(), tracing.span("checkpoint"):
                if self.window_checkpointer:
                    # snapshot BEFORE offsets: a crash between the two leaves
                    # old offsets + new rings, so replayed batches land in
                    # rings that already contain them (at-least-once
                    # duplicates); the reverse order would resume PAST events
                    # the restored rings never saw — a hole in window history
                    # per-slot partial aggregates cross a slot at a
                    # time: only the slots written since the head this
                    # checkpointer last landed
                    snap = self.processor.snapshot_window_state(
                        since=self.window_checkpointer.landed_counter
                    )
                    # armed sanitizer: a checkpoint must be REAL copies
                    # — shared memory with the live rings (or sentinel
                    # residue) is the PR 13 bug, caught before the
                    # snapshot is ever persisted
                    san = self.processor.buffer_sanitizer
                    if san is not None:
                        san.check_snapshot(
                            snap, self.processor.window_buffers
                        )
                    self.window_checkpointer.save(snap)
                    if pm is not None:
                        pm.record(
                            "DURABLE_WRITE",
                            detail="window_checkpointer.save",
                        )
                    if self.processor.state_mirror is not None:
                        # ship the owned window partitions (A/B + pointer
                        # per partition) so a rescale successor can pull
                        # exactly its assigned range — fail-closed: a
                        # dead store fails the batch, which requeues
                        self.processor.push_window_partitions(snap)
                        if pm is not None:
                            pm.record(
                                "STATE_PUSH",
                                detail="push_window_partitions",
                            )
                self.checkpointer.checkpoint_batch(consumed)
                if pm is not None:
                    pm.record(
                        "OFFSET_COMMIT", detail="checkpoint_batch",
                    )
            self._last_checkpoint = t0
            self.health.record_checkpoint()
        if pm is not None:
            pm.seal_batch(batch_time_ms)
        self.batches_processed += 1
        self.health.record_batch(
            batch_time_ms, ok=True, latency_ms=metrics["Latency-Batch"]
        )
        self.health.record_watermark(batch_time_ms)
        trace.end()
        return metrics

    def _device_report(self) -> Dict[str, object]:
        """Platform, device kind and count as jax reports them in THIS
        process, plus where the processor's data lives and which
        engines (decoder, Pallas compile mode) served the batch."""
        from ..udf.api import PallasUdf

        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "deviceKind": devices[0].device_kind,
            "deviceCount": len(devices),
            "jaxVersion": jax.__version__,
            "batchCapacity": self.processor.batch_capacity,
            "decoderPath": self.processor.last_decoder_path,
            "pallasInterpret": {
                name: udf.interpret
                for name, udf in self.processor.udfs.items()
                if isinstance(udf, PallasUdf)
            },
            **self.processor.placement(),
        }

    def _traced_poll(self, trace):
        """Poll + encode under the batch's trace (the pipelined loop
        runs this on the decode-ahead worker thread, so the span needs
        explicit activation there)."""
        with trace.activate(), tracing.span("decode"):
            polled = self._poll_and_encode()
        # what the sources say about the poll, on the batch's end event
        # (a source with no notion of one reports None: no counter).
        # Source_Backlog_Rows: source lag inside the program, the rows
        # still held when this batch's poll returned.
        # Source_Buffer_Grow_Count: 0 says every byte of the batch was
        # copied once on its way from the socket to the decoder.
        for counter, attr in (
            ("Source_Backlog_Rows", "backlog_rows"),
            ("Source_Buffer_Grow_Count", "buffer_grows"),
        ):
            said = [
                getattr(s, attr) for s in self.sources.values()
                if getattr(s, attr) is not None
            ]
            if said:
                trace.counters[counter] = float(sum(said))
        # how long the batch's rows had waited in the sources that stamp
        # arrivals when the poll cut, over the rows; the cut's time goes
        # with the root span, for the age the tail takes at the landing
        waits = row_waits_ms(self.sources.values())
        if waits is not None:
            polled_ts, (p50, p95), oldest = waits
            trace.add(polledTs=polled_ts)
            trace.counters["Source_Wait_P50_Ms"] = p50
            trace.counters["Source_Wait_P95_Ms"] = p95
            trace.counters["Source_Wait_Max_Ms"] = oldest
        # what the wait before this poll took off the batch's decode:
        # the share of its rows decoded by then, and the passes' cost
        ahead = [
            self.processor.decode_ahead_stats[name]
            for name in self._ahead_sources
            if name in self.processor.decode_ahead_stats
        ]
        if ahead:
            early, rows, ms, passes = map(sum, zip(*ahead))
            trace.counters["Decode_Ahead_Pct"] = \
                100.0 * early / rows if rows else 0.0
            trace.counters["Decode_Ahead_Ms"] = float(ms)
            trace.counters["Decode_Ahead_Passes"] = float(passes)
        return polled

    def _dispatch_traced(self, trace, raw, batch_time_ms):
        """Dispatch under the batch's trace, marking the dispatch-done
        instant the later device-step span measures from."""
        trace.add(batchTime=batch_time_ms)
        if self.fleet_publisher is not None:
            # replica identity on every batch root span: what lets
            # `obs trace --stitch` group a shared flight recorder's
            # spans into the flow's cross-replica lineage segments
            trace.add(replica=self.fleet_publisher.replica)
        self.telemetry.batch_begin(batch_time_ms)
        with trace.activate(), tracing.span("dispatch"):
            handle = self.processor.dispatch_batch(raw, batch_time_ms)
        trace.mark("dispatch-done")
        return handle

    def _start_batch(self):
        """Poll + encode + dispatch one batch; a failure anywhere here
        (bad payload, re-trace error) requeues the polled batch so a
        later batch's ack can't release it unprocessed."""
        trace = self.tracer.begin("streaming/batch")
        late_ms, self._loop_late_ms = self._loop_late_ms, None
        if late_ms is not None:
            trace.counters["Loop_Late_Ms"] = late_ms
        try:
            raw, consumed, batch_time_ms, t0 = self._traced_poll(trace)
            handle = self._dispatch_traced(trace, raw, batch_time_ms)
        except Exception as e:
            self.health.record_batch(
                None, ok=False, error=f"{type(e).__name__}: {e}"
            )
            trace.end(status="error")
            for s in self.sources.values():
                s.requeue_unacked()
            raise
        return handle, consumed, batch_time_ms, t0, trace

    def _update_backpressure(self, busy_ms: float) -> None:
        """Adaptive backpressure on the loop's *busy* time (work per
        batch, pacing sleep excluded): overrunning the interval halves
        the next poll (down to 1/8 rate); fast batches recover gently.
        The static maxRate limiter stays the ceiling
        (EventHubStreamingFactory.scala:43)."""
        if busy_ms > self.interval_s * 1000.0:
            self._rate_scale = max(0.125, self._rate_scale * 0.5)
        elif busy_ms < self.interval_s * 500.0:
            self._rate_scale = min(1.0, self._rate_scale * 1.25)

    def run_batch(self) -> Dict[str, float]:
        """One micro-batch: poll -> encode -> device step -> sinks ->
        metrics -> checkpoint."""
        metrics = self._finish(*self._start_batch())
        # synchronous loop: the batch's own latency is the busy time
        self._update_backpressure(metrics["Latency-Batch"])
        if self.pilot is not None:
            self.pilot.tick(batch_time_ms=int(time.time() * 1000))
        return metrics

    def _pass_s(self, nbytes: Optional[int] = None) -> float:
        """How long a pass over ``nbytes`` may take (the most that one
        of the latest passes took, when None): at the slowest speed
        among them, and half as long again. A whole slice, until a
        pass has been timed."""
        if not self._ahead_passes:
            return _AHEAD_SLICE_S
        if nbytes is None:
            nbytes = max(b for b, _s in self._ahead_passes)
        return 1.5 * nbytes * max(s / b for b, s in self._ahead_passes)

    def _decode_arrived(self, deadline: float) -> None:
        """One pass a source over the lines that have arrived since the
        pass before: decoded into the next batch's matrix
        (``FlowProcessor.decode_ahead``), against the base a poll at
        ``deadline`` gets. No pass starts that the passes before it say
        cannot end by ``deadline``: the poll starts when it always did.
        """
        for name, src in list(self._ahead_sources.items()):
            at_byte, at_line = self.processor.decode_ahead_cursor(name)
            got = src.arrived_lines(at_byte, at_line)
            if got is None:
                continue
            data, lines = got
            if at_line + lines > self._poll_limit(name):
                continue  # a backlog: the poll cuts it, and decodes its cut
            left = deadline - time.time()
            if self._pass_s(len(data)) > left:
                continue
            if len(data) < self._ahead_pass_bytes and \
                    self._pass_s(self._ahead_pass_bytes) < \
                    left - _AHEAD_SLICE_S:
                continue  # a later wake takes them with what comes
            t0 = time.perf_counter()
            try:
                with tracing.annotation(
                    "decode-ahead", bytes=len(data), lines=lines
                ):
                    decoded = self.processor.decode_ahead(
                        data, lines, int(deadline) * 1000, source=name
                    )
            except Exception:  # noqa: BLE001
                # nothing is staged any more; the poll decodes the
                # lines, and what is wrong with them is its to raise
                logger.exception(
                    "decode-ahead pass failed: source %s is decoded at "
                    "its polls from here on", name
                )
                del self._ahead_sources[name]
                continue
            if decoded and len(data) >= self._ahead_pass_bytes:
                # (a smaller pass's time says what a call costs, not
                # how fast the decoder is)
                self._ahead_passes.append(
                    (len(data), time.perf_counter() - t0)
                )

    def _pace(self, deadline: float) -> None:
        """Wait for ``deadline`` (the interval's end). Where a source
        can show its lines before the poll, the wait wakes in slices
        and decodes what has arrived, so that the poll finds all but
        the last slice's lines in the batch's matrix already; nothing
        is delivered before the poll."""
        if deadline - time.time() <= 0:
            return
        # pacing, measured in a capture instead of inferred from the
        # hole between two batches; the passes lie inside it
        with tracing.annotation("pace"):
            while self._ahead_sources and not self._stop:
                # the last wake leaves a pass the time to end
                nap = deadline - time.time() - self._pass_s()
                if nap <= 0:
                    break
                time.sleep(min(_AHEAD_SLICE_S, nap))
                self._decode_arrived(deadline)
            left = deadline - time.time()
            if left > 0:
                time.sleep(left)

    def run(self, max_batches: Optional[int] = None) -> None:
        """Paced loop (streaming.intervalInSeconds cadence,
        StreamingHost.scala:66-67)."""
        deadline = None
        try:
            while not self._stop:
                start = time.time()
                if deadline is not None:
                    # the sleep's overshoot, or the overrun of a batch
                    # that was busy for longer than its interval
                    self._loop_late_ms = (start - deadline) * 1000.0
                self.run_batch()
                if max_batches is not None and self.batches_processed >= max_batches:
                    break
                deadline = start + self.interval_s
                self._pace(deadline)
        finally:
            # a matrix the wait was filling goes back to its pool
            self.processor.drop_decode_ahead()
            self._stop_profiler()

    def _stop_profiler(self) -> None:
        """Close any in-flight on-demand capture so its trace flushes
        before the loop (or the process) goes away."""
        if self.profiler is not None:
            self.profiler.stop()

    def run_pipelined(
        self,
        max_batches: Optional[int] = None,
        depth: Optional[int] = None,
    ) -> None:
        """Unpaced loop with up to ``depth`` batches in flight (conf
        ``datax.job.process.pipeline.depth``, default 2): a decode-ahead
        worker thread polls + decodes batch N+1 (the C++ JSON decoder
        releases the GIL, so this genuinely overlaps) while the main
        thread dispatches batch N to the device and — once the window
        is full — finishes the OLDEST in-flight batch (collect + sinks
        + commit + ack). Throughput mode: the wall-clock per batch
        approaches max(decode, device, transport) instead of their sum,
        and at depth >= 2 a batch's D2H transfer and sink I/O hide
        under the device steps of the batches behind it.

        Ordering/recovery invariants at every depth:
        - finish/commit is strictly FIFO (the window is a deque popped
          from the left, and background landings run on ONE worker in
          submission order), so state-table commits, acks and offset
          checkpoints happen in dispatch order;
        - each batch joins its source's un-acked FIFO at poll time and
          is acked (in order) only after its own sinks succeed; a
          failure anywhere — including on the landing thread, with
          background transfers still in flight — drains the landing
          queue and requeues EVERY un-acked batch in the window before
          rethrowing (at-least-once);
        - a UDF ``on_interval`` refresh mid-window is safe: every
          ``PendingBatch`` snapshots the pipeline/schemas of the step
          that produced it, so deep windows decode against their own
          compiled shapes.

        On one chip each finish blocks only on the counts vector; the
        streamed output tables land and sinks ack on the background
        landing thread, bounded to at most ``depth`` queued landings
        (backpressure). Under a mesh the tail runs inline."""
        if depth is None:
            # resume from the COMMANDED depth: a pilot retarget from an
            # earlier run persists across loop restarts (== the conf'd
            # depth until the pilot ever actuates)
            depth = self.live_depth()
        depth = max(1, depth)
        self._depth_target = None
        self._live_depth = depth
        background = self._landing_pool is not None
        # FIFO window of (PendingBatch, consumed, batch_time_ms, t0, trace)
        pending = deque()
        pool = ThreadPoolExecutor(1)
        fut = None
        fut_trace = None  # the trace of the batch `fut` is decoding
        # batches started over the host's lifetime: landings may lag
        # batches_processed, so the loop counts dispatches itself
        # (previous runs' landings are fully drained at this point)
        started = self.batches_processed
        self._landing_failed = None

        def drain(f):
            """Wait out an in-flight poll so its delivery lands in the
            un-acked FIFO BEFORE any requeue — abandoning it would
            strand a polled batch in _inflight, where a later ack would
            release (and for Kafka, commit) it unprocessed."""
            if f is None:
                return
            try:
                f.result(timeout=60)
            except Exception:  # noqa: BLE001 — failed poll requeued below
                pass

        try:
            while not self._stop:
                # a failed background landing surfaces here: stop
                # feeding the device and run the whole-window requeue
                self._check_landing_failure()
                if max_batches is not None and started >= max_batches:
                    break
                iter_t0 = time.time()
                if fut is None:
                    fut_trace = self.tracer.begin("streaming/batch")
                    fut = pool.submit(self._traced_poll, fut_trace)
                raw, consumed, batch_time_ms, t0 = fut.result()
                trace, fut, fut_trace = fut_trace, None, None
                handle = self._dispatch_traced(trace, raw, batch_time_ms)
                started += 1
                # decode-ahead: the NEXT batch's poll starts now,
                # overlapping this window's collects + sinks — but only
                # if a next iteration will actually run
                if not self._stop and (
                    max_batches is None or started < max_batches
                ):
                    fut_trace = self.tracer.begin("streaming/batch")
                    fut = pool.submit(self._traced_poll, fut_trace)
                pending.append((handle, consumed, batch_time_ms, t0, trace))
                # a pilot depth retarget lands here, at the window
                # boundary: shrinking drains the FIFO below, growing
                # just admits more batches — either way commit order
                # and the requeue window are the ordinary ones
                depth = self._current_depth(depth)
                while len(pending) > depth:
                    # window full: retire the oldest batch (strict
                    # FIFO). depth=1 is the legacy single-`pending`
                    # overlap: finish N-1 right after dispatching N.
                    # In background mode this blocks only on the counts
                    # vector; the tail lands out-of-band.
                    self._finish(
                        *pending.popleft(), inflight_depth=len(pending) + 1,
                        background=background,
                    )
                    self._wait_landing_backlog(depth)
                # backpressure on iteration time, not Latency-Batch: a
                # pipelined batch's latency spans ~depth iterations by
                # design
                self._update_backpressure((time.time() - iter_t0) * 1000.0)
                if self.pilot is not None:
                    self.pilot.tick(batch_time_ms=batch_time_ms)
            while pending and not self._stop:
                self._check_landing_failure()
                self._finish(
                    *pending.popleft(), inflight_depth=len(pending) + 1,
                    background=background,
                )
            # all tails must land before the loop returns (or reports
            # the failure): collect/sink/ack work is only done when the
            # landing queue is empty
            self._drain_landings()
        except Exception:
            # settle the in-flight poll FIRST, then the landing queue
            # (queued landings after a failure no-op and leave their
            # batches un-acked), then requeue everything un-acked
            # across the whole window (covers poll/dispatch failures;
            # _finish requeues its own failures before rethrowing, and
            # requeue_unacked is idempotent)
            drain(fut)
            fut = None
            if fut_trace is not None:
                fut_trace.end(status="aborted")
            for item in pending:
                item[4].end(status="aborted")  # idempotent
                item[0].abandon()  # return its pooled ingest matrices
            self._settle_landings()
            for s in self.sources.values():
                s.requeue_unacked()
            raise
        finally:
            drain(fut)
            if fut_trace is not None:
                fut_trace.end(status="aborted")  # idempotent
            pool.shutdown(wait=False, cancel_futures=True)
            self._stop_profiler()

    def stop(self, close_sources: bool = True) -> None:
        """``close_sources=False`` tears the host down but leaves its
        sources open — the chaos preemption drill's 'killed process':
        a successor host takes over the surviving source/checkpoint
        state the way a rescheduled job takes over its partitions."""
        self._stop = True
        self._stop_profiler()
        if self._landing_pool is not None:
            # let queued landings flush their sinks/acks before the
            # dispatcher and sources close underneath them
            self._settle_landings()
            self._landing_pool.shutdown(wait=True)
            self._landing_pool = None
        if self.fleet_publisher is not None:
            # ship the tail window with the final drain marker — the
            # fleet view's clean-shutdown signal (a replica that dies
            # before this goes DX542-stale instead)
            self.fleet_publisher.flush(final=True)
        if self.obs_server is not None:
            self.obs_server.stop()
            self.obs_server = None
        self.dispatcher.close()
        if close_sources:
            for s in self.sources.values():
                s.close()


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    args = argv if argv is not None else sys.argv[1:]
    named = {
        a.split("=", 1)[0]: a.split("=", 1)[1] for a in args if "=" in a
    }
    ConfigManager.reset()
    ConfigManager.get_configuration_from_arguments(args)
    d = ConfigManager.load_config()
    # first touch of the backend: a chip another process holds, or an
    # accelerator platform that cannot start, fails here, by name
    devices = jax.devices()
    logger.info(
        "devices: platform=%s device_kind=%s count=%d (jax %s)",
        devices[0].platform, devices[0].device_kind, len(devices),
        jax.__version__,
    )
    host = StreamingHost(d)
    max_batches = int(named["batches"]) if "batches" in named else None
    logger.info(
        "starting flow %s (interval=%ss, capacity=%s)",
        d.get_job_name(), host.interval_s, host.processor.batch_capacity,
    )
    host.run(max_batches)


if __name__ == "__main__":
    main()
