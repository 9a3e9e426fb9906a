"""Headline benchmark: SimulatedData IoT alerting flow, ingest-inclusive.

Measures the FULL per-batch path the streaming host runs in production:
newline-JSON bytes -> native C++ decode (native/decoder.cpp) -> single
packed host->device transfer -> jitted device step (projection ->
threshold rule -> 5s-window group-by) -> async device->host result
transport -> row materialization (sink handoff point).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}

Reported figures:
- value / vs_baseline: ingest-inclusive events/s/chip vs the north-star
  per-chip share (1M ev/s on a v5e-16 => 62,500 ev/s/chip). The
  throughput loop is pipelined like StreamingHost.run_pipelined with a
  depth-N in-flight window (BENCH_PIPELINE_DEPTH, default = conf
  process.pipeline.depth = 2; decode of batch N+1 overlaps the window's
  device steps + result transport) and runs `BENCH_RUNS` times; value
  is the MEDIAN, with min/max alongside, so one noisy run can't swing
  the headline. `depth_sweep_events_per_sec` re-runs the
  loop once per depth in {1, 2, 4}; `pipeline_depth`,
  `d2h_bytes_per_batch` and `transfer_efficiency` report the headline
  depth and what sized output transfer moved vs the padded capacity
  (`hbm_model.d2h_full_fetch_bytes` is the un-sized comparison point).
- p99_rule_eval_ms: per-batch end-to-end latency in a small-batch
  (8192-row) SEQUENTIAL loop — ingest decode to results materialized on
  host. (Earlier rounds measured this inside the pipelined loop, where
  a batch's collect structurally waits for the NEXT batch's dispatch,
  double-counting an iteration; the sequential loop is the honest
  per-batch number.)
- p99_rule_compute_ms: same loop, decode to device-step completion
  (rules evaluated, state advanced) — excludes only result transport.
- The stage breakdown (decode/dispatch/device-step/sync-sequential/
  collect are sequential-loop medians, summing to ~p99_rule_eval_ms):
    stage_decode_ms          bytes -> columnar arrays (C++ decoder)
    stage_dispatch_ms        pack + h2d enqueue + step dispatch (async)
    stage_device_step_ms     device compute, measured amortized (K steps
                             enqueued back-to-back, ONE completion sync)
    stage_sync_ms            the dispatch loop's per-batch blocking cost
                             in the PIPELINED loop: the counts-only sync
                             (collect_counts) of the window's oldest
                             batch — at depth >= 2 its counts vector
                             landed while newer batches decoded, so this
                             is the production stall, not the topology's
                             round trip
    stage_sync_sequential_ms the same counts-only sync with nothing
                             overlapped (sequential loop): still
                             contains the un-hidden device wait; the
                             honest un-pipelined handshake
    stage_collect_ms         landing of the background-streamed tables +
                             row materialization (prefetched copies)
    sync_counts_bytes        wire bytes the blocking sync moved
- regression: trajectory gate vs the latest committed BENCH_r*.json —
  fractional events/s and p99 deltas with a ±10% tolerance band;
  `regressed: true` flags a drop past the band. With no committed
  baseline the gate returns None.
- idle_sync_ms: measured cost of a completion sync against an IDLE
  device — the fixed host<->device handshake. Every host-observed
  latency contains >= one such sync by construction: learning that the
  device finished IS a round trip. p99_engine_ms = decode + dispatch +
  device-step is the engine latency to judge against the <50 ms north
  star; rule_eval ~= engine + sync.
"""

import json
import os
import sys
import time

import numpy as np

PER_CHIP_TARGET = 1_000_000 / 16.0  # north-star share per chip

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# the per-stage decomposition routes through the SAME histogram type the
# streaming host feeds live (obs/histogram.py): one observe()/percentile()
# code path, so BENCH_*.json and the /metrics surface cannot drift. The
# window (2048) covers every sample this harness records, so percentiles
# here are exact (identical to np.percentile over the raw lists).
BENCH_FLOW = "bench"


def build_processor(capacity):
    from __graft_entry__ import _build

    # the headline flow is BASELINE config 1 (single-source IoT alerting),
    # kept identical across rounds so numbers stay comparable; the
    # two-source join variant is the multichip dryrun's flow
    return _build(batch_capacity=capacity, multi=False)


def make_json_payload(proc, n_rows, alert_rate=0.01, seed=3):
    """Realistic alerting stream as newline-JSON bytes: ~1% of events
    trip the DoorLock rule; mixed device types, jittered temps."""
    rng = np.random.RandomState(seed)
    types = np.array(["Heating", "WindSpeed", "DoorLock"])
    is_door = rng.uniform(size=n_rows) < 2 * alert_rate
    dtype_col = np.where(is_door, 2, rng.randint(0, 2, n_rows))
    status = np.where(is_door & (rng.uniform(size=n_rows) < 0.5), 0, 1)
    device_id = rng.randint(1, 9, n_rows)
    temp = rng.uniform(0, 100, n_rows)
    base = 1_700_000_000_000
    # vectorized-ish line assembly (10x faster than json.dumps per row)
    lines = [
        '{"deviceDetails":{"deviceId":%d,"deviceType":"%s","homeId":150,'
        '"status":%d,"temperature":%.3f},"eventTimeStamp":%d}'
        % (device_id[i], types[dtype_col[i]], status[i], temp[i], base + i)
        for i in range(n_rows)
    ]
    return ("\n".join(lines) + "\n").encode()


def bench_decoder(proc, payload, n_rows, iters=8, shards=None):
    """Standalone C++ decoder throughput on the PRODUCTION path: bytes
    -> the packed transfer-ready pool matrix (dx_decode_packed — SWAR
    scan, sharded decode, zero per-call column allocations), at
    ``shards`` decoder shards (None = the engine default)."""
    from data_accelerator_tpu.native import (
        NativeDecoder,
        PackedBufferPool,
        native_available,
    )
    from data_accelerator_tpu.runtime.processor import packed_raw_layout

    if not native_available():
        return None, None
    spec = proc.specs[proc.primary]
    layout = packed_raw_layout(spec.raw_schema.types)
    names = [c for c, _k in layout]
    col_rows = [names.index(c.name) for c in spec.schema.columns]
    pool = PackedBufferPool(len(layout) + 1, n_rows)
    mat = pool.acquire()
    nd = NativeDecoder(proc.input_schema, proc.dictionary, threads=shards)
    nd.decode_packed(payload, mat, col_rows, len(layout), 0)  # warm
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        nd.decode_packed(payload, mat, col_rows, len(layout), 0)
        ts.append(time.perf_counter() - t0)
    t = float(np.median(ts))
    return n_rows / t, len(payload) / t / 1e6


def bench_decoder_shard_curve(proc, payload, n_rows, shards=(1, 2, 4, 8)):
    """The shard-scaling curve the tentpole publishes: decoder rows/s
    vs conf'd shard count (datax.job.process.ingest.decoderthreads).
    On a single-core bench host the curve is flat-to-falling — read it
    beside bench_context.cpu_count."""
    curve = {}
    for s in shards:
        rows_s, _mb_s = bench_decoder(proc, payload, n_rows, iters=4,
                                      shards=s)
        if rows_s is None:
            return None
        curve[str(s)] = round(rows_s, 1)
    return curve


def pipelined_ingest_loop(proc, payloads, iters, base_ms, hist,
                          depth=None, transfer_stats=None):
    """The production throughput shape (StreamingHost.run_pipelined
    with background transfer): a decode-ahead worker thread parses
    batch N+1's JSON (the C++ decoder releases the GIL) while the main
    thread dispatches batch N and holds up to ``depth`` batches in
    flight (conf process.pipeline.depth, default 2). Retiring the
    oldest batch blocks only on its packed COUNTS vector (the
    counts-only sync — a few hundred bytes, streaming since dispatch);
    the output tables resolve on a background landing thread (strict
    FIFO, one worker), exactly like StreamingHost._finish. Returns
    events/s measured to the last landing; per-batch t0->landed ms (t0
    BEFORE the decode, so ingest-inclusive) lands in ``hist`` under the
    streaming host's whole-batch stage name, the per-batch counts-sync
    stall under "sync-pipelined"; per-batch Transfer_* metrics land in
    ``transfer_stats`` when given."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    if depth is None:
        depth = proc.pipeline_depth
    depth = max(1, depth)

    def decode(i):
        t0 = time.perf_counter()
        raw = proc.encode_json_bytes(
            payloads[i % len(payloads)], base_ms + i * 1000,
            to_device=False,
        )
        return raw, t0

    pending = deque()  # FIFO window of (handle, t0)
    landings = deque()  # futures of background table landings

    def land(ph, pt0):
        _d, m = ph.collect_tables()
        hist.observe(
            BENCH_FLOW, "batch", (time.perf_counter() - pt0) * 1000.0
        )
        if transfer_stats is not None:
            if "Transfer_D2HBytes" in m:
                transfer_stats.setdefault("d2h_bytes", []).append(
                    m["Transfer_D2HBytes"]
                )
            if "Transfer_Efficiency" in m:
                transfer_stats.setdefault("efficiency", []).append(
                    m["Transfer_Efficiency"]
                )
            if "Sync_CountsBytes" in m:
                transfer_stats.setdefault("sync_counts_bytes", []).append(
                    m["Sync_CountsBytes"]
                )

    def retire_oldest():
        ph, pt0 = pending.popleft()
        s0 = time.perf_counter()
        ph.collect_counts()  # the ONLY blocking device read
        hist.observe(
            BENCH_FLOW, "sync-pipelined",
            (time.perf_counter() - s0) * 1000.0,
        )
        landings.append(land_pool.submit(land, ph, pt0))
        while len(landings) > depth:  # backpressure like the host
            landings.popleft().result()

    pool = ThreadPoolExecutor(1)
    land_pool = ThreadPoolExecutor(1, thread_name_prefix="landing")
    try:
        t_start = time.perf_counter()
        fut = pool.submit(decode, 0)
        for i in range(iters):
            raw, t0 = fut.result()
            fut = None
            if i + 1 < iters:
                fut = pool.submit(decode, i + 1)
            handle = proc.dispatch_batch(
                raw, batch_time_ms=base_ms + i * 1000
            )
            pending.append((handle, t0))
            if len(pending) > depth:
                retire_oldest()
        while pending:
            retire_oldest()
        while landings:
            landings.popleft().result()
        total_s = time.perf_counter() - t_start
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        land_pool.shutdown(wait=True)
    events = proc.batch_capacity * iters
    return events / total_s


def sequential_latency_loop(proc, payloads, iters, base_ms, hist):
    """True per-batch latency: decode -> dispatch -> counts-only sync ->
    table landing, one batch at a time. Observes each stage into
    ``hist`` under the SAME stage names the streaming host uses, plus
    the bench rollups (compute = decode..sync, eval = decode..collect,
    engine-host = decode+dispatch). The sync stage is ``collect_counts``
    — the device-resident result path's single blocking read (device
    completion + the packed counts vector, already streaming since
    dispatch); collect is ``collect_tables`` resolving the
    background-streamed output copies."""
    for i in range(iters):
        t0 = time.perf_counter()
        raw = proc.encode_json_bytes(
            payloads[i % len(payloads)], base_ms + i * 1000
        )
        t1 = time.perf_counter()
        h = proc.dispatch_batch(raw, batch_time_ms=base_ms + i * 1000)
        t2 = time.perf_counter()
        h.collect_counts()
        t3 = time.perf_counter()
        h.collect_tables()
        t4 = time.perf_counter()
        hist.observe(BENCH_FLOW, "decode", (t1 - t0) * 1e3)
        hist.observe(BENCH_FLOW, "dispatch", (t2 - t1) * 1e3)
        hist.observe(BENCH_FLOW, "sync", (t3 - t2) * 1e3)
        hist.observe(BENCH_FLOW, "collect", (t4 - t3) * 1e3)
        hist.observe(BENCH_FLOW, "compute", (t3 - t0) * 1e3)
        hist.observe(BENCH_FLOW, "eval", (t4 - t0) * 1e3)
        hist.observe(BENCH_FLOW, "engine-host", (t2 - t0) * 1e3)


def measure_sync_rtt(proc, payload, base_ms, iters=8):
    """Completion-sync cost against an idle device: dispatch a batch,
    wait until the device is certainly done, then time the sync. This
    is the pure host<->device round trip the topology imposes — code
    cannot remove it, only co-location can."""
    ts = []
    for i in range(iters):
        raw = proc.encode_json_bytes(payload, base_ms + i * 1000)
        h = proc.dispatch_batch(raw, batch_time_ms=base_ms + i * 1000)
        time.sleep(0.25)
        t0 = time.perf_counter()
        h.block_until_evaluated()
        ts.append((time.perf_counter() - t0) * 1000.0)
        h.collect()
    return float(np.median(ts))


def bench_context(dec_rows_s, decoder_path=None, decoder_shards=None):
    """Host-environment context so cross-round numbers are
    self-describing (contended hosts slow the decoder >2x; loadavg +
    decoder rate at run time tell the reader whether a swing is code
    or the host). ``decoder_path`` records which decode engine served
    the run (native-sharded / native-mt) — the regression gate refuses
    to compare rounds across paths, same posture as the
    backend_mismatch guard."""
    try:
        load1, load5, _ = os.getloadavg()
    except OSError:
        load1 = load5 = None
    return {
        "loadavg_1m": round(load1, 2) if load1 is not None else None,
        "loadavg_5m": round(load5, 2) if load5 is not None else None,
        "cpu_count": os.cpu_count(),
        "decoder_rows_per_sec": round(dec_rows_s, 1) if dec_rows_s else None,
        "decoder_path": decoder_path,
        "decoder_shards": decoder_shards,
    }


def hbm_model_check(proc):
    """Cross-validate the static cost model against the production
    lowering (analysis/deviceplan.py): closed-form predicted bytes vs
    the shapes jax.eval_shape derives from the compiled plan — pure
    abstract interpretation, no device execution. Recording both every
    round means the model can never silently drift from the plan this
    bench actually runs."""
    from data_accelerator_tpu.analysis import analyze_processor

    report = analyze_processor(proc, chips=16)
    lowered = sum(s.hbm_bytes for s in report.stages)
    predicted = sum(s.model_bytes for s in report.stages)
    err = abs(predicted - lowered) / max(lowered, 1)
    return {
        "predicted_hbm_bytes": predicted,
        "lowered_hbm_bytes": lowered,
        "hbm_model_error": round(err, 4),
        "ici_bytes_per_batch_16chip": report.totals()["iciBytesPerBatch"],
        # modeled FULL-capacity D2H cost of the outputs — compare with
        # the measured d2h_bytes_per_batch to see what sized transfer
        # saves on the wire
        "d2h_full_fetch_bytes": report.totals()["d2hBytesPerBatch"],
        "stages": len(report.stages),
    }


def ici_model_check(proc):
    """Cross-validate the DX7xx mesh-sharding model against the real
    Mesh lowering (analysis/meshcheck.py) for the bench flow at the
    8-chip MULTICHIP slice: the per-stage closed-form collective bytes
    must equal the partitioner's output exactly (when this process has
    >= 2 devices to lower against — on a one-chip machine the model
    is recorded unvalidated and tier-1 validates it on the virtual CPU
    mesh). The OBSERVED side — the executed mesh program's
    collective census vs this model, asserted within the DX51x
    tolerance — lives in the MULTICHIP capture
    (``__graft_entry__.dryrun_multichip``), which actually runs the
    sharded step."""
    from data_accelerator_tpu.analysis import analyze_processor_mesh
    from data_accelerator_tpu.obs.conformance import DEFAULT_ICI_RATIO_HIGH

    report = analyze_processor_mesh(proc, chips=8)
    t = report.totals()
    mismatched = [
        s.name for s in report.stages
        if s.lowered_bytes is not None
        and s.lowered_bytes != s.ici_result_bytes
    ]
    return {
        "chips": 8,
        "model_ici_wire_bytes_per_batch": t["iciWireBytesPerBatch"],
        "model_ici_result_bytes_per_batch": t["iciResultBytesPerBatch"],
        "reshard_count": t["reshardCount"],
        "per_chip_hbm_bytes": t["perChipHbmBytes"],
        "validated_against_lowering": report.validated,
        "model_equals_lowering": report.validated and not mismatched,
        "dx51x_tolerance": DEFAULT_ICI_RATIO_HIGH,
    }


def measure_device_step(proc, payloads, base_ms, sync_rtt_ms, k=16):
    """Per-batch device compute, amortized: enqueue K steps back-to-back
    and sync ONCE, so the completion handshake is paid once for K
    batches instead of polluting each sample with its jitter (which is
    what a per-sample sync-minus-handshake subtraction does)."""
    raws = [
        proc.encode_json_bytes(payloads[i % len(payloads)],
                               base_ms + i * 1000)
        for i in range(k)
    ]
    handles = []
    t0 = time.perf_counter()
    for i, raw in enumerate(raws):
        handles.append(
            proc.dispatch_batch(raw, batch_time_ms=base_ms + i * 1000)
        )
    handles[-1].block_until_evaluated()
    elapsed_ms = (time.perf_counter() - t0) * 1000.0
    for h in handles:
        h.collect()
    # elapsed covers K dispatches (host) overlapped with K device steps,
    # plus one completion sync; the division is an upper bound on the
    # per-batch device cost
    return max(0.0, (elapsed_ms - sync_rtt_ms) / k)


def roofline_check(proc, observed_stage_ms):
    """The time-model conformance block (PR 12): calibrate THIS
    machine's profile (obs/calibrate.py — the same probes a streaming
    host runs at init), price the flow's byte/FLOP closed forms into
    per-stage roofline milliseconds (analysis/costmodel.py
    latency_model), and put predicted vs observed side by side with the
    drift ratio gated at the DX520 band. The roofline is a lower bound,
    so ratios sit >= 1 by construction; `within_band` flipping false is
    what a live host would fire DX520/DX521 on."""
    from data_accelerator_tpu.analysis import analyze_processor
    from data_accelerator_tpu.obs.calibrate import get_profile
    from data_accelerator_tpu.obs.conformance import (
        DEFAULT_STAGE_TIME_FLOOR_MS,
        DEFAULT_STAGE_TIME_RATIO_HIGH,
    )

    profile = get_profile()
    report = analyze_processor(proc, chips=16)
    lm = report.latency_model(profile.to_dict(), source="calibrated")
    stages = {}
    for stage, pred_key in (
        ("decode", "decodeMs"), ("device-step", "deviceStepMs"),
        ("collect", "d2hMs"),
    ):
        predicted = (lm["totals"] or {}).get(pred_key)
        observed = observed_stage_ms.get(stage)
        if predicted is None or observed is None:
            continue
        ratio = observed / predicted if predicted else None
        stages[stage] = {
            "predicted_ms": round(predicted, 4),
            "observed_ms": round(observed, 3),
            "drift_ratio": round(ratio, 2) if ratio is not None else None,
            # sub-floor predictions are not judged at runtime (host-side
            # fixed costs dominate; obs/conformance.py DX520 floor)
            "judged": predicted >= DEFAULT_STAGE_TIME_FLOOR_MS,
            "within_band": (
                predicted < DEFAULT_STAGE_TIME_FLOOR_MS
                or ratio is None
                or ratio <= DEFAULT_STAGE_TIME_RATIO_HIGH
            ),
        }
    return {
        "profile": profile.to_dict(),
        "dx520_band": DEFAULT_STAGE_TIME_RATIO_HIGH,
        "predicted_batch_ms": lm["totals"]["batchMs"],
        "stages": stages,
    }


def bench_cold_start(capacity=None):
    """Zero-cold-start acceptance block: time-to-first-batch of the
    headline flow COLD (fresh processor, trace+compile paid at first
    dispatch) vs WARM (AOT compile manifest + persistent compilation
    cache: init pre-compiles every manifest entry, the first dispatch
    compiles nothing — runtime/processor.py ``process.compile.*``).
    Measured twice warm; the second start is the preemption-recovery /
    scale-out-replica number. The persistent cache lives at the one
    directory ``compile/aotcache.py`` resolves, which outlives this
    run, so only a first run against an empty directory is cold. Hit/
    miss counts come from the ``Compile_Cache_{Hit,Miss}_Count``
    metrics the first collect drains."""
    from __graft_entry__ import _flow_conf
    from data_accelerator_tpu.analysis import analyze_processor_compile
    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    capacity = capacity or int(os.environ.get("BENCH_COLDSTART_CAPACITY",
                                              "8192"))
    outputs = ["OpenDoors", "HeatAvg"]
    base_ms = 1_700_000_000_000
    base_conf = dict(_flow_conf(multi=False).dict)
    # this harness feeds encode_json_bytes (the native packed ingest
    # path a streaming host uses for non-local sources); declare the
    # input non-local so the AOT warm traces the SAME raw form the
    # measured dispatches use (source_raw_form)
    base_conf["datax.job.input.default.inputtype"] = "socket"
    payload = None

    def build(extra=None):
        t0 = time.perf_counter()
        proc = FlowProcessor(
            SettingDictionary({**base_conf, **(extra or {})}),
            batch_capacity=capacity, output_datasets=outputs,
        )
        return proc, (time.perf_counter() - t0) * 1000.0

    def first_batch(proc):
        nonlocal payload
        if payload is None:
            payload = make_json_payload(proc, min(capacity, 4096), seed=7)
        raw = proc.encode_json_bytes(payload, base_ms)
        t0 = time.perf_counter()
        _d, m = proc.process_batch(raw, batch_time_ms=base_ms)
        return (time.perf_counter() - t0) * 1000.0, m

    cold, cold_init = build()
    cold_first, _m = first_batch(cold)
    # the manifest for the exact flow the cold processor runs (the
    # runtime-parity path; digests are for drift tests, not the warm)
    manifest = analyze_processor_compile(cold, digests=False).manifest
    warm_extra = {
        "datax.job.process.compile.manifest": json.dumps(manifest),
    }
    w1, warm_init = build(warm_extra)
    warm_first, m1 = first_batch(w1)
    w2, warm_cached_init = build(warm_extra)
    warm_cached_first, m2 = first_batch(w2)
    return {
        "batch_capacity": capacity,
        "cold_init_ms": round(cold_init, 1),
        "cold_first_batch_ms": round(cold_first, 1),
        "warm_init_ms": round(warm_init, 1),
        "warm_first_batch_ms": round(warm_first, 1),
        "warm_cached_init_ms": round(warm_cached_init, 1),
        "warm_cached_first_batch_ms": round(warm_cached_first, 1),
        "manifest_entries": len(manifest.get("entries") or []),
        "cache_miss_count": m1.get("Compile_Cache_Miss_Count"),
        "cache_hit_count": m2.get("Compile_Cache_Hit_Count"),
        # the acceptance bit: a warm start performs no first-dispatch
        # compile, so its time-to-first-batch sits far below cold's
        "warm_below_cold": warm_first < cold_first,
    }


def bench_state_handoff():
    """Elastic stateful rescale acceptance block: the stop→successor-
    first-batch time of a partition handoff. A predecessor runs a
    stateful TIMEWINDOW + accumulator flow with its partitions
    mirrored through a live object store; the successor (fresh local
    dirs — the mirror is its only route to state) pulls its assigned
    partitions, merges the window rings, reloads the accumulators and
    processes its first batch. ``stop_to_first_batch_ms`` is the
    handoff number the tentpole promises sub-second warm; the
    breakdown separates processor init (compile — the AOT/persistent-
    cache domain, see ``cold_start``) from the state pull+restore that
    is THIS feature's cost."""
    import shutil
    import tempfile

    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.host import StreamingHost
    from data_accelerator_tpu.runtime.sources import LocalSource
    from data_accelerator_tpu.serve.objectstore import ObjectStoreServer

    wd = tempfile.mkdtemp(prefix="dxtpu-bench-handoff-")
    store = ObjectStoreServer(port=0).start()  # in-memory
    schema = json.dumps({"type": "struct", "fields": [
        {"name": "k", "type": "long", "nullable": False, "metadata": {}},
        {"name": "v", "type": "double", "nullable": False, "metadata": {}},
    ]})
    tpath = os.path.join(wd, "handoff.transform")
    with open(tpath, "w", encoding="utf-8") as f:
        f.write(
            "--DataXQuery--\n"
            "merged = SELECT k, v FROM DataXProcessedInput "
            "UNION ALL SELECT k, v FROM seen\n"
            "--DataXQuery--\n"
            "seen = SELECT k, MAX(v) AS v FROM merged GROUP BY k\n"
            "--DataXQuery--\n"
            "Win = SELECT k, COUNT(*) AS c "
            "FROM DataXProcessedInput_10seconds GROUP BY k\n"
        )

    def conf(hostdir, replica_index=1, replica_count=1):
        return SettingDictionary({
            "datax.job.name": "BenchHandoff",
            "datax.job.input.default.inputtype": "local",
            "datax.job.input.default.blobschemafile": schema,
            "datax.job.input.default.eventhub.maxrate": "1024",
            "datax.job.input.default.eventhub.checkpointdir": os.path.join(
                hostdir, "ckpt"
            ),
            "datax.job.input.default.eventhub.checkpointinterval":
                "0 second",
            "datax.job.input.default.streaming.intervalinseconds": "1",
            "datax.job.process.timestampcolumn": "ts",
            "datax.job.process.watermark": "0 second",
            "datax.job.process.transform": tpath,
            "datax.job.process.batchcapacity": "1024",
            "datax.job.process.timewindow.DataXProcessedInput_10seconds"
            ".windowduration": "10 seconds",
            "datax.job.process.statetable.seen.schema": "k long, v double",
            "datax.job.process.statetable.seen.location": os.path.join(
                hostdir, "state", "seen"
            ),
            "datax.job.process.state.partitions": "16",
            "datax.job.process.state.partitionkey": "k",
            "datax.job.process.state.replicaindex": str(replica_index),
            "datax.job.process.state.replicacount": str(replica_count),
            "datax.job.process.state.snapshoturl":
                f"objstore://127.0.0.1:{store.port}/bench/handoff",
            "datax.job.process.pilot.enabled": "false",
            "datax.job.process.observability.calibration": "false",
            "datax.job.output.Win.console.maxrows": "0",
        })

    class _NullSink:
        kind = "null"

        def write(self, dataset, rows, batch_time_ms):
            return len(rows)

    def quiet(host):
        for op in host.dispatcher.operators.values():
            op.sinks = [_NullSink()]
        return host

    try:
        pred = quiet(StreamingHost(conf(os.path.join(wd, "pred"))))
        for _ in range(3):
            pred.run_batch()
        t_stop = time.perf_counter()
        pred.stop()
        stop_ms = (time.perf_counter() - t_stop) * 1000.0

        t0 = time.perf_counter()
        succ = quiet(StreamingHost(conf(os.path.join(wd, "succ"))))
        init_ms = (time.perf_counter() - t0) * 1000.0
        # read before the first collect drains state_stats into metrics
        state_pull_ms = succ.processor.state_stats.get("Handoff_Ms")
        t1 = time.perf_counter()
        succ.run_batch()
        first_batch_ms = (time.perf_counter() - t1) * 1000.0
        handoff_ms = (time.perf_counter() - t_stop) * 1000.0
        restored = succ.window_restored_from
        succ.stop()
        return {
            "stop_ms": round(stop_ms, 1),
            "successor_init_ms": round(init_ms, 1),
            "state_pull_restore_ms": (
                round(state_pull_ms, 1) if state_pull_ms is not None
                else None
            ),
            "successor_first_batch_ms": round(first_batch_ms, 1),
            "stop_to_first_batch_ms": round(handoff_ms, 1),
            "window_restored_from": restored,
            # the acceptance bit: a warm handoff (state follows the
            # replicas through the store) stays sub-second
            "sub_second": handoff_ms < 1000.0,
        }
    finally:
        store.stop()
        shutil.rmtree(wd, ignore_errors=True)


def bench_sanitizer(capacity=8192, warmup=2, iters=8):
    """Buffer-sanitizer overhead block: the debug mode's cost (one
    memset per released pool slot + the sentinel/alias scans at
    collect) measured as events/s with the sanitizer armed vs off.
    Published, not gated: it is a debug mode, and the number makes
    arming it during an incident an informed choice. ``poison_hits``
    doubles as a live engine check — any nonzero means a pooled view
    escaped on the bench flow itself."""
    from data_accelerator_tpu.runtime.sanitizer import BufferSanitizer

    base_ms = 1_800_000_000_000

    def run(armed):
        proc = build_processor(capacity)
        if armed:
            # attached before the first encode, so every ingest pool is
            # created with the poison-on-release hook wired
            proc.buffer_sanitizer = BufferSanitizer()
        payload = make_json_payload(proc, capacity, seed=29)
        for i in range(warmup):
            raw = proc.encode_json_bytes(payload, base_ms + i * 1000)
            proc.process_batch(raw, batch_time_ms=base_ms + i * 1000)
        t0 = time.perf_counter()
        for i in range(iters):
            t_ms = base_ms + (warmup + i) * 1000
            raw = proc.encode_json_bytes(payload, t_ms)
            proc.process_batch(raw, batch_time_ms=t_ms)
        dt = time.perf_counter() - t0
        return capacity * iters / dt, proc

    # armed phase first: process-wide warmup (XLA autotune, allocator
    # pools) then favors the OFF run, so the published overhead is the
    # conservative (overstated) side of the truth
    on_eps, proc = run(True)
    off_eps, _ = run(False)
    san = proc.buffer_sanitizer
    return {
        "events_per_sec_off": round(off_eps, 1),
        "events_per_sec_on": round(on_eps, 1),
        "overhead_pct": round((1.0 - on_eps / off_eps) * 100.0, 2),
        "slots_poisoned": san.poison_count,
        "poison_hits": san.poison_hits,
    }


def bench_protocheck(iters=200):
    """Protocol-gate cost block: the static tier's analysis latency
    over the engine packages (cold parse+walk vs the mtime-keyed
    cache hit the CLI/REST/CI path normally takes) and the runtime
    monitor's per-batch cost (the batch tail's record calls + the
    seal-time linearization check) armed vs off. The cold number is
    gated in ``regression``: the protocol gate runs in every CI
    validate call, so its cost is a committed number.
    ``violations`` doubles as a live engine check — any nonzero means
    the bench's well-ordered tail itself broke the spec."""
    from data_accelerator_tpu.analysis.protocheck import (
        _ENGINE_CACHE,
        analyze_flow_protocol,
    )
    from data_accelerator_tpu.runtime.protocolmonitor import ProtocolMonitor

    _ENGINE_CACHE.clear()
    t0 = time.perf_counter()
    report = analyze_flow_protocol({"name": "Bench"})
    cold_ms = (time.perf_counter() - t0) * 1000.0
    t0 = time.perf_counter()
    analyze_flow_protocol({"name": "Bench"})
    cached_ms = (time.perf_counter() - t0) * 1000.0

    # the monitor's whole per-batch footprint: the tail's event
    # records + one seal. Armed phase first, like the sanitizer block:
    # process warmup then favors the off run, so the published
    # overhead is the conservative (overstated) side of the truth.
    def run(pm):
        t0 = time.perf_counter()
        for i in range(iters):
            if pm is not None:
                pm.record("SINK_EMIT", detail="dispatcher.dispatch")
                pm.record("POINTER_FLIP", detail="processor.commit")
                pm.record("FIFO_ACK", source="default")
                pm.record("DURABLE_WRITE", detail="window_checkpointer.save")
                pm.record("STATE_PUSH", detail="push_window_partitions")
                pm.record("OFFSET_COMMIT", detail="checkpoint_batch")
                pm.seal_batch(float(i))
        return (time.perf_counter() - t0) / iters * 1e6

    mon = ProtocolMonitor()
    on_us = run(mon)
    off_us = run(None)
    return {
        "cold_ms": round(cold_ms, 2),
        "cached_ms": round(cached_ms, 3),
        "analyzed_files": len(report.modules),
        "effect_events": report.effect_events,
        "monitor_off_us_per_batch": round(off_us, 3),
        "monitor_on_us_per_batch": round(on_us, 3),
        "violations": mon.violations,
    }


def bench_confcheck(iters=50):
    """Conf-gate cost block: the static DX10xx tier's analysis latency
    over the engine+serve packages (cold AST scan vs the mtime-keyed
    cache hit the CLI/REST/CI path normally takes) and the runtime
    ConfAudit's boot cost over a fully populated conf (every registry
    default — the worst realistic key count a host boots with). The
    cold number is gated in ``regression``: the conf gate rides every
    CI validate call, so its cost is a committed number. ``findings``
    doubles as a live engine check — any nonzero means the tree
    itself broke the conf lattice."""
    from data_accelerator_tpu.analysis.confcheck import (
        _ENGINE_CACHE,
        analyze_flow_conf,
    )
    from data_accelerator_tpu.analysis.confspec import (
        CONF_REGISTRY,
        PROCESS_PREFIX,
    )
    from data_accelerator_tpu.runtime.confaudit import audit_conf

    _ENGINE_CACHE.clear()
    t0 = time.perf_counter()
    report = analyze_flow_conf({"name": "Bench"})
    cold_ms = (time.perf_counter() - t0) * 1000.0
    t0 = time.perf_counter()
    analyze_flow_conf({"name": "Bench"})
    cached_ms = (time.perf_counter() - t0) * 1000.0

    conf = {
        PROCESS_PREFIX + e.key: e.default
        for e in CONF_REGISTRY
        if e.default is not None and "*" not in e.key
    }
    t0 = time.perf_counter()
    for _ in range(iters):
        audit = audit_conf(conf)
    audit_us = (time.perf_counter() - t0) / iters * 1e6
    return {
        "cold_ms": round(cold_ms, 2),
        "cached_ms": round(cached_ms, 3),
        "analyzed_files": report.analyzed_files,
        "read_sites": len(report.read_sites),
        "registry_keys": len(CONF_REGISTRY),
        "audit_keys": audit.audited,
        "audit_init_us": round(audit_us, 1),
        "findings": len(report.diagnostics) + len(audit.findings),
    }


def bench_pilot_overhead(iters=2000):
    """Autopilot hot-path overhead block: the pilot rides the dispatch
    loop (``tick`` per iteration, ``admit_events`` + ``observe_poll``
    per poll, one full ``evaluate`` per window), so its cost belongs in
    the bench artifact next to the stage times it must stay invisible
    beside. Measured per call in µs over a live-shaped controller
    (actuators wired, no tracer — the recorder is its own line item)."""
    import statistics

    from data_accelerator_tpu.pilot import (
        BackpressureActuator,
        DepthActuator,
        PilotConfig,
        PilotController,
        TokenBucket,
        decide,
    )

    bucket = TokenBucket(base_rate=100_000.0)
    depth = [2]
    cfg = PilotConfig(window_s=0.0, cooldown_s=0.0)
    pilot = PilotController(
        cfg,
        bucket=bucket,
        actuators=[
            DepthActuator(lambda: depth[0],
                          lambda d: depth.__setitem__(0, d)),
            BackpressureActuator(bucket),
        ],
    )
    pilot._depth_probe = lambda: depth[0]

    def timed(fn):
        samples = []
        for _ in range(8):
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            samples.append((time.perf_counter() - t0) / iters * 1e6)
        return round(statistics.median(samples), 3)

    snap = pilot.read_signals()
    return {
        "decide_us": timed(lambda: decide(snap, cfg)),
        "evaluate_us": timed(pilot.evaluate),
        "admit_events_us": timed(lambda: pilot.admit_events(4096)),
        "observe_poll_us": timed(lambda: pilot.observe_poll(4096, 4096)),
    }


def bench_livequery(seconds=None, tenants=8, sessions_per_tenant=4,
                    arrival_rate=None):
    """LiveQuery serving-plane block: kernel QPS + p99 interactive
    latency under a simulated multi-tenant OPEN-LOOP load — executes
    arrive on a fixed schedule regardless of completion (the
    many-users-refreshing-dashboards shape), so queueing delay shows in
    the latency numbers instead of being absorbed by a closed loop.
    All sessions share one flow + query, the serving plane's dominant
    case: the coalescer merges them per compile signature, so the block
    also records the fan-in and proves the compile surface stayed at
    ONE entry while tenant count and QPS scaled. A second, throttled
    service then drives a tenant past its QPS quota and asserts the
    rejected calls consumed ZERO device dispatches (the
    no-dispatch-on-reject contract the REST 429 path relies on)."""
    import threading as _threading

    from data_accelerator_tpu.lq.service import LQ_EXEC_STAGE, LQ_FLOW, LiveQueryService
    from data_accelerator_tpu.lq.session import AdmissionRejected

    seconds = float(
        seconds if seconds is not None
        else os.environ.get("BENCH_LQ_SECONDS", "1.5")
    )
    arrival_rate = float(
        arrival_rate if arrival_rate is not None
        else os.environ.get("BENCH_LQ_RATE", "500")
    )
    schema = json.dumps({"type": "struct", "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {}},
        {"name": "temperature", "type": "double", "nullable": False,
         "metadata": {}},
        {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
         "metadata": {}},
    ]})
    base = 1_700_000_000_000
    rows = [
        {"deviceId": i % 7, "temperature": 20.0 + (i % 13),
         "eventTimeStamp": base + i}
        for i in range(60)  # pads into the 64-row pow2 bucket
    ]
    query = (
        "Agg = SELECT deviceId, COUNT(*) AS Cnt, MAX(temperature) AS "
        "MaxTemp FROM DataXProcessedInput GROUP BY deviceId"
    )
    svc = LiveQueryService(conf={
        "datax.job.process.lq.ticker": "true",
        "datax.job.process.lq.maxbatchwaitms": "4",
        "datax.job.process.lq.tenant.maxsessions": str(sessions_per_tenant),
        "datax.job.process.lq.tenant.maxqps": "1000000",
        "datax.job.process.lq.maxsessions": "4096",
    })
    try:
        sids = [
            svc.create_session(f"tenant-{t}", "BenchLQ", schema,
                               sample_rows=rows)["id"]
            for t in range(tenants) for _ in range(sessions_per_tenant)
        ]
        svc.execute(sids[0], query)  # compile once, warm

        done = []
        done_lock = _threading.Lock()

        def one(sid):
            try:
                svc.execute(sid, query)
                with done_lock:
                    done.append(time.monotonic())
            except Exception:
                pass

        from concurrent.futures import ThreadPoolExecutor

        interval = 1.0 / arrival_rate
        t0 = time.monotonic()
        submitted = 0
        with ThreadPoolExecutor(max_workers=64) as pool:
            while time.monotonic() - t0 < seconds:
                pool.submit(one, sids[submitted % len(sids)])
                submitted += 1
                # open loop: next arrival is schedule-driven
                next_at = t0 + submitted * interval
                delay = next_at - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
        elapsed = max(time.monotonic() - t0, 1e-6)
        completed = len(done)
        p99 = svc.histograms.percentile(LQ_FLOW, LQ_EXEC_STAGE, 99)
        p50 = svc.histograms.percentile(LQ_FLOW, LQ_EXEC_STAGE, 50)
        co = svc.coalescer.stats()
        cache = svc.cache.stats()
    finally:
        svc.stop()

    # quota proof on a throttled twin: rejected executes must consume
    # zero dispatches (counted here, 429-surfaced on the REST path)
    tight = LiveQueryService(conf={
        "datax.job.process.lq.tenant.maxqps": "1",
    })
    try:
        sid = tight.create_session("freeloader", "BenchLQ", schema,
                                   sample_rows=rows)["id"]
        tight.execute(sid, query)  # consumes the 1-token burst
        before = tight.coalescer.stats()["dispatches"]
        rejected = 0
        for _ in range(5):
            try:
                tight.execute(sid, query)
            except AdmissionRejected:
                rejected += 1
        rejected_dispatch_delta = (
            tight.coalescer.stats()["dispatches"] - before
        )
    finally:
        tight.stop()

    return {
        "kernel_qps": round(completed / elapsed, 1),
        "p99_exec_ms": round(p99, 2) if p99 is not None else None,
        "p50_exec_ms": round(p50, 2) if p50 is not None else None,
        "arrival_rate_qps": arrival_rate,
        "submitted": submitted,
        "completed": completed,
        "sessions": len(sids),
        "tenants": tenants,
        "coalesce_fanin_avg": co["avgFanin"],
        "dispatches": co["dispatches"],
        "calls": co["calls"],
        # the scaling proof: tenant count scaled, compile surface did not
        "compiled_entries": cache["entries"],
        "step_cache_entries": cache["stepCacheEntries"],
        "quota_rejected": rejected,
        "rejected_dispatches": rejected_dispatch_delta,
    }


def bench_fleet_rollup(replicas=8, batches=12):
    """Fleet telemetry plane acceptance block: the cost of the push-
    based cross-replica rollup. A synthetic 8-replica fleet publishes
    windowed frames (counters + per-stage histogram states + delivery
    counts) through a live object store; the control-plane ``FleetView``
    pulls and merges them. Published numbers are the per-frame wire
    size, the publish (store put) latency, and the full-fleet merge
    latency — the telemetry overhead a replica and the control plane
    each pay. ``conserved`` is the acceptance bit: the DX54x audit over
    the synthetic fleet must balance exactly."""
    from data_accelerator_tpu.obs.fleetview import FleetView
    from data_accelerator_tpu.obs.histogram import HistogramRegistry
    from data_accelerator_tpu.obs.publisher import TelemetryFramePublisher
    from data_accelerator_tpu.serve.objectstore import ObjectStoreServer

    store = ObjectStoreServer(port=0).start()  # in-memory
    url = f"objstore://127.0.0.1:{store.port}/bench/fleet"
    try:
        frame_bytes, publish_ms = [], []
        for index in range(1, replicas + 1):
            pub = TelemetryFramePublisher(
                url,
                flow="BenchFleet",
                replica=f"r{index}",
                replica_index=index,
                replica_count=replicas,
                window_s=0.0,  # publish every batch: worst-case cadence
                histograms=HistogramRegistry(),
            )
            for b in range(batches):
                for stage in ("decode", "process", "collect"):
                    # deterministic spread; merge exactness is the unit
                    # suite's job, this block only prices the plumbing
                    pub.histograms.observe(
                        "BenchFleet", stage, 1.0 + (b * 7 + index) % 23
                    )
                pub.record_batch(
                    {
                        "Input_default_Events_Count": 256.0,
                        "Output_Out_Events_Count": 256.0,
                        "Batch_ProcessedMs": 9.5,
                        "DataXProcessedInput_Count": 256.0,
                    },
                    consumed={("default", index): (b * 256, (b + 1) * 256)},
                    batch_time_ms=1_000 + b,
                )
                frame_bytes.append(pub.last_frame_bytes)
                publish_ms.append(pub.last_publish_ms)
            assert pub.flush(final=True)
            assert pub.publish_errors == 0

        view = FleetView.from_url(url)
        t0 = time.perf_counter()
        n_frames = view.refresh()
        merge_ms = (time.perf_counter() - t0) * 1000.0
        audit = view.audit("BenchFleet")
        fm = view.fleet_metrics("BenchFleet")
        expected = 256.0 * replicas * batches
        return {
            "replicas": replicas,
            "frames": n_frames,
            "frame_bytes": round(sum(frame_bytes) / len(frame_bytes)),
            "publish_ms": round(sum(publish_ms) / len(publish_ms), 3),
            "merge_ms": round(merge_ms, 1),
            "decode_errors": view.decode_errors,
            # the acceptance bit: the rollup balances — summed ingest
            # equals both the audit's emit side and the merged counter
            "conserved": bool(
                audit["conserved"]
                and audit["counts"] == {"DX540": 0, "DX541": 0, "DX542": 0}
                and fm["counters"]["Input_default_Events_Count"] == expected
            ),
        }
    finally:
        store.stop()


def regression_gate(current: dict, tolerance: float = 0.10):
    """Trajectory gate: compare this run against the latest committed
    BENCH_r*.json and emit a ``regression`` block — events/s and p99
    deltas with a tolerance band — so a perf regression is visible in
    the bench artifact itself instead of only by eyeballing history.
    Deltas are fractional (observed/previous - 1); ``regressed`` flips
    when throughput drops OR p99 rule-eval latency grows past the band.
    The band defaults to ±10%: r3->r4 showed ~13% swing from
    environment weather alone, so the gate flags, it does not fail —
    read it with bench_context (loadavg) beside it."""
    import glob
    import re as _re

    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for path in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = _re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    if not rounds:
        return None
    _, latest = max(rounds)
    try:
        with open(latest, encoding="utf-8") as f:
            doc = json.load(f)
        prev = doc.get("parsed") or doc
    except (OSError, ValueError):
        return None

    # a trajectory only means something on one backend: a CPU one-box
    # capture judged against an accelerator round (or vice versa) is
    # environment, not code — record the mismatch instead of a verdict
    prev_backend = prev.get("backend")
    cur_backend = current.get("backend")
    if prev_backend and cur_backend and prev_backend != cur_backend:
        return {
            "baseline": os.path.basename(latest),
            "baseline_backend": prev_backend,
            "backend": cur_backend,
            "backend_mismatch": True,
            "regressed": False,
            "note": "baseline captured on a different backend; "
                    "deltas not comparable",
        }

    # decoder-path gate (same posture as backend_mismatch): a round
    # decoded by the python fallback (silent g++ failure) or the
    # legacy path is a different machine as far as ingest-inclusive
    # events/s goes — record the mismatch instead of a verdict
    prev_path = (prev.get("bench_context") or {}).get("decoder_path")
    cur_path = (current.get("bench_context") or {}).get("decoder_path")
    if prev_path and cur_path and prev_path != cur_path:
        return {
            "baseline": os.path.basename(latest),
            "baseline_decoder_path": prev_path,
            "decoder_path": cur_path,
            "decoder_path_mismatch": True,
            "regressed": False,
            "note": "baseline captured on a different decoder path; "
                    "deltas not comparable",
        }

    def delta(key):
        a, b = prev.get(key), current.get(key)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)) \
                or a == 0:
            return None
        return round(b / a - 1.0, 4)

    d_eps = delta("value")
    d_p99_eval = delta("p99_rule_eval_ms")
    d_p99_batch = delta("p99_batch_ms")

    def nested_delta(block, key):
        a = (prev.get(block) or {}).get(key)
        b = (current.get(block) or {}).get(key)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)) \
                or a == 0:
            return None
        return round(b / a - 1.0, 4)

    # LiveQuery serving-plane gates (backend-aware like every other
    # delta — the backend_mismatch short-circuit above already ran):
    # kernel QPS dropping or p99 interactive latency growing past the
    # band fails like an events/s drop
    d_lq_qps = nested_delta("livequery", "kernel_qps")
    d_lq_p99 = nested_delta("livequery", "p99_exec_ms")
    # fleet telemetry gates: per-frame publish cost on the replica and
    # full-fleet merge cost on the control plane — a >band worsening of
    # either means the observability plane itself got expensive
    d_fleet_pub = nested_delta("fleet_rollup", "publish_ms")
    d_fleet_merge = nested_delta("fleet_rollup", "merge_ms")
    # protocol-gate cost: the static tier's cold analysis latency
    # rides every CI validate call — a >band worsening fails. (The
    # cached path is sub-ms and too jittery to gate; it is published
    # in the block instead.)
    d_proto_cold = nested_delta("protocheck", "cold_ms")
    # conf-gate cost: same contract as the protocol gate — the cold
    # lattice scan rides every CI validate call, so a >band worsening
    # fails; the cached/audit paths are sub-ms and published only
    d_conf_cold = nested_delta("confcheck", "cold_ms")
    # cold-start gate: warm time-to-first-batch is the restart/
    # preemption-recovery promise — a >band worsening (or warm no
    # longer beating cold at all) fails like an events/s drop
    cs_cur = current.get("cold_start") or {}
    cs_prev = prev.get("cold_start") or {}
    a, b = (
        cs_prev.get("warm_first_batch_ms"), cs_cur.get("warm_first_batch_ms")
    )
    d_warm_first = (
        round(b / a - 1.0, 4)
        if isinstance(a, (int, float)) and isinstance(b, (int, float)) and a
        else None
    )
    regressed = bool(
        (d_eps is not None and d_eps < -tolerance)
        or (d_p99_eval is not None and d_p99_eval > tolerance)
        # p99 whole-batch gate: the pipelined tail latency is the
        # interactive "babysit a live job" number — a >band worsening
        # fails the regression check like an events/s drop
        or (d_p99_batch is not None and d_p99_batch > tolerance)
        or (d_warm_first is not None and d_warm_first > tolerance)
        or (bool(cs_cur) and not cs_cur.get("warm_below_cold", True))
        or (d_lq_qps is not None and d_lq_qps < -tolerance)
        or (d_lq_p99 is not None and d_lq_p99 > tolerance)
        or (d_fleet_pub is not None and d_fleet_pub > tolerance)
        or (d_fleet_merge is not None and d_fleet_merge > tolerance)
        or (
            bool(current.get("fleet_rollup"))
            and not current["fleet_rollup"].get("conserved", True)
        )
        or (d_proto_cold is not None and d_proto_cold > tolerance)
        # acceptance bit: the bench's own well-ordered tail must seal
        # violation-free through the armed monitor
        or (
            bool(current.get("protocheck"))
            and current["protocheck"].get("violations", 0) != 0
        )
        or (d_conf_cold is not None and d_conf_cold > tolerance)
        # acceptance bit: the engine tree + the fully populated boot
        # conf must pass its own lattice clean
        or (
            bool(current.get("confcheck"))
            and current["confcheck"].get("findings", 0) != 0
        )
    )
    return {
        "baseline": os.path.basename(latest),
        "baseline_events_per_sec": prev.get("value"),
        "events_per_sec_delta": d_eps,
        "p99_rule_eval_delta": d_p99_eval,
        "p99_batch_delta": d_p99_batch,
        "warm_first_batch_delta": d_warm_first,
        "lq_kernel_qps_delta": d_lq_qps,
        "lq_p99_exec_delta": d_lq_p99,
        "protocheck_cold_delta": d_proto_cold,
        "confcheck_cold_delta": d_conf_cold,
        "fleet_publish_delta": d_fleet_pub,
        "fleet_merge_delta": d_fleet_merge,
        "tolerance": tolerance,
        "regressed": regressed,
    }


def main():
    import jax

    backend = jax.default_backend()
    capacity = int(os.environ.get(
        "BENCH_CAPACITY", "262144" if backend != "cpu" else "65536"
    ))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    iters = int(os.environ.get("BENCH_ITERS", "12"))
    runs = int(os.environ.get("BENCH_RUNS", "3"))
    base_ms = 1_700_000_000_000

    from data_accelerator_tpu.obs.histogram import HistogramRegistry

    hist = HistogramRegistry()

    # -- throughput: ingest-inclusive pipelined loop, multi-run ----------
    proc = build_processor(capacity)
    depth = int(os.environ.get(
        "BENCH_PIPELINE_DEPTH", str(proc.pipeline_depth)
    ))
    payloads = [
        make_json_payload(proc, capacity, seed=3 + j) for j in range(2)
    ]
    # the headline decoder number is the PRODUCTION path at the conf'd
    # shard count; the curve sweeps shards so scaling is published
    dec_rows_s, dec_mb_s = bench_decoder(proc, payloads[0], capacity)
    shard_curve = bench_decoder_shard_curve(proc, payloads[0], capacity)
    # warmup also seeds the sized-transfer EWMA, so the measured loops
    # run with adaptive D2H capacities like a warmed production host
    for i in range(warmup):
        raw = proc.encode_json_bytes(payloads[0], base_ms - 60_000 + i * 1000)
        proc.process_batch(raw, batch_time_ms=base_ms - 60_000 + i * 1000)
    decoder_path = proc.last_decoder_path
    decoder_shards = proc._decode_shards
    run_eps = []
    transfer_stats = {}
    for r in range(runs):
        run_eps.append(pipelined_ingest_loop(
            proc, payloads, iters, base_ms + r * 120_000, hist,
            depth=depth, transfer_stats=transfer_stats,
        ))
    eps = float(np.median(run_eps))
    p99_batch = hist.percentile(BENCH_FLOW, "batch", 99)
    # the dispatch loop's per-batch blocking cost in the pipelined loop:
    # the counts-only sync of the window's oldest batch (its tables land
    # on the background thread) — the production stall the tentpole
    # targets
    sync_pipelined = hist.percentile(BENCH_FLOW, "sync-pipelined", 50)
    d2h_bytes = (
        float(np.median(transfer_stats["d2h_bytes"]))
        if transfer_stats.get("d2h_bytes") else None
    )
    transfer_eff = (
        float(np.median(transfer_stats["efficiency"]))
        if transfer_stats.get("efficiency") else None
    )
    sync_counts_bytes = (
        float(np.median(transfer_stats["sync_counts_bytes"]))
        if transfer_stats.get("sync_counts_bytes") else None
    )

    # -- depth sweep: one run per non-headline depth, scratch histograms,
    # so the BENCH_* trajectory can attribute sync-stage/overlap deltas
    depth_sweep = {str(depth): round(eps, 1)}
    if os.environ.get("BENCH_DEPTH_SWEEP", "1") != "0":
        for d in (1, 2, 4):
            if d == depth:
                continue
            scratch = HistogramRegistry()
            depth_sweep[str(d)] = round(pipelined_ingest_loop(
                proc, payloads, iters, base_ms + 600_000 + d * 120_000,
                scratch, depth=d,
            ), 1)

    # -- latency mode: small batches, sequential, with stage breakdown ---
    lat_cap = int(os.environ.get("BENCH_LATENCY_CAPACITY", "8192"))
    lproc = build_processor(lat_cap)
    lpayloads = [
        make_json_payload(lproc, lat_cap, seed=11 + j) for j in range(2)
    ]
    for i in range(3):
        lraw = lproc.encode_json_bytes(
            lpayloads[0], base_ms + 900_000 + i * 1000
        )
        lproc.process_batch(lraw, batch_time_ms=base_ms + 900_000 + i * 1000)
    for r in range(runs):
        sequential_latency_loop(
            lproc, lpayloads, 24, base_ms + 910_000 + r * 120_000, hist
        )
    sync_rtt = measure_sync_rtt(lproc, lpayloads[0], base_ms + 990_000)
    device_step = measure_device_step(
        lproc, lpayloads, base_ms + 1_200_000, sync_rtt
    )

    med = {
        k: hist.percentile(BENCH_FLOW, k, 50)
        for k in ("decode", "dispatch", "sync", "collect")
    }
    # stage_sync_ms reports the dispatch loop's per-batch blocking cost
    # AS PRODUCTION PAYS IT: the counts-only sync of the window's
    # oldest batch inside the pipelined loop, whose counts vector has
    # been streaming since dispatch and (at depth >= 2) landed while
    # newer batches decoded/dispatched. The sequential loop's sync —
    # the same collect_counts with nothing overlapped, so it still
    # contains the un-hidden device wait — is kept
    # as stage_sync_sequential_ms (it is what sums with the other
    # sequential stages to ~p99_rule_eval_ms).
    stage_sync = sync_pipelined if sync_pipelined is not None else med["sync"]
    p99_rule = hist.percentile(BENCH_FLOW, "eval", 99)
    p99_compute = hist.percentile(BENCH_FLOW, "compute", 99)
    # engine latency = host ingest work (per-sample decode+dispatch as
    # the "engine-host" stage, so its real tail shows) + amortized
    # device compute. The completion sync is EXCLUDED here — not
    # hidden: it is reported as idle_sync_ms, the idle-device
    # handshake, not engine work.
    # rule_eval ~= engine + sync.
    p99_engine = hist.percentile(BENCH_FLOW, "engine-host", 99) + device_step

    result = {
        "metric": "iot_alerting_events_per_sec_per_chip_ingest_inclusive",
        "value": round(eps, 1),
        "unit": "events/s",
        "vs_baseline": round(eps / PER_CHIP_TARGET, 3),
        "runs": runs,
        "eps_min": round(min(run_eps), 1),
        "eps_max": round(max(run_eps), 1),
        "p99_batch_ms": round(p99_batch, 2),
        "pipeline_depth": depth,
        "depth_sweep_events_per_sec": depth_sweep,
        "d2h_bytes_per_batch": (
            round(d2h_bytes, 1) if d2h_bytes is not None else None
        ),
        "transfer_efficiency": (
            round(transfer_eff, 4) if transfer_eff is not None else None
        ),
        "p99_rule_eval_ms": round(p99_rule, 2),
        "p99_rule_compute_ms": round(p99_compute, 2),
        "p99_engine_ms": round(p99_engine, 2),
        "idle_sync_ms": round(sync_rtt, 2),
        "stage_decode_ms": round(med["decode"], 2),
        "stage_dispatch_ms": round(med["dispatch"], 2),
        "stage_device_step_ms": round(device_step, 2),
        "stage_sync_ms": round(stage_sync, 2),
        "stage_sync_sequential_ms": round(med["sync"], 2),
        "stage_collect_ms": round(med["collect"], 2),
        "sync_counts_bytes": (
            round(sync_counts_bytes, 1)
            if sync_counts_bytes is not None else None
        ),
        "decoder_rows_per_sec": round(dec_rows_s, 1) if dec_rows_s else None,
        "decoder_mb_per_sec": round(dec_mb_s, 1) if dec_mb_s else None,
        # rows/s vs conf'd decoder shard count (the tentpole's
        # published scaling curve; flat on a 1-core bench host)
        "decoder_shard_curve": shard_curve,
        "backend": backend,
        "batch_capacity": capacity,
        "bench_context": bench_context(
            dec_rows_s, decoder_path=decoder_path,
            decoder_shards=decoder_shards,
        ),
        "hbm_model": hbm_model_check(proc),
        "ici_model": ici_model_check(proc),
        # roofline vs the SEQUENTIAL latency loop's processor/stage
        # medians — predicted and observed describe the same batch shape
        "roofline": roofline_check(lproc, {
            "decode": med["decode"],
            "device-step": device_step,
            "collect": med["collect"],
        }),
        "cold_start": bench_cold_start(),
        "state_handoff": bench_state_handoff(),
        # debug-mode cost of the DX805 buffer sanitizer (poison +
        # scan), published so arming it in production is an informed
        # choice; no regression gate
        "sanitizer": bench_sanitizer(),
        # the DX9xx protocol gate: static analysis latency (cold vs
        # the mtime cache hit) and the DX906 monitor's per-batch cost;
        # the cold number is regression-gated (it rides every CI
        # validate call)
        "protocheck": bench_protocheck(),
        # the DX10xx conf gate: static lattice-scan latency (cold vs
        # the mtime cache hit) and the DX1006 ConfAudit's boot cost;
        # the cold number is regression-gated (it rides every CI
        # validate call)
        "confcheck": bench_confcheck(),
        "pilot": bench_pilot_overhead(),
        # the "millions of users" axis: interactive kernel QPS + p99
        # exec latency under multi-tenant open-loop load, published
        # beside the streaming events/s headline (ROADMAP item 3)
        "livequery": bench_livequery(),
        # fleet telemetry plane cost: per-frame publish + full-fleet
        # merge latency over a synthetic 8-replica fleet, with the
        # DX54x conservation audit as the acceptance bit
        "fleet_rollup": bench_fleet_rollup(),
    }
    reg = regression_gate(result)
    if reg is not None:
        result["regression"] = reg
    print(json.dumps(result))


if __name__ == "__main__":
    main()
