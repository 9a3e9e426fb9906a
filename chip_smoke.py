#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and answer right, on the chip?

Drives the system's main path once, the way a job client does: a child
process ``python -m data_accelerator_tpu.runtime.host conf=<file>
batches=<N>`` that reads newline JSON from a TCP socket, runs the flow on
whatever accelerator jax gives it, writes its results through the file
sinks and checkpoints offsets and window state. Two children, one after
the other, each alone on the chip:

1. **iot** — BASELINE config 1, the IoT alerting flow (projection, the
   ``OpenDoors`` rule, ``HeatAvg`` over ``DataXProcessedInput_5seconds``)
   at ``process.batchcapacity`` 262,144 and a 1 s interval: six ring
   slots of 262,144 rows live on the device. The socket is loaded ahead
   of the host's polls, so batches are as wide as the host lets them be;
   the run reports the valid rows of every batch.
2. **pallas** — BASELINE config 4 (``anomalyscore``, the one Pallas
   kernel) at the same capacity for a few batches, so Mosaic compiles it.

This process never imports jax or the package: a parent that has touched
jax holds the chip its children need. It writes conf and transform into
the output directory, makes the events from ``--seed``, feeds the socket,
waits, then reads what the CHILD recorded — its flight recorder
(``host/devices``: platform, device kind and count, placement, decode
engine, Pallas compile mode; ``streaming/batch/end``: every batch's time
and metrics) and its sink files — and compares them with a plain
numpy evaluation of the same transform over the same events.

It fails (exit 1, no result line) when the child saw no TPU, exited
non-zero or recorded a batch failure; when fewer batches landed than were
asked for or fewer than ``FULL_WIDTH_BATCHES`` of them were full width;
when the decoder was not the native one, the Pallas kernel was built in
interpret mode or ``Hbm_PeakBytes`` is zero; when a ``--numchips`` mesh
does not hold every ring and raw batch on all its chips; or when any row
differs from the reference. On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as the child's jax reported it.

    python chip_smoke.py [--seed N] [--numchips N] [--out DIR]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

CAPACITY = 262_144  # rows per batch: the size BENCH_r05 last ran on a chip
# the host halves its poll after a batch that overruns the interval (the
# first one compiles) and climbs back 1.25x a batch only while batches
# take under half the interval, which full-width ones do by a small margin
# on a quiet host (340-460 ms of 500): a run needs several batches more
# than the twelve full-width ones it must show
IOT_BATCHES = 24
FULL_WIDTH_BATCHES = 12  # two full 5 s windows and the eviction between
PALLAS_BATCHES = 4
WINDOW_MS = 5_000
INPUT_ROWS = "Input_DataXProcessedInput_Events_Count"  # valid rows a batch
RING_SLOTS = 6  # ceil(5 s / 1 s) + 1
CHILD_TIMEOUT_S = 500.0  # two children, one after the other, inside 1200 s

# float32 accumulation of up to RING_SLOTS * CAPACITY / 8 rows per group:
# random-walk rounding error is ~sqrt(n) * 2^-24 ~ 3e-5 relative
AVG_RTOL = 1e-4
# one exp and two divisions in float32, interpreter vs Mosaic vs numpy
SCORE_ATOL = 2e-6

DEVICE_TYPES = ("DoorLock", "Heating", "WindSpeed")
HOME_IDS = (150, 32, 88)

IOT_SCHEMA = {
    "type": "struct",
    "fields": [
        {"name": "deviceDetails", "type": {"type": "struct", "fields": [
            {"name": "deviceId", "type": "long", "nullable": False,
             "metadata": {}},
            {"name": "deviceType", "type": "string", "nullable": False,
             "metadata": {}},
            {"name": "homeId", "type": "long", "nullable": False,
             "metadata": {}},
            {"name": "status", "type": "long", "nullable": False,
             "metadata": {}},
            {"name": "temperature", "type": "double", "nullable": False,
             "metadata": {}},
        ]}, "nullable": False, "metadata": {}},
    ],
}

IOT_TRANSFORM = (
    "--DataXQuery--\n"
    "DoorEvents = SELECT deviceDetails.deviceId AS deviceId, "
    "deviceDetails.deviceType AS deviceType, deviceDetails.status AS status, "
    "deviceDetails.homeId AS homeId, "
    "deviceDetails.temperature AS temperature, eventTimeStamp "
    "FROM DataXProcessedInput\n"
    "--DataXQuery--\n"
    "OpenDoors = SELECT deviceId, eventTimeStamp FROM DoorEvents "
    "WHERE deviceType = 'DoorLock' AND status = 0\n"
    "--DataXQuery--\n"
    "HeatAvg = SELECT deviceId, COUNT(*) AS Cnt, AVG(temperature) AS AvgT "
    "FROM DataXProcessedInput_5seconds GROUP BY deviceId\n"
)

PALLAS_SCHEMA = {"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {}},
]}

PALLAS_TRANSFORM = (
    "--DataXQuery--\n"
    "Scored = SELECT deviceId, temperature, "
    "anomalyscore(temperature, deviceId) AS score "
    "FROM DataXProcessedInput\n"
    "--DataXQuery--\n"
    "HotAlerts = SELECT deviceId, temperature FROM Scored "
    "WHERE temperature > 90\n"
    "--DataXQuery--\n"
    "AnomalyAlerts = SELECT deviceId, score FROM Scored "
    "WHERE score > 0.9\n"
)
PALLAS_UDF_CLASS = "data_accelerator_tpu.udf.samples:anomalyscore"


# ---------------------------------------------------------------------------
# events, from the seed
# ---------------------------------------------------------------------------
def make_iot_events(seed: int, n: int) -> Dict[str, np.ndarray]:
    """An alerting stream: 2 % DoorLock events, half of them open
    (status 0) — so ~1 % of rows trip the rule; the rest Heating or
    WindSpeed; temperatures in thousandths of a degree, 0–100."""
    rng = np.random.default_rng(seed)
    is_door = rng.random(n) < 0.02
    return {
        "device": rng.integers(1, 9, n, dtype=np.int64),
        "type": np.where(is_door, 0, rng.integers(1, 3, n)),
        "home": rng.integers(0, len(HOME_IDS), n),
        "status": np.where(is_door & (rng.random(n) < 0.5), 0, 1),
        "milli": rng.integers(0, 100_000, n, dtype=np.int64),
    }


def iot_lines(ev: Dict[str, np.ndarray], lo: int, hi: int) -> bytes:
    fmt = (
        '{"deviceDetails":{"deviceId":%d,"deviceType":"%s","homeId":%d,'
        '"status":%d,"temperature":%d.%03d}}'
    )
    types = [DEVICE_TYPES[t] for t in ev["type"][lo:hi]]
    homes = [HOME_IDS[h] for h in ev["home"][lo:hi]]
    whole, frac = np.divmod(ev["milli"][lo:hi], 1000)
    rows = zip(
        ev["device"][lo:hi].tolist(), types, homes,
        ev["status"][lo:hi].tolist(), whole.tolist(), frac.tolist(),
    )
    return ("\n".join([fmt % r for r in rows]) + "\n").encode()


def make_pallas_events(seed: int, n: int) -> Dict[str, np.ndarray]:
    """Readings near their device's mean (score <= 0.74), with 1 % far
    outliers (score >= 0.95) and 1 % hot ones (> 90 degrees, which also
    score ~1): no score falls near the 0.9 rule threshold, so a last-bit
    difference between exp implementations cannot move a row across it."""
    rng = np.random.default_rng(seed + 1)
    device = rng.integers(1, 9, n, dtype=np.int64)
    kind = rng.random(n)
    spread = np.where(kind < 0.01, rng.uniform(3.0, 6.0, n),
                      rng.uniform(0.0, 1.0, n))
    temp = device + spread * (1.0 + device)
    temp = np.where(kind >= 0.99, rng.uniform(91.0, 100.0, n), temp)
    return {
        "device": device,
        "milli": np.round(temp * 1000.0).astype(np.int64),
    }


def pallas_lines(ev: Dict[str, np.ndarray], lo: int, hi: int,
                 ts_ms: int) -> bytes:
    fmt = '{"deviceId":%d,"temperature":%d.%03d,"eventTimeStamp":' \
        + str(ts_ms) + "}"
    whole, frac = np.divmod(ev["milli"][lo:hi], 1000)
    rows = zip(ev["device"][lo:hi].tolist(), whole.tolist(), frac.tolist())
    return ("\n".join([fmt % r for r in rows]) + "\n").encode()


def _temperature(milli: np.ndarray) -> np.ndarray:
    """The float32 the decoder stores for the text ``<milli/1000>``."""
    return (milli / 1000.0).astype(np.float32)


# ---------------------------------------------------------------------------
# the plain reference: same transform, numpy only
# ---------------------------------------------------------------------------
def reference_iot(
    ev: Dict[str, np.ndarray], batches: List[Tuple[int, int]],
) -> Tuple[List[List[int]], List[Dict[int, Tuple[int, float]]]]:
    """``batches``: (batch time ms, valid rows) as the host recorded
    them, in order; batch k consumed the next ``rows`` events of the
    stream. Returns per batch the OpenDoors deviceIds in stream order,
    and HeatAvg as deviceId -> (Cnt, AvgT) over the batches the engine's
    ring still holds (the last RING_SLOTS) whose time lies in
    [t - 5 s, t]."""
    bounds = np.concatenate([[0], np.cumsum([n for _t, n in batches])])
    temp = _temperature(ev["milli"]).astype(np.float64)
    open_doors: List[List[int]] = []
    heat_avg: List[Dict[int, Tuple[int, float]]] = []
    for k, (t, _n) in enumerate(batches):
        lo, hi = bounds[k], bounds[k + 1]
        door = (ev["type"][lo:hi] == 0) & (ev["status"][lo:hi] == 0)
        open_doors.append(ev["device"][lo:hi][door].tolist())
        cnt = np.zeros(9, np.int64)
        tot = np.zeros(9, np.float64)
        for j in range(max(0, k - RING_SLOTS + 1), k + 1):
            if not t - WINDOW_MS <= batches[j][0] <= t:
                continue
            a, b = bounds[j], bounds[j + 1]
            cnt += np.bincount(ev["device"][a:b], minlength=9)
            tot += np.bincount(ev["device"][a:b], temp[a:b], minlength=9)
        heat_avg.append({
            d: (int(cnt[d]), tot[d] / cnt[d]) for d in range(9) if cnt[d]
        })
    return open_doors, heat_avg


def reference_pallas(
    ev: Dict[str, np.ndarray], batches: List[Tuple[int, int]],
) -> Tuple[List[List[Tuple[int, float]]], List[List[Tuple[int, float]]]]:
    """Per batch: HotAlerts (deviceId, temperature) and AnomalyAlerts
    (deviceId, score) in stream order; the score is the formula of
    ``udf/samples.py _anomaly_kernel`` in float32."""
    bounds = np.concatenate([[0], np.cumsum([n for _t, n in batches])])
    x = _temperature(ev["milli"])
    mu = ev["device"].astype(np.float32)
    one = np.float32(1.0)
    score = one / (one + np.exp(-(np.abs(x - mu) / (one + np.abs(mu)))))
    hot, anomalies = [], []
    for k in range(len(batches)):
        lo, hi = bounds[k], bounds[k + 1]
        d, xs, s = ev["device"][lo:hi], x[lo:hi], score[lo:hi]
        h = xs > np.float32(90.0)
        hot.append(list(zip(d[h].tolist(), xs[h].tolist())))
        a = s > np.float32(0.9)
        anomalies.append(list(zip(d[a].tolist(), s[a].tolist())))
    return hot, anomalies


# ---------------------------------------------------------------------------
# the child host
# ---------------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_conf(
    run_dir: str, name: str, schema: dict, transform: str, port: int,
    capacity: int, outputs: List[str], extra: Dict[str, str],
) -> str:
    # what an earlier run left here the host would take for its own past:
    # a checkpoint to resume from, a flight recorder to append to, sink
    # files of batches this run never saw. Every run starts from nothing.
    if os.path.isdir(run_dir):
        shutil.rmtree(run_dir)
    os.makedirs(run_dir)
    schema_path = os.path.join(run_dir, "input.schema.json")
    transform_path = os.path.join(run_dir, "flow.transform")
    with open(schema_path, "w", encoding="utf-8") as f:
        json.dump(schema, f)
    with open(transform_path, "w", encoding="utf-8") as f:
        f.write(transform)
    conf = {
        "datax.job.name": name,
        "datax.job.input.default.inputtype": "socket",
        "datax.job.input.default.socket.port": str(port),
        "datax.job.input.default.blobschemafile": schema_path,
        "datax.job.input.default.streaming.intervalinseconds": "1",
        # the rate a deployment of this width declares: one full batch
        # per interval
        "datax.job.input.default.eventhub.maxrate": str(capacity),
        "datax.job.input.default.eventhub.checkpointdir":
            os.path.join(run_dir, "checkpoint"),
        "datax.job.input.default.eventhub.checkpointinterval": "5 second",
        "datax.job.process.batchcapacity": str(capacity),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.transform": transform_path,
        "datax.job.process.telemetry.tracefile":
            os.path.join(run_dir, "telemetry.jsonl"),
    }
    for out in outputs:
        conf[f"datax.job.output.{out}.file.path"] = os.path.join(
            run_dir, "out", out
        )
        conf[f"datax.job.output.{out}.file.compressiontype"] = "none"
    conf.update(extra)
    path = os.path.join(run_dir, "flow.conf")
    with open(path, "w", encoding="utf-8") as f:
        for k, v in conf.items():
            f.write(f"{k}={v}\n")
    return path


def run_host(
    run_dir: str, conf_path: str, batches: int, port: int,
    chunks: List[bytes],
) -> Dict[str, object]:
    """Start the host as a child, feed its socket, wait for it. Returns
    its exit code and wall times. The child inherits this process's
    environment untouched: which platform it runs on is jax's decision
    in the child, read back from the child's own record."""
    log_path = os.path.join(run_dir, "host.log")
    t_start = time.time()
    with open(log_path, "wb") as log:
        child = subprocess.Popen(
            [sys.executable, "-m", "data_accelerator_tpu.runtime.host",
             f"conf={conf_path}", f"batches={batches}"],
            cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
        )
    fed = {"error": None, "seconds": None}
    deadline = t_start + CHILD_TIMEOUT_S
    try:
        # the port opens when the host builds its source; everything is
        # sent at once from then on, so the source's buffer stays ahead
        # of the polls (the reader thread cannot keep a 1 s cadence full
        # against a paced sender — ROADMAP S2/S4)
        conn = None
        while conn is None and child.poll() is None and time.time() < deadline:
            try:
                conn = socket.create_connection(("127.0.0.1", port), 1.0)
            except OSError:
                time.sleep(0.1)

        def feed() -> None:
            t0 = time.time()
            try:
                with conn:
                    conn.settimeout(None)
                    for chunk in chunks:
                        conn.sendall(chunk)
                fed["seconds"] = time.time() - t0
            except OSError as e:  # child gone: its exit code tells why
                fed["error"] = f"{type(e).__name__}: {e}"

        feeder = None
        if conn is not None:
            feeder = threading.Thread(target=feed, daemon=True)
            feeder.start()
        timed_out = False
        try:
            child.wait(timeout=max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            timed_out = True
        if feeder is not None:
            # the host stops after its last batch with events still
            # queued; the reset that gives the sender is expected
            feeder.join(timeout=5.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)
    return {
        "returncode": child.returncode,
        "timed_out": timed_out,
        "connected": conn is not None,
        "started_at": t_start,
        "wall_s": time.time() - t_start,
        "feed_s": fed["seconds"],
        "log": log_path,
    }


def read_recorder(run_dir: str) -> Dict[str, object]:
    """What the child's flight recorder says: its device report, every
    landed batch's (time, metrics), and any exception it tracked."""
    device: Optional[dict] = None
    batches: List[Tuple[int, Dict[str, float]]] = []
    ends: List[float] = []
    exceptions: List[str] = []
    path = os.path.join(run_dir, "telemetry.jsonl")
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("type") == "exception":
                    exceptions.append(rec.get("error", ""))
                elif rec.get("name") == "host/devices":
                    device = rec["properties"]
                elif rec.get("name") == "streaming/batch/end":
                    batches.append((
                        int(rec["properties"]["batchTime"]),
                        rec["measurements"],
                    ))
                    ends.append(float(rec["ts"]))
    return {"device": device, "batches": batches, "ends": ends,
            "exceptions": exceptions}


def read_sink(run_dir: str, dataset: str) -> Dict[int, List[dict]]:
    """Sink files of one dataset -> batch time ms -> rows. The file
    sink names each file ``<dataset>_<batch ms>_<n>.json``."""
    out: Dict[int, List[dict]] = {}
    pattern = os.path.join(run_dir, "out", dataset, "**", f"{dataset}_*.json")
    for path in glob.glob(pattern, recursive=True):
        m = re.search(rf"{dataset}_(\d+)_\d+\.json$", path)
        with open(path, encoding="utf-8") as f:
            out.setdefault(int(m.group(1)), []).extend(
                json.loads(line) for line in f if line.strip()
            )
    return out


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------
def recorded_batches(rec: Dict[str, object]) -> List[Tuple[int, int]]:
    """(batch time ms, valid rows) of every landed batch, in order."""
    return [(t, int(m.get(INPUT_ROWS, 0))) for t, m in rec["batches"]]


def check_run(
    run: Dict[str, object], rec: Dict[str, object], asked: int,
    capacity: int, full_width_needed: int,
    numchips: int = 1, pallas: Optional[str] = None,
) -> Tuple[List[str], Dict[str, object]]:
    """The checks every phase shares; returns (failures, summary)."""
    fails: List[str] = []
    dev = rec["device"] or {}
    batches = rec["batches"]
    if not run["connected"]:
        fails.append("child: never opened its socket")
    if run["timed_out"]:
        fails.append(f"child: killed after {CHILD_TIMEOUT_S:.0f} s")
    if run["returncode"] != 0:
        fails.append(f"child: exit code {run['returncode']} ({run['log']})")
    if rec["exceptions"]:
        fails.append(f"child: recorded failures {rec['exceptions'][:3]}")
    with open(run["log"], encoding="utf-8", errors="replace") as f:
        if "rethrowing for retry" in f.read():
            fails.append("child: a batch failed and was requeued")
    if not dev:
        fails.append("child: no host/devices record")
    if dev.get("platform") != "tpu":
        fails.append(
            f"platform: child ran on {dev.get('platform')!r}, not a TPU"
        )
    if not str(dev.get("decoderPath") or "").startswith("native"):
        fails.append(f"decoder: path {dev.get('decoderPath')!r} is not native")
    if dev.get("batchCapacity") != capacity:
        fails.append(
            f"capacity: host ran {dev.get('batchCapacity')}, not {capacity}"
        )
    valid_rows = [n for _t, n in recorded_batches(rec)]
    if len(batches) < asked:
        fails.append(f"batches: {len(batches)} landed, {asked} asked for")
    full = sum(1 for n in valid_rows if n == capacity)
    if full < full_width_needed:
        fails.append(
            f"width: {full} batches of {capacity} valid rows, "
            f"{full_width_needed} needed (valid rows {valid_rows})"
        )
    hbm_peak = max((m.get("Hbm_PeakBytes", 0.0) for _t, m in batches),
                   default=0.0)
    if not hbm_peak > 0:
        fails.append("hbm: Hbm_PeakBytes is zero or absent")
    if pallas is not None:
        modes = dev.get("pallasInterpret") or {}
        if pallas not in modes:
            fails.append(f"pallas: host lists no Pallas UDF {pallas!r}")
        elif modes[pallas]:
            fails.append(f"pallas: {pallas} was built with interpret=True")
    if numchips > 1:
        placed = {
            "stepDevices": dev.get("stepDevices"),
            **{f"ring {t}": n
               for t, n in (dev.get("ringDevices") or {}).items()},
            **{f"raw {s}": n
               for s, n in (dev.get("rawDevices") or {}).items()},
        }
        for what, n in placed.items():
            if n != numchips:
                fails.append(f"mesh: {what} on {n} devices, not {numchips}")
        in_use = dev.get("deviceBytesInUse") or []
        if len(in_use) != numchips or not all(b > 0 for b in in_use):
            fails.append(f"mesh: bytes in use per device {in_use}")
    summary = {
        "platform": dev.get("platform"),
        "device_kind": dev.get("deviceKind"),
        "device_count": dev.get("deviceCount"),
        "jax_version": dev.get("jaxVersion"),
        "decoder_path": dev.get("decoderPath"),
        "batch_capacity": dev.get("batchCapacity"),
        "placement": {k: dev.get(k) for k in (
            "stepDevices", "ringDevices", "rawDevices", "deviceBytesInUse",
        )},
        "pallas_interpret": dev.get("pallasInterpret"),
        "batches_landed": len(batches),
        "valid_rows_per_batch": valid_rows,
        "full_width_batches": full,
        "Hbm_PeakBytes": hbm_peak,
        "compile_cache_hits": sum(
            m.get("Compile_Cache_Hit_Count", 0.0) for _t, m in batches),
        "compile_cache_misses": sum(
            m.get("Compile_Cache_Miss_Count", 0.0) for _t, m in batches),
        "time_to_first_batch_s": (
            rec["ends"][0] - run["started_at"] if rec["ends"] else None),
        "latency_batch_ms": [
            round(m.get("Latency-Batch", 0.0), 1) for _t, m in batches],
        "child_wall_s": run["wall_s"],
        "feed_s": run["feed_s"],
    }
    return fails, summary


def check_iot(ev: Dict[str, np.ndarray], run_dir: str,
              rec: Dict[str, object]) -> Tuple[List[str], int]:
    """Sink rows against the reference; returns (failures, rows
    compared). Every differing row is one failure (the first few are
    spelled out)."""
    batches = recorded_batches(rec)
    want_doors, want_heat = reference_iot(ev, batches)
    got_doors = read_sink(run_dir, "OpenDoors")
    got_heat = read_sink(run_dir, "HeatAvg")
    diffs: List[str] = []
    compared = 0
    for k, (t, _n) in enumerate(batches):
        rows = got_doors.get(t, [])
        compared += max(len(rows), len(want_doors[k]))
        if [r["deviceId"] for r in rows] != want_doors[k]:
            diffs.append(
                f"OpenDoors batch {k}: {len(rows)} rows, reference "
                f"{len(want_doors[k])}, or their deviceIds differ"
            )
        if any(r["eventTimeStamp"] != t for r in rows):
            diffs.append(f"OpenDoors batch {k}: eventTimeStamp != batch time")
        got = {r["deviceId"]: (r["Cnt"], r["AvgT"])
               for r in got_heat.get(t, [])}
        compared += max(len(got), len(want_heat[k]))
        for d in sorted(set(got) | set(want_heat[k])):
            g, w = got.get(d), want_heat[k].get(d)
            if g is None or w is None or g[0] != w[0] \
                    or abs(g[1] - w[1]) > AVG_RTOL * abs(w[1]):
                diffs.append(
                    f"HeatAvg batch {k} device {d}: got {g}, reference {w}"
                )
    for dataset, got in (("OpenDoors", got_doors), ("HeatAvg", got_heat)):
        for t in sorted(set(got) - {t for t, _n in batches}):
            diffs.append(f"{dataset}: sink file for unrecorded batch {t}")
    return _row_failures(diffs), compared


def check_pallas(ev: Dict[str, np.ndarray], run_dir: str,
                 rec: Dict[str, object]) -> Tuple[List[str], int]:
    batches = recorded_batches(rec)
    want_hot, want_anom = reference_pallas(ev, batches)
    got_hot = read_sink(run_dir, "HotAlerts")
    got_anom = read_sink(run_dir, "AnomalyAlerts")
    diffs: List[str] = []
    compared = 0
    for k, (t, _n) in enumerate(batches):
        for name, rows, want, col, tol in (
            ("HotAlerts", got_hot.get(t, []), want_hot[k],
             "temperature", 0.0),
            ("AnomalyAlerts", got_anom.get(t, []), want_anom[k],
             "score", SCORE_ATOL),
        ):
            compared += max(len(rows), len(want))
            if [r["deviceId"] for r in rows] != [d for d, _v in want]:
                diffs.append(
                    f"{name} batch {k}: {len(rows)} rows, reference "
                    f"{len(want)}, or their deviceIds differ"
                )
                continue
            for i, (r, (_d, v)) in enumerate(zip(rows, want)):
                if abs(r[col] - v) > tol:
                    diffs.append(
                        f"{name} batch {k} row {i}: {col} {r[col]!r}, "
                        f"reference {v!r}"
                    )
    return _row_failures(diffs), compared


def check_checkpoint(run_dir: str, valid_rows: List[int]
                     ) -> Tuple[List[str], Dict[str, object]]:
    """The offsets the host committed must end on one of its batch
    boundaries, and the window snapshot must be there. The snapshot
    (the whole ring) is then deleted: it would not fit in what a chip
    call may bring back."""
    fails: List[str] = []
    ckpt = os.path.join(run_dir, "checkpoint")
    until = None
    offsets = os.path.join(ckpt, "offsets.txt")
    if os.path.exists(offsets):
        with open(offsets, encoding="utf-8") as f:
            until = int(f.readline().strip().split(",")[-1])
    if until is None or until not in set(np.cumsum(valid_rows).tolist()):
        fails.append(
            f"checkpoint: committed offset {until} is no batch boundary"
        )
    window = os.path.join(ckpt, "window.npz")
    window_bytes = os.path.getsize(window) if os.path.exists(window) else 0
    if not window_bytes:
        fails.append("checkpoint: no window.npz")
    for path in (window, window + ".old"):
        if os.path.exists(path):
            os.remove(path)
    return fails, {"offset": until, "window_npz_bytes": window_bytes}


def _row_failures(diffs: List[str]) -> List[str]:
    if not diffs:
        return []
    return [f"rows: {len(diffs)} differ from the reference"] + diffs[:5]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def _chunks(lines, ev, total: int, step: int) -> List[bytes]:
    return [lines(ev, lo, min(lo + step, total))
            for lo in range(0, total, step)]


def phase_iot(out_dir: str, seed: int, numchips: int = 1,
              capacity: int = CAPACITY, batches: int = IOT_BATCHES,
              full_width: int = FULL_WIDTH_BATCHES) -> Dict[str, object]:
    run_dir = os.path.join(out_dir, "iot")
    t0 = time.time()
    # two batches more than asked for: the buffer never runs dry
    total = (batches + 2) * capacity
    ev = make_iot_events(seed, total)
    chunks = _chunks(iot_lines, ev, total, capacity)
    gen_s = time.time() - t0
    port = _free_port()
    extra = {
        "datax.job.process.timewindow.DataXProcessedInput_5seconds"
        ".windowduration": "5 seconds",
        "datax.job.process.projection":
            "current_timestamp() AS eventTimeStamp\\nRaw.*",
    }
    if numchips > 1:
        extra["datax.job.process.numchips"] = str(numchips)
    conf = write_conf(
        run_dir, "ChipSmokeIoT", IOT_SCHEMA, IOT_TRANSFORM, port, capacity,
        ["OpenDoors", "HeatAvg"], extra,
    )
    run = run_host(run_dir, conf, batches, port, chunks)
    t1 = time.time()
    rec = read_recorder(run_dir)
    fails, summary = check_run(
        run, rec, batches, capacity, full_width, numchips=numchips,
    )
    row_fails, compared = check_iot(ev, run_dir, rec)
    ckpt_fails, ckpt = check_checkpoint(
        run_dir, summary["valid_rows_per_batch"]
    )
    summary.update(
        rows_compared=compared, checkpoint=ckpt, generate_s=gen_s,
        verify_s=time.time() - t1, numchips=numchips,
    )
    return {"failures": fails + row_fails + ckpt_fails, "summary": summary,
            "events": ev, "run_dir": run_dir, "recorder": rec}


def phase_pallas(out_dir: str, seed: int, capacity: int = CAPACITY,
                 batches: int = PALLAS_BATCHES,
                 udf_class: str = PALLAS_UDF_CLASS) -> Dict[str, object]:
    run_dir = os.path.join(out_dir, "pallas")
    t0 = time.time()
    total = (batches + 2) * capacity
    ev = make_pallas_events(seed, total)
    now_ms = int(time.time() * 1000)
    chunks = _chunks(
        lambda e, lo, hi: pallas_lines(e, lo, hi, now_ms), ev, total,
        capacity,
    )
    gen_s = time.time() - t0
    port = _free_port()
    conf = write_conf(
        run_dir, "ChipSmokePallas", PALLAS_SCHEMA, PALLAS_TRANSFORM, port,
        capacity, ["HotAlerts", "AnomalyAlerts"],
        {"datax.job.process.jar.udf.anomalyscore.class": udf_class},
    )
    run = run_host(run_dir, conf, batches, port, chunks)
    t1 = time.time()
    rec = read_recorder(run_dir)
    # width is the iot phase's to show; this one is about the kernel
    fails, summary = check_run(
        run, rec, batches, capacity, 0, pallas="anomalyscore",
    )
    row_fails, compared = check_pallas(ev, run_dir, rec)
    summary.update(
        rows_compared=compared, generate_s=gen_s, verify_s=time.time() - t1,
    )
    return {"failures": fails + row_fails, "summary": summary,
            "events": ev, "run_dir": run_dir, "recorder": rec}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926,
                    help="seed of the generated events")
    ap.add_argument("--numchips", type=int, default=1,
                    help="process.numchips of the iot host (a mesh over "
                         "that many chips; fails when fewer are visible)")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"), help="output directory")
    args = ap.parse_args(argv)
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)

    t0 = time.time()
    iot = phase_iot(out_dir, args.seed, numchips=args.numchips)
    pallas = phase_pallas(out_dir, args.seed)
    failures = (
        [f"iot: {f}" for f in iot["failures"]]
        + [f"pallas: {f}" for f in pallas["failures"]]
    )
    report = {
        "ok": not failures,
        "seed": args.seed,
        "failures": failures,
        "iot": iot["summary"],
        "pallas": pallas["summary"],
        "wall_s": time.time() - t0,
    }
    with open(os.path.join(out_dir, "chip_smoke.json"), "w",
              encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    if failures:
        # the report goes to stderr: stdout carries a result only when
        # there is one
        print(json.dumps(report, indent=1), file=sys.stderr)
        print(f"chip_smoke FAILED: {len(failures)} failure(s)",
              file=sys.stderr)
        return 1
    print(json.dumps(report, indent=1))
    dev = iot["summary"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
