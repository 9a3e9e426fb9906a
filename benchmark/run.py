#!/usr/bin/env python3
"""One run of one benchmark cell against the served host.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports jax or the package. It reads the cell's files
(``BENCHMARK.json`` -> ``benchmark/workloads/<cell>.json`` ->
``benchmark/configs/<config>.json`` -> ``benchmark/flows/<flow>.py``),
writes conf, schema and transform into a run directory emptied first,
starts the entry a job client deploys (``python -m
data_accelerator_tpu.runtime.host conf=<file>``), feeds its socket source
from ``--seed`` on the mix's schedule, and afterwards reads only what a
client can see: sink files, the committed checkpoint, the child's flight
recorder. See ``benchmark/README.md``.

Set-up ends, and the measured window opens, at the landing of the batch
that completes the warm-up; ``--seconds`` later it closes; then the
parent stops sending, lets the host land what it holds and ends it. A
window in which the host still compiled or loaded a program was warm-up
after all: the window opens anew, and set-up counts that stretch.
Every row of every landed batch is compared with the plain reference.

Exit code 0 and one JSON object as the last line of stdout when there is
a result. Anything else prints no result: no TPU in the child's own
report, a decoder that was not the native one, a compilation inside the
window, a child that failed."""

import argparse
import glob
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # started as a script, sys.path[0] is benchmark/: its trace.py would
    # then shadow the standard library's
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, readers, served, traffic, window  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
ASK_BATCHES = 1_000_000  # main() stops on batches=N alone: never reached
SETUP_LIMIT_S = 900.0   # spawn -> first batch landed (a cold compile)
RUN_LIMIT_S = 1100.0    # the whole run, beyond its window's length
CATCH_UP_CHUNKS = 5
MAX_REOPENS = 3         # windows given up because the host compiled in them
DRAIN_LIMIT_S = 60.0
CAPTURE_LIMIT_S = 120.0
PROFILE_S = 5
# the capture is asked for this long before the window closes: writing it
# out stalls the traced host's loop for seconds (its Python tracer), so
# the spans and counters are read from the window's batches before it
PROFILE_BEFORE_CLOSE_S = PROFILE_S + 2.0


class BenchFailure(Exception):
    """The run has no result."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_flow(name: str):
    path = os.path.join(BENCH, "flows", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_flow_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str) -> dict:
    """Everything one cell names, found by name from BENCHMARK.json. A
    mix that has its file and no manifest entry yet (a cell kept under
    PERF.md's open questions) runs too, and reports no metric."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mix_path = os.path.join(BENCH, "workloads", f"{name}.json")
    if not os.path.exists(mix_path):
        raise BenchFailure(f"no workload file {mix_path}")
    mix = load_json(mix_path)
    listed = any(w["name"] == name for w in manifest["workloads"])
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == mix["config"])
    cells = [w["name"] for w in manifest["workloads"]]
    reports = lambda m: listed and name in m.get("workloads", cells)  # noqa: E731
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    return {
        "name": name, "chips": mix["chips"], "mix": mix, "config": config,
        "flow": load_flow(config["flow"]),
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


# ---------------------------------------------------------------------------
# the run: one thread sends on schedule and follows the recorder
# ---------------------------------------------------------------------------
def drive(cell: dict, seed: int, seconds: float, trace: bool, run_dir: str,
          child_argv: Optional[List[str]] = None) -> dict:
    """Start the host, warm it up, hold the mix's load for ``seconds``,
    drain, end the host. Returns what the parent itself knows: when
    every chunk was due and sent, which batch opened the window."""
    config, mix = cell["config"], cell["mix"]
    tr = mix["traffic"]
    width = int(tr["declared_width"])
    interval = float(config["interval_s"])
    t_spawn = time.time()
    port = served.free_port()
    obs_port = served.free_port() if trace else None
    conf_path = served.write_conf(run_dir, config, width, port, obs_port)
    rec = served.Recorder(run_dir)
    stream = traffic.EventStream(cell["flow"], seed)
    paced = tr["mode"] == "paced"
    sched = traffic.Schedule(tr) if paced else None
    dt = tr["chunk_ms"] / 1000.0
    ahead = int(tr.get("ahead_widths", 0) * width)
    chunk_lo: List[int] = []      # first event of chunk c
    chunk_due: List[float] = []   # when it was due, epoch s
    chunk_sent: List[float] = []  # when its send began
    warm = mix["warmup"]
    conn = None
    # nothing between the child's start and the ``try`` that ends it
    child = served.spawn_host(run_dir, conf_path, ASK_BATCHES, child_argv)
    try:
        deadline = t_spawn + SETUP_LIMIT_S
        while conn is None:
            if child.poll() is not None:
                raise BenchFailure(
                    f"child exited with {child.returncode} before it opened "
                    f"its socket ({run_dir}/host.log)")
            if time.time() > deadline:
                raise BenchFailure("child never opened its socket")
            try:
                conn = socket.create_connection(("127.0.0.1", port), 1.0)
            except OSError:
                time.sleep(0.05)
        conn.settimeout(60.0)
        sent = consumed = k = base = 0
        origin = time.time()
        if paced:
            # one interval's arrivals for the first batch, which compiles
            # (tens of seconds cold); the schedule starts when it has
            # landed. Sending on schedule through the compile would pile
            # minutes of arrivals into the source's buffer, and the window
            # would open on a host still working them off.
            base = sent = int(sched.rate * interval)
            chunk_lo.append(0)
            chunk_due.append(origin)
            chunk_sent.append(origin)
            conn.sendall(stream.render(0, sent, origin))
            while not rec.poll():
                if child.poll() is not None:
                    raise BenchFailure(
                        f"child exited with {child.returncode} in its first "
                        f"batch ({run_dir}/host.log)")
                if time.time() > deadline:
                    raise BenchFailure("the first batch never landed")
                time.sleep(0.05)
            origin = time.time()
        opened_by = None          # index of the batch whose landing opens
        opened_at = stop_at = None
        profile_at = None
        profiled = None
        seen = reopened = 0
        sending = True
        ready = None              # the next paced chunk, rendered ahead
        while True:
            now = time.time()
            if child.poll() is not None:
                raise BenchFailure(
                    f"child exited with {child.returncode} mid-run "
                    f"({run_dir}/host.log)")
            if now > t_spawn + RUN_LIMIT_S + seconds:
                raise BenchFailure(
                    f"run not over {RUN_LIMIT_S:.0f} s past its window's "
                    f"length: {len(rec.batches)} batches landed, "
                    f"{sent - consumed} events behind")
            if sending and paced:
                # at most a few chunks a turn: a sender the host holds back
                # (TCP backpressure) must still follow the recorder
                for _ in range(CATCH_UP_CHUNKS):
                    if origin + sched.due(k) > now:
                        break
                    lo, hi = base + sched.lo(k), base + sched.lo(k + 1)
                    due = origin + sched.due(k)
                    data = ready or stream.render(lo, hi, due)
                    ready = None
                    chunk_lo.append(lo)
                    chunk_due.append(due)
                    chunk_sent.append(time.time())
                    conn.sendall(data)
                    sent = hi
                    k += 1
                    now = time.time()
                ready = ready or stream.render(
                    base + sched.lo(k), base + sched.lo(k + 1),
                    origin + sched.due(k))
            elif sending and origin + k * dt <= now:
                n = min(int(tr["max_chunk_events"]), ahead - (sent - consumed))
                if n > 0:
                    data = stream.render(sent, sent + n, now)
                    chunk_lo.append(sent)
                    chunk_due.append(now)
                    chunk_sent.append(time.time())
                    conn.sendall(data)
                    sent += n
                k = int((time.time() - origin) / dt) + 1
            rec.poll()
            rows = rec.rows
            consumed = sum(rows)
            for b in range(seen, len(rec.batches)):
                # the host compiled, or loaded a program, in a batch that
                # landed inside the window: a shape the warm-up had not
                # reached (the sized transfer changes its bucket batches
                # after the count that moved it). That stretch was warm-up:
                # the window opens anew once the host is quiet again. Not
                # once the capture is asked for; measure() then refuses a
                # compilation, as it does after MAX_REOPENS. (The end
                # event's ``ts`` lies up to a checkpoint after the landing
                # measure() goes by: half a second of margin.)
                _t, meas, ts = rec.batches[b]
                if sending and opened_by is not None and b > opened_by \
                        and ts <= opened_at + seconds + 0.5 \
                        and profiled is None and reopened < MAX_REOPENS \
                        and compiled(meas):
                    reopened += 1
                    opened_by = opened_at = stop_at = profile_at = None
            seen = len(rec.batches)
            if opened_by is None and rows:
                if _warm(rows, sent - consumed, warm, sched, interval,
                         [meas for _t, meas, _ts in rec.batches]):
                    opened_by = len(rows) - 1
                    opened_at = rec.batches[-1][2]
                    stop_at = opened_at + seconds + 1.5 * interval
                    profile_at = opened_at + seconds - PROFILE_BEFORE_CLOSE_S
                elif now > deadline:
                    raise BenchFailure(
                        f"warm-up not over after {SETUP_LIMIT_S:.0f} s: "
                        f"valid rows {rows[-12:]}, {sent - consumed} behind")
            elif opened_by is None and now > deadline:
                raise BenchFailure("no batch landed")
            if trace and profiled is None and profile_at and now >= profile_at:
                profiled = _start_profile(obs_port)
            if sending and stop_at is not None and now >= stop_at:
                sending = False
                conn.close()
                conn = None
            if not sending and (consumed >= sent
                                or now > stop_at + DRAIN_LIMIT_S):
                break
            nxt = origin + (sched.due(k) if paced else k * dt) if sending \
                else now + 0.05
            time.sleep(min(max(nxt - time.time(), 0.0), 0.02))
        # the batch that took the last events has landed. Its root span
        # is recorded after its end event (a checkpoint due with it comes
        # in between), and its spans are found through the root span:
        # wait for it, then end the host
        waited = time.time()
        while not rec.spans(rec.batches[-1][0]):
            if time.time() > waited + DRAIN_LIMIT_S:
                raise BenchFailure("the last batch's spans never came")
            time.sleep(0.05)
            rec.poll()
        if profiled is not None:
            # the child's timer thread is still writing the capture out
            # (tens of MB, behind the loop's Python): ending the child now
            # would lose it
            capture = os.path.join(profiled["path"], "**", "*.xplane.pb")
            while not glob.glob(capture, recursive=True):
                if time.time() > waited + CAPTURE_LIMIT_S:
                    raise BenchFailure("the device trace was never written")
                time.sleep(0.25)
            time.sleep(1.0)
    except OSError as e:  # the socket: reset by a dying child, or held 60 s
        raise BenchFailure(f"socket to the host: {e!r} "
                           f"({run_dir}/host.log)") from e
    finally:
        if conn is not None:
            conn.close()
        if child.poll() is None:
            child.send_signal(signal.SIGTERM)
            try:
                child.wait(timeout=30)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait(timeout=30)
    rec.settle()
    return {
        "t_spawn": t_spawn, "origin": origin, "rec": rec, "stream": stream,
        "sent": sent, "consumed": consumed, "opened_by": opened_by,
        "chunk_lo": np.array(chunk_lo + [sent], np.int64),
        "chunk_due": np.array(chunk_due), "chunk_sent": np.array(chunk_sent),
        "profile": profiled, "profile_posted_at": profile_at,
        "run_dir": run_dir, "reopened": reopened,
    }


def compiled(measurements: Dict[str, float]) -> bool:
    """Did the host compile a program (a miss of jax's persistent cache)
    or load one from it (a hit) in this batch? The host arms the cache
    for every program, however small, so one of the two counts rises
    whenever a shape runs for the first time in the process."""
    return bool(measurements.get("Compile_Cache_Miss_Count", 0.0)
                or measurements.get("Compile_Cache_Hit_Count", 0.0))


def _warm(rows: List[int], behind: int, warm: dict, sched,
          interval: float, measurements: List[Dict[str, float]]) -> bool:
    """Is the warm-up over at this landing? ``min_batches`` have landed
    (the 5 s ring is full, every shape has run), the newest
    ``quiet_batches`` neither compiled nor loaded a program and, in a
    steady paced mix, the newest ``steady_batches`` each took one
    interval's arrivals within ``steady_tolerance`` with no more than
    ``max_behind_intervals`` of arrivals sent and not yet landed: the
    ramp of the loop's backpressure after the compiling first batch is
    over. A host that is still not steady after ``max_batches`` is
    measured as it is (its backlog then shows in the latencies), not
    waited for; one that still compiles is waited for."""
    if len(rows) < warm["min_batches"]:
        return False
    quiet = warm.get("quiet_batches", 0)
    if quiet and any(compiled(m) for m in measurements[-quiet:]):
        return False
    if sched is None or not sched.steady or "steady_batches" not in warm \
            or len(rows) >= warm["max_batches"]:
        return True
    want = sched.rate * interval
    newest = rows[-warm["steady_batches"]:]
    return all(abs(r - want) <= warm["steady_tolerance"] * want
               for r in newest) \
        and behind <= warm["max_behind_intervals"] * want


def _start_profile(obs_port: int) -> dict:
    """Ask the child for a device trace of the steady window, as a
    separate client would: POST /profile on its observability port."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{obs_port}/profile?seconds={PROFILE_S}",
        data=b"", method="POST")
    with urllib.request.urlopen(req, timeout=10) as resp:
        got = json.loads(resp.read())
    if "path" not in got or got.get("error"):
        raise BenchFailure(f"profiler: {got}")
    return got


# ---------------------------------------------------------------------------
# from the run to the result line
# ---------------------------------------------------------------------------
def check_child(run: dict, cell: dict, require_tpu: bool) -> None:
    rec = run["rec"]
    dev = rec.device or {}
    fails = []
    if rec.exceptions:
        fails.append(f"child recorded failures {rec.exceptions[:3]}")
    with open(os.path.join(run["run_dir"], "host.log"), encoding="utf-8",
              errors="replace") as f:
        if "rethrowing for retry" in f.read():
            fails.append("a batch failed and was requeued")
    if not dev:
        fails.append("no host/devices record")
    if require_tpu and dev.get("platform") != "tpu":
        fails.append(f"child ran on {dev.get('platform')!r}, not a TPU")
    if require_tpu and dev.get("deviceCount", 0) < cell["chips"]:
        fails.append(f"child saw {dev.get('deviceCount')} chips, the cell "
                     f"asks for {cell['chips']}")
    if not str(dev.get("decoderPath") or "").startswith("native"):
        fails.append(f"decoder path {dev.get('decoderPath')!r} is not native")
    if dev.get("batchCapacity") != int(cell["mix"]["traffic"]["declared_width"]):
        fails.append(f"host ran capacity {dev.get('batchCapacity')}")
    if run["consumed"] < run["sent"]:
        fails.append(f"{run['sent'] - run['consumed']} events sent never "
                     f"landed within {DRAIN_LIMIT_S:.0f} s of the close")
    if fails:
        raise BenchFailure("; ".join(fails))


def measure(run: dict, cell: dict, seconds: float) -> dict:
    """Window, end-to-end numbers and the material the readers use."""
    rec = run["rec"]
    batches = rec.batches
    rows = rec.rows
    spans = [rec.spans(t) for t, _m, _ts in batches]
    landed_at = []
    for (t, _m, _ts), sp in zip(batches, spans):
        if "sinks" not in sp:
            raise BenchFailure(f"batch {t} has no sinks span")
        landed_at.append(sp["sinks"][0] + sp["sinks"][1] / 1000.0)
    opened_at = landed_at[run["opened_by"]]
    win = window.in_window(landed_at, opened_at, seconds)
    if len(win) < 3:
        raise BenchFailure(f"{len(win)} batches landed inside the window")
    misses = sum(batches[k][1].get("Compile_Cache_Miss_Count", 0.0)
                 for k in win)
    if misses:
        raise BenchFailure(
            f"Compile_Cache_Miss_Count rose by {misses:.0f} inside the "
            "measured window: a shape was not warmed up")
    bounds = window.batch_bounds(rows)
    stream = run["stream"]
    ev = stream.events(int(bounds[-1]))
    alerts = {k: cell["flow"].alert_events(ev, int(bounds[k]),
                                           int(bounds[k + 1])) for k in win}
    due_s = DueLookup(run["chunk_lo"], run["chunk_due"])
    lat = window.alert_latencies_ms(due_s, alerts, landed_at)
    e2e = {
        "events_per_s": window.events_per_s(rows, landed_at, opened_at, win),
        "setup_s": opened_at - run["t_spawn"],
    }
    if len(lat):
        e2e["alert_latency_p50_ms"] = window.percentile(lat, 50)
        e2e["alert_latency_p95_ms"] = window.percentile(lat, 95)
    # what the per-layer readers see: in a traced run, the window up to
    # the moment the capture was asked for
    quiet_until = run["profile_posted_at"] or opened_at + seconds
    quiet = [k for k in win if landed_at[k] <= quiet_until]
    in_win = (run["chunk_due"] > opened_at) & (run["chunk_due"] <= quiet_until)

    def backlog(k: int) -> int:
        """Events due by batch k's landing less events landed by it."""
        due = np.searchsorted(run["chunk_due"], landed_at[k], side="right")
        return int(run["chunk_lo"][due] - bounds[k + 1])
    return {
        "e2e": e2e, "rows": rows, "bounds": bounds, "events": ev,
        "landed_at": landed_at, "opened_at": opened_at, "window": win,
        "spans": [spans[k] for k in quiet],
        "measurements": [batches[k][1] for k in quiet],
        "send_late_ms": (run["chunk_sent"] - run["chunk_due"])[in_win] * 1e3,
        "alert_rows": int(len(lat)),
        "backlog_open": backlog(run["opened_by"]),
        "backlog_close": backlog(win[-1]),
        "memory_peak_bytes": int(max(
            (m.get("Hbm_PeakBytes", 0.0) for _t, m, _ts in batches),
            default=0.0)),
    }


class DueLookup:
    """``due[i]``: when event i was due to be sent (its chunk's time)."""

    def __init__(self, chunk_lo: np.ndarray, chunk_due: np.ndarray):
        self._lo, self._due = chunk_lo, chunk_due

    def __getitem__(self, idx: np.ndarray) -> np.ndarray:
        return self._due[np.searchsorted(self._lo, idx, side="right") - 1]


def decide(run: dict, cell: dict, m: dict, got=None) -> dict:
    """Every row of every landed batch against the plain reference, and
    the committed offset. ``got(events, batches)`` replaces the sink
    files' rows (the low-precision control puts itself in the program's
    place: see ``control.py``)."""
    flow, config = cell["flow"], cell["config"]
    batches = [(t, n) for (t, _m, _ts), n in zip(run["rec"].batches,
                                                m["rows"])]
    want = flow.reference(m["events"], batches)
    mtime_gap_ms = None
    if got is None:
        got, mtime_gap_ms = sink_rows(run, flow, batches, m["landed_at"])
    else:
        got = got(m["events"], batches)
    numbers, compared, notes = compare.compare_rows(flow.COLUMNS, got, want)
    committed, window_bytes = served.read_checkpoint(run["run_dir"])
    numbers["offset_off_boundary"] = compare.offset_off_boundary(
        committed, m["bounds"])
    if "window" in config["roofline"]:
        numbers["window_snapshot_missing"] = 0 if window_bytes else 1
    ok, compared_numbers = compare.verdict(numbers, flow.LIMITS)
    return {"correct": ok, "compared": compared_numbers, "notes": notes,
            "rows_compared": compared, "committed_offset": committed,
            "sink_mtime_after_span_ms": mtime_gap_ms}


def sink_rows(run: dict, flow, batches, landed_at):
    """Sink files -> per dataset, per batch, one array a column; and by
    how much the newest file's mtime lies after the end of its batch's
    ``sinks`` span (the file sink renames a temp file, which keeps the
    earlier mtime: the span's end is when a reader could see it)."""
    got = {}
    gap_ms = 0.0
    times = {t: k for k, (t, _n) in enumerate(batches)}
    for dataset, how in flow.COLUMNS.items():
        files = served.read_sink(run["run_dir"], dataset, list(how))
        # a file newer than the last recorded batch is of a batch the
        # child was ended in the middle of: not a result anyone was given
        stray = sorted(t for t in set(files) - set(times)
                       if t < batches[-1][0])
        files = {t: f for t, f in files.items() if t in times}
        if stray:
            raise BenchFailure(f"{dataset}: sink files for unrecorded "
                               f"batches {stray[:3]}")
        per_batch = [None] * len(batches)
        for t, (cols, mtime) in files.items():
            per_batch[times[t]] = cols
            gap_ms = max(gap_ms, (mtime - landed_at[times[t]]) * 1000.0)
        got[dataset] = per_batch
    return got, gap_ms


def result_line(cell: dict, run: dict, m: dict, verdict: dict,
                trace: Optional[dict]) -> dict:
    dev = run["rec"].device
    device = {
        "platform": dev["platform"], "kind": dev["deviceKind"],
        "count": dev["deviceCount"],
        "memory_peak_bytes": m["memory_peak_bytes"],
    }
    if trace is None:
        units = {e["name"]: e["unit"] for e in cell["end_to_end"]}
        metrics = {n: {"value": m["e2e"][n], "unit": u}
                   for n, u in units.items()}
    else:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        metrics = readers.read_all(cell, run, m, trace)
    line = {
        "correct": verdict["correct"],
        "attempted": int(sum(m["rows"][k] for k in m["window"])),
        "failed": int(verdict["compared"]["rows_differ"]["value"]),
        "metrics": metrics, "device": device,
    }
    if trace is not None:
        line["breakdown"] = trace["breakdown"]
    line["window"] = {
        "batches": len(m["window"]), "alert_rows": m["alert_rows"],
        "rows_compared": verdict["rows_compared"],
        "events_sent": run["sent"], "end_to_end": m["e2e"],
        "opened_by_batch": run["opened_by"], "reopened": run["reopened"],
        "backlog_open": m["backlog_open"], "backlog_close": m["backlog_close"],
        "send_late_p95_ms": window.percentile(m["send_late_ms"], 95)
        if len(m["send_late_ms"]) else None,
        "valid_rows": [m["rows"][k] for k in m["window"]],
        "sink_mtime_after_span_ms": verdict["sink_mtime_after_span_ms"],
    }
    line["compared"] = verdict["compared"]
    return line


def execute(name: str, seed: int, seconds: float, trace: bool,
            run_dir: Optional[str] = None,
            child_argv: Optional[List[str]] = None,
            require_tpu: bool = True, overrides: Optional[dict] = None):
    """Drive one run and measure it: (cell, run, measurements).
    ``run_dir``, ``child_argv``, ``require_tpu`` and ``overrides`` (of
    the mix's ``traffic`` and ``warmup``) are for the tests, the sweep
    and the control, which run a cell at a size a CPU can hold, at
    another rate or with a part replaced; the command the driver runs
    sets none of them."""
    cell = load_cell(name)
    for part, values in (overrides or {}).items():
        cell["mix"][part].update(values)
    run_dir = run_dir or os.path.join(ROOT, ".bench_runs", name)
    run = drive(cell, seed, seconds, trace, run_dir, child_argv)
    check_child(run, cell, require_tpu)
    return cell, run, measure(run, cell, seconds)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             **how) -> dict:
    cell, run, m = execute(name, seed, seconds, trace, **how)
    reduced = None
    if trace:
        from benchmark import trace as trace_mod

        try:
            reduced = trace_mod.reduce_in_helper(
                run["profile"]["path"], run, m)
        except (RuntimeError, FileNotFoundError) as e:
            raise BenchFailure(f"trace: {e}") from e
    verdict = decide(run, cell, m)
    line = result_line(cell, run, m, verdict, reduced)
    report(verdict)
    return line


def report(verdict: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for note in verdict["notes"]:
        print(f"differs: {note}", file=sys.stderr)
    for n, c in verdict["compared"].items():
        print(f"compared {n}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except BenchFailure as e:
        print(f"benchmark run has no result: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
