"""The system under test, from a client's side: writing a deployment's
conf, starting ``python -m data_accelerator_tpu.runtime.host`` as a child,
and reading back what a client can see: the child's flight recorder, its
sink files, its committed checkpoint. Nothing here imports jax or the
package (a parent that has touched jax holds the chip its child needs).
Copied from ``chip_smoke.py`` (PR 21) and made to follow a live run."""

import glob
import json
import os
import re
import shutil
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# valid rows a batch took from the source
INPUT_ROWS = "Input_DataXProcessedInput_Events_Count"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_conf(run_dir: str, config: dict, capacity: int, port: int,
               obs_port: Optional[int]) -> str:
    """Conf, schema and transform of one run, in a directory emptied
    first: what an earlier run left there the host would take for its
    own past (a checkpoint to resume from, a recorder to append to,
    sink files of batches this run never saw)."""
    if os.path.isdir(run_dir):
        shutil.rmtree(run_dir)
    os.makedirs(run_dir)
    schema_path = os.path.join(run_dir, "input.schema.json")
    transform_path = os.path.join(run_dir, "flow.transform")
    with open(schema_path, "w", encoding="utf-8") as f:
        json.dump(config["schema"], f)
    with open(transform_path, "w", encoding="utf-8") as f:
        f.write(config["transform"])
    conf = {
        "datax.job.name": config["job_name"],
        "datax.job.input.default.inputtype": "socket",
        "datax.job.input.default.socket.port": str(port),
        "datax.job.input.default.blobschemafile": schema_path,
        "datax.job.input.default.streaming.intervalinseconds":
            str(config["interval_s"]),
        # the rate a deployment of this width declares: one full batch
        # per interval
        "datax.job.input.default.eventhub.maxrate":
            str(capacity // config["interval_s"]),
        "datax.job.input.default.eventhub.checkpointdir":
            os.path.join(run_dir, "checkpoint"),
        "datax.job.input.default.eventhub.checkpointinterval":
            config["checkpoint_interval"],
        "datax.job.process.batchcapacity": str(capacity),
        "datax.job.process.watermark": config["guarantees"]["watermark"],
        "datax.job.process.transform": transform_path,
        "datax.job.process.telemetry.tracefile":
            os.path.join(run_dir, "telemetry.jsonl"),
    }
    for out in config["outputs"]:
        conf[f"datax.job.output.{out}.file.path"] = os.path.join(
            run_dir, "out", out)
        conf[f"datax.job.output.{out}.file.compressiontype"] = "none"
    conf.update(config["conf"])
    if obs_port is not None:
        conf["datax.job.process.observability.port"] = str(obs_port)
    path = os.path.join(run_dir, "flow.conf")
    with open(path, "w", encoding="utf-8") as f:
        for k, v in conf.items():
            f.write(f"{k}={v}\n")
    return path


def spawn_host(run_dir: str, conf_path: str, batches: int,
               argv: Optional[List[str]] = None) -> subprocess.Popen:
    """The entry a job client deploys. The child inherits this process's
    environment untouched: which platform it runs on is jax's decision
    there, read back from the child's own record. ``argv`` replaces the
    interpreter and module (a test's broken host)."""
    argv = argv or [sys.executable, "-m", "data_accelerator_tpu.runtime.host"]
    with open(os.path.join(run_dir, "host.log"), "wb") as log:
        return subprocess.Popen(
            argv + [f"conf={conf_path}", f"batches={batches}"],
            cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
        )


class Recorder:
    """The child's flight recorder, followed as it grows (the recorder
    appends and closes its file per record). Keeps the device report,
    every landed batch in order (time, metrics, the ``ts`` of its end
    event) and every span of every batch's trace."""

    def __init__(self, run_dir: str):
        self.path = os.path.join(run_dir, "telemetry.jsonl")
        self._at = 0
        self._rest = b""
        self.device: Optional[dict] = None
        self.batches: List[Tuple[int, Dict[str, float], float]] = []
        self.rows: List[int] = []  # valid rows of every landed batch
        self.exceptions: List[str] = []
        self._spans: Dict[str, Dict[str, Tuple[float, float]]] = {}
        self._trace_of: Dict[int, str] = {}

    def poll(self) -> int:
        """Read what was appended; returns how many batches landed."""
        try:
            with open(self.path, "rb") as f:
                f.seek(self._at)
                data = f.read()
        except FileNotFoundError:
            return 0
        self._at += len(data)
        *whole, self._rest = (self._rest + data).split(b"\n")
        landed = 0
        for line in whole:
            rec = json.loads(line)
            if rec.get("type") == "exception":
                self.exceptions.append(rec.get("error", ""))
            elif rec.get("type") == "span":
                self._spans.setdefault(rec["trace"], {})[rec["name"]] = (
                    float(rec["startTs"]), float(rec["durationMs"]))
                if rec["name"] == "streaming/batch":
                    self._trace_of[int(rec["properties"]["batchTime"])] = \
                        rec["trace"]
            elif rec.get("name") == "host/devices":
                self.device = rec["properties"]
            elif rec.get("name") == "streaming/batch/end":
                self.batches.append((
                    int(rec["properties"]["batchTime"]),
                    rec["measurements"], float(rec["ts"]),
                ))
                self.rows.append(int(rec["measurements"].get(INPUT_ROWS, 0)))
                landed += 1
        return landed

    def settle(self) -> None:
        """After the child has ended: read the rest, and drop the newest
        batches it was ended in the middle of recording (an end event and
        no root span yet). They landed after the drain and took no event."""
        self.poll()
        while self.batches and not self.spans(self.batches[-1][0]):
            self.batches.pop()
            self.rows.pop()

    def spans(self, batch_time: int) -> Dict[str, Tuple[float, float]]:
        """name -> (start s, duration ms) of one batch's spans."""
        return self._spans.get(self._trace_of.get(batch_time, ""), {})


_KEY = re.compile(rb'"([^"]+)":')


def parse_rows(data: bytes, names) -> Dict[str, np.ndarray]:
    """One sink file (a JSON object a line) as one array a column. Files
    of numbers alone, with the same keys in every row (both flows'), are
    read in bulk: keys and punctuation blanked out, the numbers parsed in
    one go. Anything else goes through ``json``."""
    first = data[:data.find(b"\n") + 1] or data
    keys = [k.decode() for k in _KEY.findall(first)]
    bare = data
    for k in keys:
        bare = bare.replace(b'"%s":' % k.encode(), b" ")
    if keys and b'"' not in bare:
        flat = np.fromstring(bare.translate(None, b"{},"), np.float64, sep=" ")
        if len(flat) == data.count(b"\n") * len(keys):
            table = flat.reshape(-1, len(keys))
            return {c: table[:, keys.index(c)] for c in names}
    rows = [json.loads(line) for line in data.splitlines() if line.strip()]
    return {c: np.array([r[c] for r in rows]) for c in names}


def read_sink(run_dir: str, dataset: str, names
              ) -> Dict[int, Tuple[Dict[str, np.ndarray], float]]:
    """Sink files of one dataset -> batch time ms -> (its rows as one
    array a column of ``names``, the newest mtime of its files). The
    file sink names each file ``<dataset>_<batch ms>_<n>.json``."""
    out: Dict[int, Tuple[Dict[str, np.ndarray], float]] = {}
    pattern = os.path.join(run_dir, "out", dataset, "**", f"{dataset}_*.json")
    for path in sorted(glob.glob(pattern, recursive=True)):
        t = int(re.search(rf"{dataset}_(\d+)_\d+\.json$", path).group(1))
        with open(path, "rb") as f:
            cols = parse_rows(f.read(), names)
        if t in out:
            had, mtime = out[t]
            cols = {c: np.concatenate([had[c], cols[c]]) for c in names}
            out[t] = (cols, max(mtime, os.path.getmtime(path)))
        else:
            out[t] = (cols, os.path.getmtime(path))
    return out


def read_checkpoint(run_dir: str) -> Tuple[Optional[int], int]:
    """(the committed offset, bytes of the window snapshot). The
    snapshot (the whole ring) is then deleted: runs write little."""
    ckpt = os.path.join(run_dir, "checkpoint")
    until = None
    offsets = os.path.join(ckpt, "offsets.txt")
    if os.path.exists(offsets):
        with open(offsets, encoding="utf-8") as f:
            until = int(f.readline().strip().split(",")[-1])
    window = os.path.join(ckpt, "window.npz")
    size = os.path.getsize(window) if os.path.exists(window) else 0
    for path in (window, window + ".old"):
        if os.path.exists(path):
            os.remove(path)
    return until, size
