"""On-demand jax profiler surface: ``POST /profile?seconds=N``.

Replaces the old first-N-batches trace dump (a conf key you had to set
BEFORE starting the host, which is never when the mystery happens):
a live host now arms ``jax.profiler`` on demand through its
observability port, captures for N seconds while batches keep flowing,
and the capture lands beside the flight recorder —

- ``POST <host>/profile?seconds=N`` (obs/exposition.py) starts a
  capture and returns its path immediately; a timer thread stops the
  trace when the window closes.
- a capture holds the device planes and the host's ``dx/<span>``
  annotations (obs/tracing.py), NOT Python frames: jax's Python tracer
  is all but 0.1 % of a capture's bytes, slows the traced host's Python
  and stalls the loop for seconds while the file is written.
  ``&python=1`` (CLI ``--python``) turns it back on for the operator
  who wants the frames and pays for them.
- every finished capture is drained by the streaming host at the next
  batch finish and recorded as a ``profiler/capture`` span inside that
  batch's trace (so ``obs trace <batch>`` shows exactly which capture
  overlapped which batches) and counted by the
  ``Profiler_Captures_Count`` registry series.
- ``python -m data_accelerator_tpu.obs profile <url>`` drives it from
  a terminal; captures open in tensorboard/xprof.

Profiling is diagnostics, never load-bearing: a capture that fails to
start or stop is reported to the caller and logged, and the batch loop
carries on.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

DEFAULT_SECONDS = 5.0
MAX_SECONDS = 120.0


class ProfilerSurface:
    """One host's on-demand capture state: at most one trace at a time,
    a timer to close the window, and a drain queue of finished captures
    for the host to stitch into batch traces."""

    def __init__(self, base_dir: str, flow: str = ""):
        self.base_dir = base_dir
        self.flow = flow
        self.captures_count = 0
        self._seq = 0
        self._active: Optional[dict] = None
        self._timer: Optional[threading.Timer] = None
        self._finished: List[Dict] = []
        self._lock = threading.Lock()

    def active(self) -> Optional[dict]:
        with self._lock:
            return dict(self._active) if self._active else None

    def start(self, seconds: float = DEFAULT_SECONDS,
              python: bool = False) -> dict:
        """Arm a capture for ``seconds``; returns
        ``{path, seconds, active}`` or ``{error}`` (already capturing /
        start failed). The path is returned immediately so the caller
        can watch it fill. ``python``: also trace Python frames."""
        seconds = min(max(float(seconds), 0.1), MAX_SECONDS)
        with self._lock:
            if self._active is not None:
                return {
                    "error": "capture already in progress",
                    "path": self._active["path"],
                }
            self._seq += 1
            path = os.path.join(
                self.base_dir, f"capture-{self._seq:04d}"
            )
            os.makedirs(path, exist_ok=True)
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python else 0
            options.host_tracer_level = 2
            try:
                jax.profiler.start_trace(path, profiler_options=options)
            except Exception as e:  # noqa: BLE001 — diagnostics only
                logger.warning("profiler start failed: %s", e)
                return {"error": f"profiler start failed: {e}"}
            self._active = {
                "path": path,
                "seconds": seconds,
                "python": bool(python),
                "startedTs": time.time(),
            }
            self._timer = threading.Timer(seconds, self._stop_timed)
            self._timer.daemon = True
            self._timer.start()
            logger.info(
                "profiler capture armed for %.1fs -> %s", seconds, path
            )
            return {"path": path, "seconds": seconds,
                    "python": bool(python), "active": True}

    def _stop_timed(self) -> None:
        try:
            self.stop()
        except Exception:  # noqa: BLE001 — timer thread must not die loud
            logger.exception("timed profiler stop failed")

    def stop(self) -> Optional[str]:
        """Close the active capture (idempotent); returns its path."""
        with self._lock:
            active, self._active = self._active, None
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
        if active is None:
            return None
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — capture may be torn
            logger.warning("profiler stop failed: %s", e)
        active["durationMs"] = round(
            (time.time() - active["startedTs"]) * 1000.0, 1
        )
        with self._lock:
            self.captures_count += 1
            self._finished.append(active)
        logger.info("profiler capture written to %s", active["path"])
        return active["path"]

    def drain_finished(self) -> List[Dict]:
        """Captures completed since the last drain — the host records
        each as a ``profiler/capture`` span event on the batch trace
        that drains it."""
        with self._lock:
            out, self._finished = self._finished, []
            return out
