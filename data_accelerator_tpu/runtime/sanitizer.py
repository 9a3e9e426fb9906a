"""Buffer sanitizer — the DYNAMIC half of the DX8xx buffer-lifetime
story (``analysis/racecheck.py`` is the static half).

The bug class (PRs 8/13/14 each found one): on the CPU backend
``jnp.asarray``/``np.asarray`` of a 64-byte-aligned buffer is a
zero-copy VIEW. The engine deliberately exploits that for ingest (the
``PackedBufferPool`` matrices are donated straight into the step), so a
view that outlives its buffer's donation/release reads freed-for-reuse
memory — silent corruption on a good day, a segfault on a bad one.

AddressSanitizer-style defense, adapted to what can be safely written:

* **Pool slots** are poisoned with a sentinel pattern the moment they
  are released (``PackedBufferPool.release`` calls ``poison`` when a
  sanitizer is attached). The pool owns a released matrix — nobody may
  legitimately read it — so any sentinel that later surfaces in a sink
  payload or checkpoint is a use-after-release caught red-handed.
* **Donated ring buffers** cannot be poisoned: after donation the
  memory belongs to XLA (writing it would corrupt live device state —
  the very bug we hunt). They are guarded by ALIAS checks instead:
  ``check_snapshot`` asserts a window-state checkpoint shares no memory
  with the live rings (a real copy never does; the PR 13 bug — a
  dropped ``copy=True`` — trips it on the first checkpoint).
* **Sink payloads / checkpoints** are scanned for sentinel runs
  (``scan_table`` / ``check_snapshot``): >= ``MIN_RUN`` consecutive
  sentinel words is no plausible payload, it is a poisoned slot leaking
  through a zero-copy view.

Every hit becomes a runtime **DX805** event — drained by the host into
the flight recorder beside conformance drift — and bumps
``Sanitizer_PoisonHit_Count``; everything the sanitizer guarded bumps
``Sanitizer_GuardedViews_Count``. Armed via conf
``datax.job.process.debug.buffersanitizer`` (a debug mode: poisoning
costs one memset per released slot).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

import numpy as np

# 0x5A5A5A5A: the classic poison byte pattern (ASan uses 0xbe/0xbd
# regions; 'Z' bytes read obviously-wrong in both int32 and f32 views)
SENTINEL = np.int32(0x5A5A5A5A)
# a single sentinel word can occur in honest data; four consecutive
# words (16 bytes) cannot, outside astronomically unlucky payloads
MIN_RUN = 4


def _longest_sentinel_run(arr: np.ndarray) -> int:
    """Longest run of consecutive SENTINEL words in ``arr`` viewed as
    int32 (0 when the dtype is not 4-byte or nothing matches)."""
    try:
        a = np.ascontiguousarray(arr)
    except Exception:  # noqa: BLE001 — exotic array-likes never fail a scan
        return 0
    if a.dtype.itemsize != 4 or a.size < MIN_RUN:
        return 0
    flat = a.view(np.int32).ravel()
    idx = np.flatnonzero(flat == SENTINEL)
    if idx.size < MIN_RUN:
        return 0
    # split the match positions into consecutive runs
    breaks = np.flatnonzero(np.diff(idx) != 1)
    best = 0
    start = 0
    for b in list(breaks) + [idx.size - 1]:
        best = max(best, int(b - start + 1))
        start = b + 1
    return best


class BufferSanitizer:
    """Poison released pool slots; scan outputs/checkpoints for leaks.

    Thread-safe: poisoning happens on whatever thread releases a slot
    (dispatch or landing), scans run on the landing thread, and the
    host drains events/metrics at collect time.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.poison_count = 0       # slots poisoned (lifetime)
        self.guarded_views = 0      # buffers guarded: poisons + scans
        self.poison_hits = 0        # DX805s fired (lifetime)
        self._events: List[Dict[str, object]] = []
        self._hits_drained = 0
        self._guarded_drained = 0

    # -- the poisoning half (pool release hook) ---------------------------
    def poison(self, matrix: np.ndarray) -> None:
        """Overwrite a RELEASED pool matrix with the sentinel. Safe by
        ownership: the pool holds the only legitimate reference."""
        try:
            matrix.fill(SENTINEL)
        except (ValueError, AttributeError):
            return  # read-only or non-ndarray: nothing to guard
        with self._lock:
            self.poison_count += 1
            self.guarded_views += 1

    # -- the scanning half ------------------------------------------------
    def check_snapshot(
        self, snap: Dict[str, object], window_buffers: Dict[str, object],
    ) -> int:
        """Guard a ``snapshot_window_state`` result: every saved array
        must be a REAL copy (no shared memory with the live rings) and
        sentinel-free. Returns the number of new hits."""
        before = self.poison_hits
        rings = snap.get("rings", {}) if isinstance(snap, dict) else {}
        partials = snap.get("partials", {}) if isinstance(snap, dict) else {}
        # every saved array beside the live one it was copied from (None:
        # a row of a [slots, groups] partial, copied out by the device)
        pairs = []
        for table, saved in rings.items():
            live = window_buffers.get(table)
            for cname, a in saved.get("cols", {}).items():
                pairs.append((table, cname, a,
                              live.cols.get(cname) if live else None))
            pairs.append((table, "__valid__", saved.get("valid"),
                          live.valid if live else None))
        for view, saved in partials.items():
            live = window_buffers.get(view)
            for i, a in enumerate(saved.get("keys", ())):
                pairs.append((view, f"key{i}", a,
                              live.keys[i] if live else None))
            for field_ in ("used", "slot_ts", "slot_live", "slot_gen"):
                pairs.append((view, field_, saved.get(field_),
                              getattr(live, field_, None)))
            for pname, a in saved.get("parts", {}).items():
                pairs.append((view, pname, a,
                              live.parts.get(pname) if live else None))
        for table, cname, a, live_arr in pairs:
            if a is None:
                continue
            with self._lock:
                self.guarded_views += 1
            run = _longest_sentinel_run(a)
            if run >= MIN_RUN:
                self._record(
                    kind="sentinel-run", where="checkpoint",
                    table=table, column=cname, run=run,
                )
            if live_arr is None:
                continue
            try:
                # dx-race: allow-zero-copy read-only identity probe —
                # the view dies inside this call, nothing escapes
                aliased = np.shares_memory(a, np.asarray(live_arr))
            except Exception:  # noqa: BLE001 — non-CPU backends copy
                aliased = False
            if aliased:
                self._record(
                    kind="snapshot-alias", where="checkpoint",
                    table=table, column=cname, run=0,
                )
        return self.poison_hits - before

    def scan_table(self, name: str, table) -> int:
        """Scan one landed host output table (sink payload) for
        sentinel leakage. Returns the number of new hits."""
        before = self.poison_hits
        arrays = dict(getattr(table, "cols", {}) or {})
        valid = getattr(table, "valid", None)
        if valid is not None:
            arrays["__valid__"] = valid
        for cname, a in arrays.items():
            with self._lock:
                self.guarded_views += 1
            run = _longest_sentinel_run(np.asarray(a))
            if run >= MIN_RUN:
                self._record(
                    kind="sentinel-run", where="sink", table=name,
                    column=cname, run=run,
                )
        return self.poison_hits - before

    # -- event/metric drains (host collect cadence) -----------------------
    def _record(self, kind: str, where: str, table: str, column: str,
                run: int) -> None:
        with self._lock:
            self.poison_hits += 1
            self._events.append({
                "code": "DX805",
                "kind": kind,
                "where": where,
                "table": str(table),
                "column": str(column),
                "runLength": int(run),
                "message": (
                    f"DX805: {kind} in {where} table {table!r} column "
                    f"{column!r}"
                    + (f" ({run} sentinel words)" if run else "")
                    + " — a donated/pooled buffer view outlived its "
                    "buffer (use-after-release)"
                ),
            })

    def drain_events(self) -> List[Dict[str, object]]:
        """DX805 events since the last drain (flight-recorder feed)."""
        with self._lock:
            events, self._events = self._events, []
        return events

    def drain_metric_deltas(self) -> Dict[str, float]:
        """Sanitizer_* metric deltas since the last drain; hit count is
        only reported once nonzero (silence == health, like the other
        incident counters)."""
        with self._lock:
            hits = self.poison_hits - self._hits_drained
            self._hits_drained = self.poison_hits
            guarded = self.guarded_views - self._guarded_drained
            self._guarded_drained = self.guarded_views
        out: Dict[str, float] = {}
        if guarded:
            out["Sanitizer_GuardedViews_Count"] = float(guarded)
        if hits:
            out["Sanitizer_PoisonHit_Count"] = float(hits)
        return out


def from_conf(dbg_conf) -> Optional[BufferSanitizer]:
    """``datax.job.process.debug.buffersanitizer=true`` arms the
    sanitizer (``dbg_conf`` is the ``debug.`` sub-dictionary)."""
    # dx-conf: read debug.buffersanitizer default=false
    flag = (dbg_conf.get_or_else("buffersanitizer", "false") or "").lower()
    return BufferSanitizer() if flag == "true" else None
