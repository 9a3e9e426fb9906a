"""From what the host recorded to the end-to-end numbers: which batches
landed inside the measured window, the rate over it, and every alert
row's latency from the moment its source event was due."""

from typing import Dict, List, Sequence

import numpy as np


def batch_bounds(rows: Sequence[int]) -> np.ndarray:
    """Batch k consumed events [bounds[k], bounds[k+1]) of the stream:
    an event maps to its batch by stream order and the valid-row counts
    the host recorded."""
    return np.concatenate([[0], np.cumsum(rows)]).astype(np.int64)


def in_window(landed_at: Sequence[float], opened_at: float,
              seconds: float) -> List[int]:
    """Batches whose results were there for a reader inside the window
    (opened_at, opened_at + seconds]. The window opens at a landing, so
    the first batch counted is the one after it."""
    return [k for k, t in enumerate(landed_at)
            if opened_at < t <= opened_at + seconds]


def events_per_s(rows: Sequence[int], landed_at: Sequence[float],
                 opened_at: float, window: Sequence[int]) -> float:
    """All events whose results reached the sinks in the window, over
    all its time: from the landing that opened it to the last landing
    inside it (both edges are landings, so no batch is cut in two)."""
    if not window:
        return 0.0
    span = landed_at[window[-1]] - opened_at
    return float(sum(rows[k] for k in window)) / span


def alert_latencies_ms(due_s: np.ndarray, alerts: Dict[int, np.ndarray],
                       landed_at: Sequence[float]) -> np.ndarray:
    """``alerts``: batch -> stream indices of the events that landed an
    alert row in it; ``due_s[i]``: when event i was due to be sent. One
    latency a row: the moment its batch's sink files were there, less
    the moment its event was due."""
    parts = [(landed_at[k] - due_s[idx]) * 1000.0
             for k, idx in sorted(alerts.items())]
    return np.concatenate(parts) if parts else np.zeros(0)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all values (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))
