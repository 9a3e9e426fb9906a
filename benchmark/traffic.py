"""The one traffic generator: events from the seed, rendered just ahead of
their send, on the schedule a mix's data file gives. Two modes:

- ``paced`` (open loop): ``rate_events_per_s`` in chunks of ``chunk_ms``,
  due at ``origin + k * chunk_ms`` whether or not the host keeps up. An
  optional ``profile`` ``[[seconds, factor], ...]`` repeats and scales the
  rate (bursts); chunk sizes follow its integral, so the mean holds.
- ``backlog`` (a source that always has more, as a broker with lag):
  every ``chunk_ms`` tops events sent up to ``ahead_widths`` declared
  widths over events consumed, at most ``max_chunk_events`` a tick. An
  event's due time is then its send time: latency from it says nothing.

Events come in blocks of ``BLOCK`` from ``default_rng([seed, block])``, so
the stream depends on the seed alone, not on the chunking."""

from typing import Dict, List

import numpy as np

BLOCK = 16_384


class EventStream:
    """Events of one run, generated block by block as the feeder needs
    them and kept for the reference. ``due_ms`` is filled at render time."""

    def __init__(self, flow, seed: int):
        self.flow = flow
        self.seed = seed
        self._blocks: List[Dict[str, np.ndarray]] = []

    def _block(self, b: int) -> Dict[str, np.ndarray]:
        while len(self._blocks) <= b:
            at = len(self._blocks)
            ev = self.flow.make_events(
                np.random.SeedSequence([self.seed, at]), BLOCK, at * BLOCK)
            ev["due_ms"] = np.zeros(BLOCK, np.int64)
            self._blocks.append(ev)
        return self._blocks[b]

    def render(self, lo: int, hi: int, due_s: float) -> bytes:
        parts = []
        while lo < hi:
            b, off = divmod(lo, BLOCK)
            n = min(hi - lo, BLOCK - off)
            ev = self._block(b)
            ev["due_ms"][off:off + n] = int(due_s * 1000.0)
            parts.append(self.flow.lines(ev, off, off + n))
            lo += n
        return b"".join(parts)

    def events(self, n: int) -> Dict[str, np.ndarray]:
        """The first ``n`` events as whole arrays, for the reference."""
        blocks = self._blocks[:-(-n // BLOCK)] if n else []
        if not blocks:
            return {}
        return {k: np.concatenate([b[k] for b in blocks])[:n]
                for k in blocks[0]}


class Schedule:
    """Chunk k of a paced mix: how many events, due when (seconds after
    the origin)."""

    def __init__(self, traffic: dict):
        self.dt = traffic["chunk_ms"] / 1000.0
        rate = float(traffic["rate_events_per_s"])
        profile = traffic.get("profile") or [[1.0, 1.0]]
        # cumulative events at the end of every chunk of one period
        factors = np.concatenate([
            np.full(int(round(sec / self.dt)), f) for sec, f in profile])
        self._cum = np.round(np.cumsum(factors * rate * self.dt)).astype(
            np.int64)
        self.steady = len(profile) == 1
        self.rate = rate

    def lo(self, k: int) -> int:
        """Events due before chunk k."""
        period, at = divmod(k, len(self._cum))
        return int(period * self._cum[-1] + (self._cum[at - 1] if at else 0))

    def due(self, k: int) -> float:
        return k * self.dt
