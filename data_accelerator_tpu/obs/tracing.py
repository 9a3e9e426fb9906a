"""Batch-granular span tracing for the streaming/batch engines.

Every micro-batch gets a ``trace_id``; every stage the host runs on its
behalf (decode -> dispatch -> device step -> completion sync -> collect
-> per-sink writes -> checkpoint) becomes a ``span`` record carrying
``span_id``/``parent_id``, start timestamp and duration. Spans are
emitted through the existing ``TelemetryWriter`` fan-out
(obs/telemetry.py), so the JSONL flight recorder doubles as a trace log
a CLI can reconstruct: ``python -m data_accelerator_tpu.obs trace
<batch_id>`` rebuilds one batch's span tree.

reference: the AppInsights operation-correlation the reference gets for
free from DataX.Utilities.Telemetry (every ``streaming/batch/*`` event
shares an operation id); here the correlation is explicit and the store
is pluggable.

Design notes:
- Span boundaries are wall-clock host timestamps (``time.time`` for the
  epoch anchor, ``perf_counter`` for durations) — overhead is two clock
  reads and one dict per span; there is no per-row work.
- A thread-local *active trace* lets deep code (sinks, checkpointers,
  the processor's collect path) attach child spans without threading a
  context object through every signature: ``with tracing.span("x"):``
  is a no-op when no trace is active.
- Cross-thread stages (the pipelined decode-ahead worker) re-activate
  the batch's context explicitly via ``ctx.activate()``.
- Every finished span also feeds the per-stage latency histograms
  (obs/histogram.py) when the tracer holds a registry — spans and
  histograms cannot disagree because they share the one measurement.
- One clock: every span opened through ``_child`` is also a
  ``jax.profiler.TraceAnnotation`` named ``dx/<span name>`` for its
  duration, so an on-demand capture (obs/profiler.py) holds the host's
  stages on the same clock as the device's ``XLA Ops`` line. A TraceMe
  is a flag test while no capture runs. Spans whose two ends are seen
  at different call sites (``record``/``record_since``: ``device-step``,
  ``profiler/capture``, ``compile``) get none — the device's own line
  is their truth.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import struct
import sys
import threading
import time
from typing import Dict, Iterator, Optional

from .histogram import HistogramRegistry

_local = threading.local()

_trace_counter = itertools.count(1)


def _new_trace_id() -> str:
    """Unique, sortable-enough trace id: epoch-ms + 4 random bytes."""
    rnd = struct.unpack("<I", os.urandom(4))[0]
    return f"{int(time.time() * 1000):x}-{rnd:08x}"


def _span_prefix() -> str:
    """Random per-context span-id prefix, used when a context JOINS an
    existing trace (cross-process propagation): span ids are minted by
    a per-context counter, so two processes sharing one trace id need
    disjoint id spaces or their span ids collide."""
    return f"{struct.unpack('<I', os.urandom(4))[0]:08x}."


def format_parent(cap) -> Optional[str]:
    """Serialize a ``capture()`` as the ``<trace_id>:<span_id>`` string
    the ``datax.job.process.telemetry.parenttrace`` conf key carries
    across the process boundary (control plane -> spawned host)."""
    if cap is None:
        return None
    ctx, parent_id = cap
    return f"{ctx.trace_id}:{parent_id}"


def parse_parent(text: Optional[str]):
    """Inverse of ``format_parent``: ``(trace_id, span_id)`` or None."""
    if not text or ":" not in text:
        return None
    trace_id, _, span_id = text.rpartition(":")
    if not trace_id or not span_id:
        return None
    return trace_id, span_id


def annotation(name: str, **stats):
    """``dx/<name>`` on the profiler's host plane for the length of a
    ``with`` block; a no-op context where jax is not loaded (the
    control plane pins itself to CPU and must not pay the import: no
    capture can run in a process that never imported jax)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(profiler, "TraceAnnotation", None)
    if cls is None:
        return contextlib.nullcontext()
    return cls("dx/" + name, **stats)


def current_trace() -> Optional["TraceContext"]:
    """The trace active on THIS thread (None outside any batch)."""
    stack = getattr(_local, "stack", None)
    return stack[-1][0] if stack else None


def capture():
    """Opaque (trace, parent-span) capture of this thread's active
    position, for handing to a worker thread (the sink fan-out runs one
    thread per output operator)."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def activated(cap) -> Iterator[None]:
    """Re-activate a ``capture()`` on another thread; no-op for None."""
    if cap is None:
        yield
        return
    ctx, parent_id = cap
    with ctx.activate(parent_id=parent_id):
        yield


@contextlib.contextmanager
def span(name: str, **props) -> Iterator[None]:
    """Child span under the thread's active trace; no-op without one.

    The no-op path costs one attribute lookup — safe to leave in hot
    host code permanently (sinks, checkpoint, collect)."""
    stack = getattr(_local, "stack", None)
    if not stack:
        yield
        return
    ctx, parent_id = stack[-1]
    with ctx._child(name, parent_id, props):
        yield


def add(**props) -> None:
    """Attach properties to the innermost span open on THIS thread (a
    callee that learns a number its caller's span should carry: the
    bytes of a sink write); no-op outside any span."""
    stack = getattr(_local, "stack", None)
    if stack:
        ctx, span_id = stack[-1]
        open_span = ctx._open.get(span_id)
        if open_span is not None:
            open_span[0].update(props)


class TraceContext:
    """One batch's trace: a root span plus explicitly-parented children.

    With ``trace_id``/``parent_span_id`` the context JOINS an existing
    (possibly remote) trace instead of minting one: the root span keeps
    a parent pointer into the foreign trace and every span id carries a
    random per-context prefix so concurrent contexts — other batches of
    the same job, other processes — cannot collide inside the shared
    trace."""

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        props: Dict,
        trace_id: Optional[str] = None,
        parent_span_id: Optional[str] = None,
    ):
        self.tracer = tracer
        self.parent_span_id = parent_span_id
        if trace_id is not None:
            self.trace_id = trace_id
            prefix = _span_prefix()
        else:
            self.trace_id = _new_trace_id()
            prefix = ""
        self.root_span_id = prefix + "1"
        self._span_counter = (
            prefix + str(n) for n in itertools.count(2)
        )
        self._name = name
        self._props = dict(props)
        self._start_ts = time.time()
        self._start_pc = time.perf_counter()
        self._ended = False
        self._lock = threading.Lock()
        # named timestamps for spans whose endpoints are observed at
        # different call sites (e.g. device-step: dispatch return ->
        # completion sync)
        self.marks: Dict[str, tuple] = {}
        # numbers one stage measured that belong on the batch's end
        # event (the poll's Source_Backlog_Rows): the trace is what
        # travels with a batch from its poll to its tail
        self.counters: Dict[str, float] = {}
        # (properties, ``perf_counter`` at the start) of the child spans
        # still open, by span id (``add``, ``unspanned_ms``)
        self._open: Dict[str, tuple] = {}
        # ms the root's closed children took, by name (a span seen more
        # than once adds up)
        self.child_ms: Dict[str, float] = {}

    # -- root ------------------------------------------------------------
    def add(self, **props) -> None:
        """Attach properties to the root span (e.g. batchTime once the
        poll has determined it)."""
        self._props.update(props)

    def prop(self, name: str):
        """A property of the root span (None when it was never added)."""
        return self._props.get(name)

    def unspanned_ms(self, names) -> float:
        """What of the batch so far no span holds: the ms from the
        trace's begin to the start of the innermost span open on THIS
        thread (to now, where none is), less what the root's closed
        children ``names`` took. With every stage of the chain among
        ``names`` the rest is the time between spans. From what the
        trace holds: no clock is read while a span is open."""
        stack = getattr(_local, "stack", None)
        open_span = self._open.get(stack[-1][1]) \
            if stack and stack[-1][0] is self else None
        upto = open_span[1] if open_span else time.perf_counter()
        return (upto - self._start_pc) * 1000.0 - sum(
            self.child_ms.get(name, 0.0) for name in names
        )

    def end(self, **props) -> None:
        """Close the root span (idempotent — a retry path may race the
        normal close)."""
        with self._lock:
            if self._ended:
                return
            self._ended = True
        self._props.update(props)
        self.tracer._emit_span(
            self, self._name, self.root_span_id, self.parent_span_id,
            self._start_ts, (time.perf_counter() - self._start_pc) * 1000.0,
            self._props,
        )

    # -- children --------------------------------------------------------
    @contextlib.contextmanager
    def activate(self, parent_id: Optional[str] = None) -> Iterator["TraceContext"]:
        """Install as the thread's active trace (children created via the
        module-level ``span()`` parent onto the root — or onto
        ``parent_id`` when re-activating a captured position — or the
        innermost open span of THIS thread)."""
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append((self, parent_id or self.root_span_id))
        try:
            yield self
        finally:
            stack.pop()

    @contextlib.contextmanager
    def span(self, name: str, **props) -> Iterator[None]:
        """Explicit child of the root — usable from any thread without
        activation (the pipelined loop holds several batches at once)."""
        with self._child(name, self.root_span_id, props):
            yield

    def mark(self, name: str) -> None:
        """Remember 'now' under ``name`` (see ``record_since``)."""
        self.marks[name] = (time.time(), time.perf_counter())

    def record_since(self, name: str, mark: str, **props) -> None:
        """Emit a span from a prior ``mark()`` to now (no-op when the
        mark was never set)."""
        m = self.marks.get(mark)
        if m is None:
            return
        self.record(
            name, m[0], (time.perf_counter() - m[1]) * 1000.0, **props
        )

    def record(self, name: str, start_ts: float, duration_ms: float,
               **props) -> None:
        """A span whose boundaries were measured externally (e.g. the
        device-step interval between dispatch return and completion
        sync, whose endpoints the host observed at different places)."""
        self.child_ms[name] = self.child_ms.get(name, 0.0) + duration_ms
        self.tracer._emit_span(
            self, name, str(next(self._span_counter)), self.root_span_id,
            start_ts, duration_ms, props,
        )

    @contextlib.contextmanager
    def _child(self, name: str, parent_id: str, props: Dict) -> Iterator[None]:
        span_id = str(next(self._span_counter))
        start_ts = time.time()
        t0 = time.perf_counter()
        stack = getattr(_local, "stack", None)
        pushed = False
        if stack is not None and stack and stack[-1][0] is self:
            # nest further children under this span on the same thread
            stack.append((self, span_id))
            pushed = True
        self._open[span_id] = (props, t0)
        try:
            with annotation(
                name, batch=self._props.get("batchTime", self.trace_id)
            ):
                yield
        finally:
            if pushed:
                stack.pop()
            del self._open[span_id]
            duration_ms = (time.perf_counter() - t0) * 1000.0
            if parent_id == self.root_span_id:
                self.child_ms[name] = \
                    self.child_ms.get(name, 0.0) + duration_ms
            self.tracer._emit_span(
                self, name, span_id, parent_id, start_ts, duration_ms, props,
            )


class Tracer:
    """Factory for per-batch traces, bound to a flow's telemetry fan-out
    and (optionally) the per-stage histogram registry.

    ``parent``: a ``<trace_id>:<span_id>`` string (the
    ``datax.job.process.telemetry.parenttrace`` conf value) — every
    trace this tracer begins then JOINS that trace instead of minting
    its own, so a spawned host's batch spans root in the control-plane
    request that launched the job."""

    def __init__(
        self,
        telemetry=None,
        histograms: Optional[HistogramRegistry] = None,
        flow: str = "",
        enabled: bool = True,
        parent: Optional[str] = None,
    ):
        self.telemetry = telemetry
        self.histograms = histograms
        self.flow = flow
        self.enabled = enabled
        self.parent = parse_parent(parent)

    def begin(self, name: str = "streaming/batch", **props) -> TraceContext:
        if self.parent is not None:
            return TraceContext(
                self, name, props,
                trace_id=self.parent[0], parent_span_id=self.parent[1],
            )
        return TraceContext(self, name, props)

    def _emit_span(
        self, ctx: TraceContext, name: str, span_id: str,
        parent_id: Optional[str], start_ts: float, duration_ms: float,
        props: Dict,
    ) -> None:
        # histograms always observe (they are the live latency source
        # even when span emission is turned off); the root span's
        # "streaming/" prefix is stripped so its stage is "batch"
        if self.histograms is not None:
            stage = name[10:] if name.startswith("streaming/") else name
            # the span's trace id rides along as the histogram exemplar
            # (a latency spike links back to the batch that caused it)
            self.histograms.observe(
                self.flow, stage, duration_ms, trace_id=ctx.trace_id
            )
        if not self.enabled or self.telemetry is None:
            return
        self.telemetry.track_span(
            name,
            trace_id=ctx.trace_id,
            span_id=span_id,
            parent_id=parent_id,
            start_ts=start_ts,
            duration_ms=duration_ms,
            properties=props,
        )
