"""Persistent XLA compilation cache, optionally shared via objstore://.

The runtime half of the zero-cold-start path: ``FlowProcessor._aot_warm``
compiles every manifest entry at init; with this cache the compiles
inside that warm (and any later first-dispatch compile) resolve from
serialized executables on disk — and, when a shared object store is
configured, newly compiled entries are pushed back so the NEXT start
(restart, preemption recovery, scale-out replica) deserializes instead
of compiling.

Layering:

- **local dir**: jax's own persistent compilation cache, at the ONE
  directory ``resolve_cache_dir`` names — ``JAX_COMPILATION_CACHE_DIR``
  when the operator set it (jax reads that variable itself; nothing
  here overrides it), else ``<checkout>/.jax_cache``. The directory is
  part of jax's cache key, so it must never move between starts: no
  conf key, temp name, pid or timestamp may place it. Tuned so every
  entry persists (no min-size/min-compile-time gating — a restart
  should never recompile something this process already paid for).
- **shared store** (``datax.job.process.compile.cacheurl``, an
  ``objstore://host:port/bucket/prefix`` URL): ``enable()`` pulls
  entries absent locally before arming the cache; ``push()`` uploads
  entries created since ``enable()``. Cache files are opaque bytes to
  us — jax names them by its own cache key (backend + jaxlib version +
  computation fingerprint), so a stale entry can never be *loaded*
  wrongly, only ignored.

``Compile_Cache_{Hit,Miss}_Count`` report jax's own cache events
(``/jax/compilation_cache/cache_hits`` / ``cache_misses``), counted
process-wide by one ``jax.monitoring`` listener. The same listener keeps
*which* program each was: jax times every backend compile (a load from
the cache included) as ``/jax/core/compile/backend_compile_duration``
with the function's name, and the hit or miss event fires inside that
interval on the same thread; ``take_programs`` hands them to the batch
that paid for them, which records one ``compile`` span each.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

logger = logging.getLogger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def resolve_cache_dir() -> str:
    """THE compile-cache directory of this process: where
    ``JAX_COMPILATION_CACHE_DIR`` is set, that; else
    ``<checkout>/.jax_cache`` (git-ignored)."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


class _CacheEvents:
    """Monotonic process-wide totals of jax's persistent-cache events.
    jax's listener registry is itself process-global and its events
    carry no attribution, so one listener counts for everyone and each
    ``PersistentCompileCache`` reports deltas against its own marks."""

    # programs remembered between two takes; a start-up's AOT warm
    # compiles a few dozen
    KEEP_PROGRAMS = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False
        self.hits = 0
        self.misses = 0
        self.programs_total = 0
        self._programs: deque = deque(maxlen=self.KEEP_PROGRAMS)
        # "hit" | "miss" of the compile this thread is inside
        self._compiling = threading.local()

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._compiling.cache = "hit"
            with self._lock:
                self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._compiling.cache = "miss"
            with self._lock:
                self.misses += 1

    def _on_time_span(
        self, event: str, start_time: float, end_time: float, **kwargs
    ) -> None:
        if event != "/jax/core/compile/backend_compile_duration":
            return
        program = {
            "fn": str(kwargs.get("fun_name", "")),
            "startTs": start_time,
            "ms": (end_time - start_time) * 1000.0,
            # None: jax did not consult the persistent cache for it
            "cache": getattr(self._compiling, "cache", None),
        }
        self._compiling.cache = None
        with self._lock:
            self.programs_total += 1
            self._programs.append(program)

    def programs_since(self, seen: int) -> Tuple[List[Dict], int]:
        """(programs compiled or loaded after the first ``seen``, the
        new total)."""
        with self._lock:
            fresh = min(self.programs_total - seen, len(self._programs))
            return list(self._programs)[len(self._programs) - fresh:], \
                self.programs_total

    def ensure_registered(self) -> None:
        with self._lock:
            if self._registered:
                return
            self._registered = True
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_time_span_listener(self._on_time_span)

    def totals(self) -> Tuple[int, int]:
        with self._lock:
            return self.hits, self.misses


_EVENTS = _CacheEvents()


def _parse_objstore_url(url: str) -> Tuple[str, str, str]:
    """objstore://host:port/bucket/prefix -> (endpoint, bucket, prefix)."""
    if url.startswith("objstore+https://"):
        scheme, rest = "https", url[len("objstore+https://"):]
    elif url.startswith("objstore://"):
        scheme, rest = "http", url[len("objstore://"):]
    else:
        raise ValueError(f"not an objstore URL: {url!r}")
    host, _, bucket_key = rest.partition("/")
    bucket, _, prefix = bucket_key.partition("/")
    if not bucket:
        raise ValueError(f"objstore URL needs a bucket: {url!r}")
    return f"{scheme}://{host}", bucket, prefix.strip("/")


class PersistentCompileCache:
    """One flow's compile-cache session: jax's cache at the resolved
    local dir + the optional shared objstore layer."""

    def __init__(self, cache_url: Optional[str] = None):
        self.url = cache_url
        self._client = None
        self._prefix = ""
        if cache_url:
            from ..serve.objectstore import ObjectStoreClient

            endpoint, bucket, prefix = _parse_objstore_url(cache_url)
            token = os.environ.get("DATAX_OBJSTORE_TOKEN")
            self._client = ObjectStoreClient(endpoint, bucket, token=token)
            self._prefix = prefix
        self.dir = resolve_cache_dir()
        self._baseline: Set[str] = set()
        self._seen = (0, 0)
        self._seen_programs = 0

    # -- local entries ---------------------------------------------------
    def _entries(self) -> List[str]:
        try:
            return sorted(
                fn for fn in os.listdir(self.dir)
                if not fn.endswith("-atime") and not fn.endswith(".tmp")
            )
        except OSError:
            return []

    # -- lifecycle -------------------------------------------------------
    def enable(self) -> None:
        """Pull shared entries, then arm jax's persistent cache at the
        resolved dir. Stays armed for the life of the process so later
        re-traces also persist."""
        os.makedirs(self.dir, exist_ok=True)
        self.pull()
        import jax
        from jax._src import compilation_cache

        if jax.config.jax_compilation_cache_dir != self.dir:
            # JAX_COMPILATION_CACHE_DIR was not in jax's environment at
            # import (jax reads the variable itself, and then this
            # branch is skipped). jax memoizes "no cache" at its first
            # compile; drop that so a dir set after earlier jits takes
            # effect.
            jax.config.update("jax_compilation_cache_dir", self.dir)
            compilation_cache.reset_cache()
        # the executable carries the op metadata a device trace is read
        # by (the step's ``dx.<stage>`` scopes, source lines). jax leaves
        # metadata out of the key by default; a start would then load an
        # executable compiled before a scope was added or renamed, and
        # the trace would show the old names
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        # ...and an operation's location is its own source line under its
        # scope, not the call stack above it: the key must not move with
        # the line a host was constructed on. (The frame limit, not
        # jax_include_full_tracebacks_in_locations=False: with that the
        # TPU compiler drops the scope from op_name.)
        jax.config.update("jax_traceback_in_locations_limit", 1)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _EVENTS.ensure_registered()
        self._seen = _EVENTS.totals()
        self._seen_programs = _EVENTS.programs_total
        self._baseline = set(self._entries())

    def take_counts(self) -> Tuple[int, int]:
        """(hits, misses) jax's persistent cache recorded in this
        process since ``enable()`` or the previous take — a hit is a
        compile served from disk, a miss one compiled and written."""
        hits, misses = _EVENTS.totals()
        seen_h, seen_m = self._seen
        self._seen = (hits, misses)
        return hits - seen_h, misses - seen_m

    def take_programs(self) -> List[Dict]:
        """``{fn, startTs, ms, cache}`` of every program jax compiled
        (``cache`` "miss") or loaded from the persistent cache ("hit")
        in this process since ``enable()`` or the previous take."""
        programs, self._seen_programs = _EVENTS.programs_since(
            self._seen_programs
        )
        return programs

    # -- shared layer ----------------------------------------------------
    def _key(self, fn: str) -> str:
        return f"{self._prefix}/{fn}" if self._prefix else fn

    def pull(self) -> int:
        """Download shared entries absent locally. FAIL-OPEN: the
        client retries transient failures with bounded jittered backoff
        (serve/objectstore.py), and whatever still fails degrades to
        the local-only cache — a cold compile beats a dead host. Each
        entry fails independently so one bad object can't abort the
        rest of the pull (contrast the state-snapshot store, which is
        fail-closed: runtime/statepartition.py)."""
        if self._client is None:
            return 0
        n = 0
        try:
            have = set(self._entries())
            keys = self._client.list(self._prefix)
        except Exception as e:  # noqa: BLE001 — shared layer is best-effort
            logger.warning("compile-cache pull failed: %s", e)
            return 0
        for key in keys:
            fn = key.rsplit("/", 1)[-1]
            if fn in have or fn.endswith("-atime"):
                continue
            try:
                data = self._client.get(key)
                if data is None:
                    continue
                path = os.path.join(self.dir, fn)
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
                n += 1
            except Exception as e:  # noqa: BLE001 — best-effort per entry
                logger.warning("compile-cache pull %s failed: %s", fn, e)
        return n

    def push(self) -> int:
        """Upload entries created since ``enable()`` or the last push
        (the compiles this process actually paid for); returns how
        many were uploaded."""
        if self._client is None:
            return 0
        new = [fn for fn in self._entries() if fn not in self._baseline]
        for fn in new:
            try:
                with open(os.path.join(self.dir, fn), "rb") as f:
                    self._client.put(self._key(fn), f.read())
            except Exception as e:  # noqa: BLE001 — best-effort
                logger.warning("compile-cache push %s failed: %s", fn, e)
        self._baseline |= set(new)
        return len(new)
