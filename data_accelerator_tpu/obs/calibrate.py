"""Machine-profile calibration: the time axis of the cost model.

The DX2xx/DX7xx closed forms predict *bytes and FLOPs*; turning those
into predicted *milliseconds* needs the machine constants of whatever
backend this process actually runs on — HBM stream bandwidth, dense
FLOP/s, the fixed per-dispatch overhead of one jitted call, D2H
transfer bandwidth and (under a mesh) per-link ICI bandwidth. This
module measures them once per process with tiny jit micro-probes
(~100 ms total on CPU, less on a real accelerator), so the roofline
latency model (``analysis/costmodel.py stage_time_ms``) and the DX52x
runtime conformance checks (``obs/conformance.py``) judge observations
against *this machine*, not a datasheet.

Probe design (each: warm once, take the best of a few reps — bandwidth
is a max, overhead a min, so best-of is the right estimator and is far
more run-to-run stable than a mean):

- **hbm read GB/s**: sum-reduce a large f32 array (reads N, writes ~0).
- **hbm write GB/s**: broadcast-fill the same shape (writes N, reads ~0).
- **flops GFLOP/s**: one square f32 matmul (2*n^3 FLOPs).
- **dispatch overhead µs**: a jitted scalar add, timed per blocking
  call — the fixed cost of getting ANY step onto the device and
  learning it finished (exactly what a host-observed stage time
  contains too).
- **d2h GB/s**: ``jax.device_get`` of the probe array.
- **ici GB/s**: a psum across local devices (absent on 1-device hosts;
  the field is None and ICI latency terms fall back to the DX7xx wire
  model's bytes with no time prediction).

The profile persists as JSON — locally (``calibrationfile``) and,
like the persistent compile cache, through the shared object store
(``calibrationurl``, an ``objstore://`` URL) so a fleet of hosts on
identical hardware calibrates once. A cached profile is only reused
for the same backend + device kind. Every field exports as a
``Calib_*`` registry series so dashboards can see the machine model
their roofline ratios are judged against.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from dataclasses import asdict, dataclass
from typing import Dict, Optional

logger = logging.getLogger(__name__)

# probe sizing: big enough to stream past caches on an accelerator,
# small enough that the whole calibration stays ~100 ms on CPU
PROBE_ELEMS = 1 << 20  # 4 MiB of f32
PROBE_MATMUL_N = 256
# best-of over enough reps to shrug off scheduler noise on a loaded
# host (bandwidth probes are single-digit ms; reps are cheap)
PROBE_REPS = 8
DISPATCH_REPS = 10

# the version stamp persisted profiles carry; bump when probe semantics
# change so stale cached profiles recalibrate instead of mispredicting
# (v2 added the host JSON-decode probe — decode_rows_per_sec)
PROFILE_VERSION = 2

# decode probe sizing: enough rows that per-call overhead vanishes,
# small enough to stay ~10 ms
DECODE_PROBE_ROWS = 20_000


@dataclass
class MachineProfile:
    """Measured machine constants the latency closed forms consume."""

    backend: str
    device_kind: str
    hbm_read_gbps: float
    hbm_write_gbps: float
    flops_gflops: float
    dispatch_overhead_us: float
    d2h_gbps: float
    ici_gbps: Optional[float] = None
    # measured native ingest-decode rate over the reference payload
    # (rows/s; None when the native library is unavailable) — prices
    # the latency model's host-decode term so DX520 can judge
    # stage_decode_ms
    decode_rows_per_sec: Optional[float] = None
    probe_ms: float = 0.0
    version: int = PROFILE_VERSION

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, obj: dict) -> Optional["MachineProfile"]:
        try:
            known = {f for f in cls.__dataclass_fields__}  # noqa: SLF001
            return cls(**{k: v for k, v in obj.items() if k in known})
        except (TypeError, ValueError):
            return None

    def metrics(self) -> Dict[str, float]:
        """The ``Calib_*`` registry series (constants.MetricName)."""
        out = {
            "Calib_HbmReadGBps": self.hbm_read_gbps,
            "Calib_HbmWriteGBps": self.hbm_write_gbps,
            "Calib_FlopsGFlops": self.flops_gflops,
            "Calib_DispatchOverheadUs": self.dispatch_overhead_us,
            "Calib_D2HGBps": self.d2h_gbps,
        }
        if self.ici_gbps is not None:
            out["Calib_IciGBps"] = self.ici_gbps
        if self.decode_rows_per_sec is not None:
            out["Calib_DecodeRowsPerSec"] = self.decode_rows_per_sec
        return out


def _best_seconds(fn, reps: int = PROBE_REPS) -> float:
    """Min wall time of ``fn()`` over ``reps`` runs (after the caller
    warmed it): the least-interfered-with sample."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-9)


def _probe_decode_rate() -> Optional[float]:
    """Measure the native ingest decoder on a reference IoT-shaped
    payload (nested object, string + numeric + timestamp columns) —
    rows/s on THIS host, the constant the latency model's decode term
    is priced with. None when the native library is unavailable (the
    decode prediction then stays silent, like a missing ICI link)."""
    try:
        import json

        from ..core.schema import Schema, StringDictionary
        from ..native import NativeDecoder, native_available

        if not native_available():
            return None
        schema = Schema.from_spark_json(json.dumps({
            "type": "struct",
            "fields": [
                {"name": "d", "type": {"type": "struct", "fields": [
                    {"name": "id", "type": "long", "nullable": False,
                     "metadata": {}},
                    {"name": "kind", "type": "string", "nullable": False,
                     "metadata": {}},
                    {"name": "value", "type": "double", "nullable": False,
                     "metadata": {}},
                ]}, "nullable": False, "metadata": {}},
                {"name": "ts", "type": "timestamp", "nullable": True,
                 "metadata": {}},
            ],
        }))
        n = DECODE_PROBE_ROWS
        payload = ("\n".join(
            '{"d":{"id":%d,"kind":"K%d","value":%d.%03d},"ts":%d}'
            % (i % 97, i % 7, i % 100, i % 1000, 1_700_000_000_000 + i)
            for i in range(n)
        ) + "\n").encode()
        dec = NativeDecoder(schema, StringDictionary())
        dec.decode(payload, n)  # warm (build/trie/dict)
        best = _best_seconds(lambda: dec.decode(payload, n), reps=3)
        return round(n / best, 1)
    except Exception as e:  # noqa: BLE001 — the decode term is optional
        logger.debug("decode-rate probe unavailable: %s", e)
        return None


def calibrate(device=None) -> MachineProfile:
    """Run the micro-probes against ``device`` (default: the first
    local device) and return a fresh profile."""
    import jax
    import jax.numpy as jnp

    t_start = time.perf_counter()
    devices = jax.local_devices()
    dev = device if device is not None else devices[0]
    backend = jax.default_backend()
    kind = getattr(dev, "device_kind", backend) or backend

    x = jax.device_put(
        jnp.linspace(0.0, 1.0, PROBE_ELEMS, dtype=jnp.float32), dev
    )
    nbytes = PROBE_ELEMS * 4

    # inputs are committed to `dev` by device_put, so each jitted probe
    # runs there without the deprecated jit(device=...) pin
    read_fn = jax.jit(lambda a: jnp.sum(a))
    write_fn = jax.jit(lambda s: jnp.full((PROBE_ELEMS,), s, jnp.float32))
    m = jax.device_put(
        jnp.ones((PROBE_MATMUL_N, PROBE_MATMUL_N), jnp.float32), dev
    )
    mm_fn = jax.jit(lambda a: a @ a)
    tiny = jax.device_put(jnp.float32(1.0), dev)
    tick_fn = jax.jit(lambda a: a + 1.0)

    # warm every probe (trace + compile happen here, not in the timing)
    read_fn(x).block_until_ready()
    write_fn(tiny).block_until_ready()
    mm_fn(m).block_until_ready()
    tick_fn(tiny).block_until_ready()
    jax.device_get(x)

    read_s = _best_seconds(lambda: read_fn(x).block_until_ready())
    write_s = _best_seconds(lambda: write_fn(tiny).block_until_ready())
    mm_s = _best_seconds(lambda: mm_fn(m).block_until_ready())
    d2h_s = _best_seconds(lambda: jax.device_get(x))

    def ticks():
        for _ in range(DISPATCH_REPS):
            tick_fn(tiny).block_until_ready()

    tick_s = _best_seconds(ticks) / DISPATCH_REPS

    ici_gbps: Optional[float] = None
    if len(devices) > 1:
        try:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            mesh = Mesh(devices, ("d",))
            sharded = jax.device_put(
                jnp.ones((len(devices), PROBE_ELEMS // 8), jnp.float32),
                NamedSharding(mesh, PartitionSpec("d")),
            )
            psum_fn = jax.jit(
                lambda a: jnp.broadcast_to(jnp.sum(a, axis=0), a.shape)
            )
            psum_fn(sharded).block_until_ready()
            psum_s = _best_seconds(
                lambda: psum_fn(sharded).block_until_ready()
            )
            # ring all-reduce wire bytes of the [cols]-sized result
            from ..analysis.costmodel import allreduce_wire_bytes

            wire = allreduce_wire_bytes(
                (PROBE_ELEMS // 8) * 4, len(devices)
            )
            ici_gbps = wire / psum_s / 1e9
        except Exception as e:  # noqa: BLE001 — ici term is optional
            logger.debug("ici probe unavailable: %s", e)

    # subtract the measured fixed dispatch cost from the bandwidth
    # probes so the handshake doesn't masquerade as low bandwidth
    def bw(nb: float, s: float) -> float:
        return nb / max(s - tick_s, 1e-9) / 1e9

    decode_rate = _probe_decode_rate()

    profile = MachineProfile(
        backend=backend,
        device_kind=str(kind),
        hbm_read_gbps=round(bw(nbytes, read_s), 3),
        hbm_write_gbps=round(bw(nbytes, write_s), 3),
        flops_gflops=round(
            2.0 * PROBE_MATMUL_N ** 3 / max(mm_s - tick_s, 1e-9) / 1e9, 3
        ),
        dispatch_overhead_us=round(tick_s * 1e6, 3),
        d2h_gbps=round(nbytes / d2h_s / 1e9, 3),
        ici_gbps=round(ici_gbps, 3) if ici_gbps else None,
        decode_rows_per_sec=decode_rate,
        probe_ms=round((time.perf_counter() - t_start) * 1000.0, 1),
    )
    logger.info("machine profile calibrated: %s", profile.to_dict())
    return profile


# a conservative static fallback for contexts that must not touch a
# device (the analyzers run under JAX_PLATFORMS=cpu with no probes):
# the latency model then reports with profileSource="default" so
# readers know the milliseconds are datasheet-shaped, not measured
DEFAULT_PROFILE = MachineProfile(
    backend="default",
    device_kind="v5e-datasheet",
    hbm_read_gbps=819.0,
    hbm_write_gbps=819.0,
    flops_gflops=197_000.0,  # bf16 dense peak; f32 runs lower
    dispatch_overhead_us=50.0,
    d2h_gbps=8.0,  # PCIe-ish host link
    ici_gbps=49.0,  # v5e per-link half-duplex
)


# -- persistence ------------------------------------------------------------
def _matches(profile: MachineProfile, backend: str, kind: str) -> bool:
    return (
        profile.version == PROFILE_VERSION
        and profile.backend == backend
        and profile.device_kind == kind
    )


def load_profile(path: str) -> Optional[MachineProfile]:
    try:
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
        return MachineProfile.from_dict(obj) if isinstance(obj, dict) else None
    except (OSError, ValueError):
        return None


def save_profile(profile: MachineProfile, path: str) -> None:
    parent = os.path.dirname(os.path.abspath(path))
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(profile.to_dict(), f, separators=(",", ":"))
    os.replace(tmp, path)


def _objstore_client(url: str):
    from ..compile.aotcache import _parse_objstore_url
    from ..serve.objectstore import ObjectStoreClient

    endpoint, bucket, prefix = _parse_objstore_url(url)
    token = os.environ.get("DATAX_OBJSTORE_TOKEN")
    return ObjectStoreClient(endpoint, bucket, token=token), prefix


def _share_key(prefix: str, backend: str, kind: str) -> str:
    safe_kind = "".join(
        c if c.isalnum() or c in "._-" else "_" for c in kind
    )
    key = f"machineprofile-{backend}-{safe_kind}.json"
    return f"{prefix}/{key}" if prefix else key


def pull_shared(url: str, backend: str, kind: str) -> Optional[MachineProfile]:
    """Fetch a peer's profile for this backend+device from the shared
    store; best-effort (a dead store just means we calibrate)."""
    try:
        client, prefix = _objstore_client(url)
        data = client.get(_share_key(prefix, backend, kind))
        if not data:
            return None
        obj = json.loads(data.decode("utf-8"))
        return MachineProfile.from_dict(obj) if isinstance(obj, dict) else None
    except Exception as e:  # noqa: BLE001 — shared layer is best-effort
        logger.warning("machine-profile pull failed: %s", e)
        return None


def push_shared(url: str, profile: MachineProfile) -> bool:
    """Publish this host's profile so identical peers skip calibration."""
    try:
        client, prefix = _objstore_client(url)
        client.put(
            _share_key(prefix, profile.backend, profile.device_kind),
            json.dumps(profile.to_dict(), separators=(",", ":")).encode(),
        )
        return True
    except Exception as e:  # noqa: BLE001 — best-effort
        logger.warning("machine-profile push failed: %s", e)
        return False


# -- the once-per-process entry point ---------------------------------------
_cache_lock = threading.Lock()
_cached: Optional[MachineProfile] = None


def get_profile(
    cache_file: Optional[str] = None,
    share_url: Optional[str] = None,
    force: bool = False,
) -> MachineProfile:
    """The profile for this process's backend: process-cached, then the
    local ``cache_file``, then the shared store, then live calibration
    (whose result is persisted back through both layers). ``force``
    skips every cache (the ``obs calibrate`` CLI's re-measure)."""
    global _cached
    import jax

    backend = jax.default_backend()
    kind = (
        getattr(jax.local_devices()[0], "device_kind", backend) or backend
    )
    with _cache_lock:
        if not force:
            if _cached is not None and _matches(_cached, backend, str(kind)):
                return _cached
            if cache_file:
                p = load_profile(cache_file)
                if p is not None and _matches(p, backend, str(kind)):
                    _cached = p
                    return p
            if share_url:
                p = pull_shared(share_url, backend, str(kind))
                if p is not None and _matches(p, backend, str(kind)):
                    _cached = p
                    if cache_file:
                        save_profile(p, cache_file)
                    return p
        profile = calibrate()
        _cached = profile
        if cache_file:
            try:
                save_profile(profile, cache_file)
            except OSError as e:
                logger.warning("machine-profile save failed: %s", e)
        if share_url:
            push_shared(share_url, profile)
        return profile
