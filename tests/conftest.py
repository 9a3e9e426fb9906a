"""Test harness: run JAX on a virtual 8-device CPU mesh.

Must set the env vars before the first ``import jax`` anywhere in the test
process so sharding tests can exercise real multi-device code paths without
TPU hardware. x64 is deliberately left OFF to match TPU numerics (the
framework keeps device time columns as int32 millis relative to a host-side
batch base instead of int64 epochs).
"""

import os

# importing the resolver pulls in no JAX (compile/aotcache.py imports it
# lazily), so the env below is still set before the first ``import jax``
from data_accelerator_tpu.compile.aotcache import (  # noqa: E402
    CACHE_DIR_ENV,
    resolve_cache_dir,
)

# force CPU even when the ambient env names an accelerator platform;
# tests always use the virtual mesh
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"
# arm JAX's persistent cache from the first jit (not only from the first
# FlowProcessor) at the directory the engine itself resolves
os.environ.setdefault(CACHE_DIR_ENV, resolve_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: spawns real engine child processes"
    )
