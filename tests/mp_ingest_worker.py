"""Worker for the REAL multi-process ingest test (run by
test_dist_multiprocess.py, once per simulated host).

Each process initializes jax.distributed (gloo over TCP — the DCN
stand-in), consumes ITS OWN partitions/rows per HostIngestPlan,
assembles the global sharded batch without cross-host data movement,
and runs a jitted cross-shard aggregation whose result must include the
OTHER host's rows — proving the collective path, not just the plan
arithmetic. Device count per process is environment-dependent (an
ambient XLA_FLAGS may pin xla_force_host_platform_device_count), so
shapes derive from the actual global device count.
"""

import json
import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import jax  # noqa: E402

jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from data_accelerator_tpu.dist import HostIngestPlan, make_mesh  # noqa: E402
from data_accelerator_tpu.dist.mesh import replicated  # noqa: E402

mesh = make_mesh()  # all global devices across both processes
n_global = len(jax.devices())
n_local = len(jax.local_devices())
assert n_global == 2 * n_local, (n_global, n_local)

rows_per_device = 2
cap = n_global * rows_per_device
plan = HostIngestPlan(mesh, global_capacity=cap, n_partitions=4, max_rate=8000)
assert plan.partitions == [p for p in range(4) if p % 2 == pid], plan.partitions
assert plan.local_capacity == n_local * rows_per_device, plan.local_capacity
assert plan.max_rate == 4000.0

# "ingest" this host's slice only: distinct ids/temps per host
n_rows = plan.local_capacity
ids = np.array([pid * 100 + i for i in range(n_rows)], np.int32)
temps = np.full(n_rows, 10.0 * (pid + 1), np.float32)
table = plan.make_global(
    {"deviceId": ids, "temperature": temps}, np.ones(n_rows, bool)
)

rep = replicated(mesh)


@jax.jit
def agg(cols, valid):
    s = jnp.sum(jnp.where(valid, cols["temperature"], 0.0))
    mx = jnp.max(jnp.where(valid, cols["deviceId"], -1))
    return (
        jax.lax.with_sharding_constraint(s, rep),
        jax.lax.with_sharding_constraint(mx, rep),
    )


s, mx = agg(table.cols, table.valid)
print(json.dumps({
    "pid": pid,
    "rows_per_host": n_rows,
    "sum": float(np.asarray(jax.device_get(s))),
    "max": int(np.asarray(jax.device_get(mx))),
}), flush=True)
