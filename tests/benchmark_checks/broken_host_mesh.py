"""The served host under a mesh with the one fault a one-chip cell cannot
have, for test_benchmark_mesh.py: ``python broken_host_mesh.py conf=...``
leaves the exchange between the chips out of the step, then runs the
host's own ``main()``. ``HeatAvg`` is a GROUP BY over the whole ring, whose
rows lie sharded over the chips: without the exchange a chip sees the
groups of its own row shard alone, and that partial answer lands as if it
were whole. The benchmark's comparison has to see it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def exchange_left_out():
    """``HeatAvg`` as the first chip computes it from its own shard of the
    batch and of the ring; everything else as the step gives it."""
    import jax
    import jax.numpy as jnp
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    dispatch = FlowProcessor.dispatch_batch

    def broken(self, raw, batch_time_ms=None):
        if not getattr(self, "_step_broken", False):
            step, chips = self._step, self.mesh.size
            alone = jax.jit(self._step_fn)  # donates nothing, gathers nothing

            def own_shard(t):
                rows = t.valid.shape[-1]
                return type(t)(t.cols,
                               t.valid & (jnp.arange(rows) < rows // chips))

            def left_out(raw_, rings, *rest):
                partial = alone({n: own_shard(t) for n, t in raw_.items()},
                                {n: own_shard(r) for n, r in rings.items()},
                                *rest)[0]
                out, new_rings, state, counts = step(raw_, rings, *rest)
                return (dict(out, HeatAvg=partial["HeatAvg"]), new_rings,
                        state, counts)

            self._step, self._step_broken = left_out, True
        return dispatch(self, raw, batch_time_ms)

    FlowProcessor.dispatch_batch = broken


if __name__ == "__main__":
    exchange_left_out()
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[1:])
