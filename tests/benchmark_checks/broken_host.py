"""The served host with its timed path broken underneath, for
test_benchmark_faults.py: ``python broken_host.py fault=<name> conf=...``
patches one place where results are made, then runs the host's own
``main()``. The benchmark's comparison has to see each fault;
``late_compile`` is no fault of the results, and the window has to move
past it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def state_unchanged():
    """The step returns its window state as it got it."""
    import jax
    import jax.numpy as jnp
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    dispatch = FlowProcessor.dispatch_batch

    def broken(self, raw, batch_time_ms=None):
        if not getattr(self, "_step_broken", False):
            step = self._step

            def unchanged(raw_, rings, *rest):
                kept = jax.tree_util.tree_map(jnp.copy, rings)
                out, _new_rings, state, counts = step(raw_, rings, *rest)
                return out, kept, state, counts

            self._step, self._step_broken = unchanged, True
        return dispatch(self, raw, batch_time_ms)

    FlowProcessor.dispatch_batch = broken


def half_left_out():
    """Half of every batch's result rows never reach the sinks."""
    from data_accelerator_tpu.runtime.processor import PendingBatch

    collect = PendingBatch.collect_tables

    def broken(self):
        datasets, metrics = collect(self)
        return {k: v[:(len(v) + 1) // 2] for k, v in datasets.items()}, metrics

    PendingBatch.collect_tables = broken


def answer_altered():
    """One value of one row is altered where it is written."""
    from data_accelerator_tpu.runtime.sinks import FileSink

    write = FileSink.write

    def broken(self, dataset, rows, batch_time_ms):
        rows = [dict(r) for r in rows]
        if rows:
            col = [c for c, v in rows[0].items() if isinstance(v, float)]
            if col:
                rows[0][col[-1]] *= 1.01
            else:
                first = next(iter(rows[0]))
                rows[0][first] += 1
        return write(self, dataset, rows, batch_time_ms)

    FileSink.write = broken


def late_compile():
    """No fault of the results: the host's twelfth batch reports a
    program loaded from the compile cache, as the sized transfer does
    when it changes its bucket batches after the count that moved it."""
    from data_accelerator_tpu.runtime.processor import PendingBatch

    collect = PendingBatch.collect_tables
    calls = []

    def late(self):
        datasets, metrics = collect(self)
        calls.append(1)
        if len(calls) == 12:
            metrics["Compile_Cache_Hit_Count"] = 1.0
            metrics.setdefault("Compile_Cache_Miss_Count", 0.0)
        return datasets, metrics

    PendingBatch.collect_tables = late


if __name__ == "__main__":
    {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
     "answer_altered": answer_altered,
     "late_compile": late_compile}[sys.argv[1].split("=", 1)[1]]()
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[2:])
