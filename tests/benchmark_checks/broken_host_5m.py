"""The served host with the one fault only a window held as per-slot
partial aggregates can have, for test_benchmark_5m.py: ``python
broken_host_5m.py conf=...`` makes the combine over the slots skip one
live slot (the batch before the one being folded), then runs the host's
own ``main()``. ``HeatAvg`` then counts one batch too few for every device
that batch saw, and that answer lands as if it were whole. The
benchmark's comparison has to see it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def one_live_slot_skipped():
    import jax.numpy as jnp
    from data_accelerator_tpu.runtime import timewindow

    fold = timewindow.fold_partials

    def broken(state, ops, keys, valid, args, slot, *rest):
        k = state.cols["slot_live"].shape[0]
        skipped = jnp.arange(k) == (slot + k - 1) % k
        cols = dict(state.cols,
                    slot_live=state.cols["slot_live"] & ~skipped)
        return fold(type(state)(cols, state.valid), ops, keys, valid, args,
                    slot, *rest)

    timewindow.fold_partials = broken


if __name__ == "__main__":
    one_live_slot_skipped()
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[1:])
