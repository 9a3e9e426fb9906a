"""The served host with the one fault only a carried accumulator can have,
for test_benchmark_accumulator.py: ``python broken_host_accumulator.py
conf=...`` hands every batch's step an empty ``DeviceTotals`` in place of
the table the batch before left, then runs the host's own ``main()``. The
table then holds each batch's own devices alone, and that answer lands
(and is written A/B and committed) as if it were the running totals. The
benchmark's comparison has to see it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def every_batch_from_empty_state():
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    dispatch = FlowProcessor.dispatch_batch

    def broken(self, *args, **kwargs):
        self.state_data = {n: st.empty()
                           for n, st in self.state_tables.items()}
        return dispatch(self, *args, **kwargs)

    FlowProcessor.dispatch_batch = broken


if __name__ == "__main__":
    every_batch_from_empty_state()
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[1:])
