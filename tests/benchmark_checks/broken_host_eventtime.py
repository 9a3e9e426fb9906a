"""The served host with the one fault only an event-time window can have,
for test_benchmark_eventtime.py: ``python broken_host_eventtime.py
conf=...`` makes every accepted row count in its batch's own second,
however late it is stamped (the window then reads a late row a few
seconds after the second it belongs to has entered), then runs the host's
own ``main()``. ``HeatAvg`` counts are then wrong for the devices whose
rows came late, and land as if they were right. The benchmark's
comparison has to see it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def late_rows_in_their_batchs_second():
    import jax.numpy as jnp
    from data_accelerator_tpu.runtime import processor, timewindow

    event_rows = timewindow.event_rows

    def broken(ts, valid, base_s, now_rel_ms, clock):
        rows = event_rows(ts, valid, base_s, now_rel_ms, clock)
        return rows._replace(age=jnp.zeros_like(rows.age))

    timewindow.event_rows = processor.event_rows = broken


if __name__ == "__main__":
    late_rows_in_their_batchs_second()
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[1:])
