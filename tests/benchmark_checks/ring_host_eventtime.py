"""The served host with the cell's window held as the raw-row ring, for
test_benchmark_eventtime.py: ``python ring_host_eventtime.py conf=...``
appends to the run's transform a join that reads the window's rows (the
planner then keeps the ring: its choice is from the statements alone, so
a statement is the only way to ask), gives the join room in the run's
conf, then runs the host's own ``main()``. The answers must not change:
both window states keep the one event-time rule."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

JOIN = (
    "--DataXQuery--\n"
    "Seen = SELECT a.deviceDetails.deviceId AS deviceId, "
    "b.deviceDetails.homeId AS homeId FROM DataXProcessedInput a "
    "JOIN DataXProcessedInput_5minutes b "
    "ON a.deviceDetails.deviceId = b.deviceDetails.deviceId\n"
)


def read_the_windows_rows(conf_path: str) -> None:
    with open(conf_path, encoding="utf-8") as f:
        conf = dict(line.rstrip("\n").split("=", 1) for line in f if "=" in line)
    with open(conf["datax.job.process.transform"], "a", encoding="utf-8") as f:
        f.write(JOIN)
    with open(conf_path, "a", encoding="utf-8") as f:
        f.write("datax.job.process.joincapacity=4096\n")


if __name__ == "__main__":
    read_the_windows_rows(next(
        a.split("=", 1)[1] for a in sys.argv[1:] if a.startswith("conf=")))
    from data_accelerator_tpu.runtime import host

    host.main(sys.argv[1:])
