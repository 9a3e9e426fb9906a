"""chip_smoke.py at a tiny size against a CPU child: the reference
evaluator and the comparison agree with the engine row for row, and the
run still FAILS for exactly the reasons a CPU run must — no TPU, no HBM
reading, an interpreter-built Pallas kernel — and for a seeded wrong row.

One child per flow, shared by the tests of this module (each child costs
a jax start-up and a compile)."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

CAPACITY = 2048
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def iot(tmp_path_factory):
    """The run the tests read is the SECOND in its output directory, as
    the driver's chip run is (it runs the smoke in its sandbox first):
    the first run's checkpoint, flight recorder and sink files must not
    reach it."""
    out = str(tmp_path_factory.mktemp("smoke_iot"))
    stale = chip_smoke.phase_iot(
        out, seed=8, capacity=CAPACITY, batches=3, full_width=0,
    )
    assert stale["summary"]["batches_landed"] == 3
    for left in ("telemetry.jsonl", "checkpoint/offsets.txt", "out/HeatAvg"):
        assert os.path.exists(os.path.join(stale["run_dir"], left)), left
    return chip_smoke.phase_iot(
        out, seed=7, capacity=CAPACITY, batches=9, full_width=3,
    )


@pytest.fixture(scope="module")
def pallas(tmp_path_factory):
    return chip_smoke.phase_pallas(
        str(tmp_path_factory.mktemp("smoke_pallas")), seed=7,
        capacity=CAPACITY, batches=3,
        udf_class="tests.data.udfs.anomalyscore_interpret:anomalyscore",
    )


def _kinds(failures):
    return sorted({f.split(":", 1)[0] for f in failures})


def test_iot_rows_agree_and_a_cpu_child_still_fails(iot):
    """Every OpenDoors and HeatAvg row of every batch equals the numpy
    reference (two windows' worth of batches, the eviction included),
    the checkpoint ends on a batch boundary — and the phase fails all
    the same, because the child saw no TPU."""
    s = iot["summary"]
    assert _kinds(iot["failures"]) == ["hbm", "platform"], iot["failures"]
    assert s["platform"] == "cpu" and s["batches_landed"] == 9
    assert s["decoder_path"] == "native-sharded"
    assert s["rows_compared"] > 9 * 8  # 8 HeatAvg groups a batch + doors
    assert s["checkpoint"]["offset"] in np.cumsum(s["valid_rows_per_batch"])
    assert s["compile_cache_hits"] + s["compile_cache_misses"] > 0


def test_the_smoke_runs_the_repos_own_flow():
    """The smoke carries its own copy of the flow (it must not import
    the repo); the copy may not drift from BASELINE config 1."""
    import __graft_entry__ as ge

    assert chip_smoke.IOT_TRANSFORM == ge.BASE_TRANSFORM
    fields = json.loads(ge.IOT_SCHEMA)["fields"][0]["type"]["fields"]
    smoke = chip_smoke.IOT_SCHEMA["fields"][0]["type"]["fields"]
    assert [(f["name"], f["type"]) for f in fields] == \
        [(f["name"], f["type"]) for f in smoke]


def test_one_wrong_row_is_a_failure(iot):
    """Seed one wrong expected row: flip a closed DoorLock of the third
    batch to open in the events the reference evaluates. The sink (which
    saw the true events) then has one OpenDoors row fewer than the
    reference, and the comparison says so."""
    clean, _n = chip_smoke.check_iot(
        iot["events"], iot["run_dir"], iot["recorder"]
    )
    assert clean == []
    ev = copy.deepcopy(iot["events"])
    rows = iot["summary"]["valid_rows_per_batch"]
    lo = rows[0] + rows[1]
    closed = [
        i for i in range(lo, lo + rows[2])
        if ev["type"][i] == 0 and ev["status"][i] == 1
    ]
    ev["status"][closed[0]] = 0
    bad, _n = chip_smoke.check_iot(ev, iot["run_dir"], iot["recorder"])
    assert bad and bad[0].startswith("rows: 1 differ")
    assert "OpenDoors batch 2" in bad[1]
    # and a wrong temperature moves an average past the tolerance
    ev = copy.deepcopy(iot["events"])
    ev["milli"][:rows[0]] += 50_000
    bad, _n = chip_smoke.check_iot(ev, iot["run_dir"], iot["recorder"])
    assert any("HeatAvg batch 0" in f for f in bad)


def test_interpreter_built_pallas_kernel_is_a_failure(pallas):
    """The config-4 flow with the kernel built by the interpreter: the
    scores agree with the formula, and the phase fails BECAUSE of the
    build mode (read from the child's own report)."""
    assert _kinds(pallas["failures"]) == ["hbm", "pallas", "platform"], \
        pallas["failures"]
    assert any("interpret=True" in f for f in pallas["failures"])
    assert pallas["summary"]["pallas_interpret"] == {"anomalyscore": True}
    assert pallas["summary"]["rows_compared"] > 0


def test_wrong_score_is_a_failure(pallas):
    ev = copy.deepcopy(pallas["events"])
    ev["device"][:] = 9 - ev["device"]  # other means: other scores
    bad, _n = chip_smoke.check_pallas(
        ev, pallas["run_dir"], pallas["recorder"]
    )
    assert bad and bad[0].startswith("rows: ")


def test_exits_nonzero_and_prints_no_result_outside_the_repo(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the child cannot start: exit code 1, nothing on stdout, and no
    process left behind. (Here the default Pallas build also stands in
    for 'no TPU': nothing in this run can print a result.)"""
    script = tmp_path / "chip_smoke.py"
    script.write_bytes(
        open(os.path.join(REPO, "chip_smoke.py"), "rb").read()
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, str(script), "--out", str(tmp_path / "out")],
        cwd=str(tmp_path), env=env, capture_output=True, timeout=300,
    )
    assert done.returncode == 1
    assert done.stdout == b""
    report = json.loads((tmp_path / "out" / "chip_smoke.json").read_text())
    assert report["ok"] is False
    assert any("exit code" in f for f in report["failures"])
