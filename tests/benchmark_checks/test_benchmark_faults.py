"""The benchmark's comparison sees a broken timed path: the harness's look
for a chip is skipped, the rest of a run is driven against a CPU child
whose result path is broken underneath (``broken_host.py``), and
``correct`` comes out false; once for each fault a cell can have. (The
exchange between chips does not exist in a one-chip cell.)"""

import os
import sys

import pytest

from benchmark import run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500,
                    "max_chunk_events": 512},
        "warmup": {"min_batches": 7}}

CASES = [
    ("homeautomation.paced", "state_unchanged", "rows_differ"),
    ("homeautomation.paced", "half_left_out", "rows_differ"),
    ("homeautomation.paced", "answer_altered", "avg_rel_gap"),
    ("nexmark-q1.paced", "half_left_out", "rows_differ"),
    ("nexmark-q1.paced", "answer_altered", "price_rel_gap"),
]


@pytest.mark.parametrize("cell,fault,number", CASES)
def test_a_broken_timed_path_is_not_correct(tmp_path, cell, fault, number):
    line = bench.run_cell(
        cell, 24_000_000_007, 4, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable, os.path.join(HERE, "broken_host.py"),
                    f"fault={fault}"],
        require_tpu=False, overrides=TINY)
    assert line["correct"] is False
    over = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert number in over, line["compared"]


def test_a_window_the_host_compiled_in_is_opened_anew(tmp_path):
    """The host's twelfth batch (index 11) reports a program loaded: the
    window that had opened before it was warm-up after all. It opens anew
    three quiet batches later, set-up counts the stretch, no batch of the
    measured window compiled, and the rows still agree."""
    cell, run, m = bench.execute(
        "homeautomation.paced", 24_000_000_011, 5, False,
        run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable, os.path.join(HERE, "broken_host.py"),
                    "fault=late_compile"],
        require_tpu=False, overrides=TINY)
    assert cell["mix"]["warmup"]["quiet_batches"] == 3
    batches = run["rec"].batches
    assert batches[11][1]["Compile_Cache_Hit_Count"] == 1.0
    assert run["reopened"] == 1 and run["opened_by"] >= 14
    assert m["window"][0] == run["opened_by"] + 1
    assert not any(bench.compiled(batches[k][1]) for k in m["window"])
    assert m["e2e"]["setup_s"] > batches[11][2] - run["t_spawn"]
    assert bench.decide(run, cell, m)["correct"]
