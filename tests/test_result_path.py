"""The one result path: every output crosses to the host at its
declared capacity, on one device as under a mesh.

``dispatch_batch`` hands ``PendingBatch`` the step's own output tables;
``collect_counts()`` is the batch's one blocking read;
``collect_tables()`` fetches the tables whole and slices each on the
host to the count the sync learned. Checked here: the rows against a
plain reference at every fill level and layout, that no program but the
step ever compiles (whatever the counts do), that a removed
``pipeline.*`` key is reported as any stray key is, and that the bytes
a batch moves equal the cost model's to the byte."""

import json
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from data_accelerator_tpu.analysis.deviceplan import analyze_processor
from data_accelerator_tpu.core.config import EngineException, SettingDictionary
from data_accelerator_tpu.runtime.confaudit import audit_conf
from data_accelerator_tpu.runtime.processor import FlowProcessor

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
]})

# an output whose count the data decides: the rows with v >= 0
TRANSFORM = (
    "--DataXQuery--\n"
    "Out = SELECT k, v FROM DataXProcessedInput WHERE v >= 0\n"
)
CAPACITY = 1024


def _proc(tmp_path, extra=None, capacity=CAPACITY):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text(TRANSFORM)
    d = {
        "datax.job.name": "ResultPathFlow",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": str(capacity),
    }
    d.update(extra or {})
    return FlowProcessor(SettingDictionary(d), output_datasets=["Out"])


def _rows(count, n=CAPACITY, seed=0):
    """``n`` input rows of which ``count``, scattered over the batch,
    pass the flow's filter."""
    keep = np.zeros(n, bool)
    keep[np.random.default_rng(seed).permutation(n)[:count]] = True
    return [
        {"k": i, "v": float(i) if keep[i] else -1.0 - i} for i in range(n)
    ]


def _reference(rows):
    """The flow in plain Python: filter, project, input order."""
    return [{"k": r["k"], "v": r["v"]} for r in rows if r["v"] >= 0]


LAYOUTS = {"one_device": {}, "mesh4": {"datax.job.process.numchips": "4"}}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("count", [0, 1, CAPACITY // 2, CAPACITY])
def test_result_rows_equal_the_reference(tmp_path, count, layout):
    proc = _proc(tmp_path, LAYOUTS[layout])
    assert (proc.mesh is not None) == (layout == "mesh4")
    rows = _rows(count, seed=count)
    handle = proc.dispatch_batch(proc.encode_rows(rows, 0), 1000)
    assert handle.collect_counts().dataset_counts == {"Out": count}
    batches, metrics = handle.collect_tables()
    assert batches["Out"].rows() == _reference(rows)
    assert metrics["Output_Out_Events_Count"] == float(count)
    assert metrics["Transfer_Efficiency"] == count / CAPACITY


SWEEPS = {
    "sliver_to_full": [3, CAPACITY],
    "full_to_zero_to_full": [CAPACITY, 0, CAPACITY],
    "ramp": [i * CAPACITY // 19 for i in range(20)],
}


@pytest.mark.parametrize("sweep", list(SWEEPS))
def test_no_program_compiles_after_the_first_batch(tmp_path, sweep):
    """An output's count may do what it likes: the step is the one
    program, so after the first batch nothing compiles and nothing is
    loaded from the compile cache (a bucket's helper did, on the first
    batch that reached it: what refused PR 24's first version)."""
    proc = _proc(tmp_path)
    for i, count in enumerate(SWEEPS[sweep]):
        rows = _rows(count, seed=i)
        datasets, metrics = proc.process_batch(
            proc.encode_rows(rows, 0), 1000 * (i + 1)
        )
        assert datasets["Out"] == _reference(rows)
        if i == 0:
            steps = proc._step_cache_size()
            continue
        assert proc._step_cache_size() == steps == 1
        assert "Compile_Cache_Miss_Count" not in metrics, (i, count, metrics)
        assert "Compile_Cache_Hit_Count" not in metrics, (i, count, metrics)
        assert "Retrace_Count" not in metrics


@pytest.mark.parametrize("key", [
    "pipeline.sizedtransfer", "pipeline.outputslots",
    "pipeline.backgroundtransfer", "compile.jitcachecap",
])
def test_removed_pipeline_key_is_reported_unknown(tmp_path, key):
    """A job conf that still carries a key of the deleted lattice is
    input from outside: the audit names it, as any other stray key."""
    full = f"datax.job.process.{key}"
    proc = _proc(tmp_path, {full: "true" if "pipeline" in key else "32"})
    audit = audit_conf(proc.dict)
    assert [(e["kind"], e["key"]) for e in audit.events()] == [
        ("unknown", key)
    ]


@pytest.mark.parametrize("count", [5, CAPACITY], ids=["sparse", "dense"])
def test_d2h_bytes_equal_the_model_on_every_batch(tmp_path, count):
    """What DX501 compares: the outputs cross at their capacity, so a
    batch's ``Transfer_D2HBytes`` is the model's ``d2hBytesPerBatch``
    to the byte, whatever the batch holds."""
    proc = _proc(tmp_path)
    modelled = analyze_processor(proc).totals()["d2hBytesPerBatch"]
    for i, n in enumerate([count, 0, count]):
        _d, metrics = proc.process_batch(
            proc.encode_rows(_rows(n, seed=i), 0), 1000 * (i + 1)
        )
        assert metrics["Transfer_D2HBytes"] == float(modelled)


def test_pipeline_depth_conf_validation(tmp_path):
    with pytest.raises(EngineException):
        _proc(tmp_path, {"datax.job.process.pipeline.depth": "0"})
    proc = _proc(tmp_path / "ok", {"datax.job.process.pipeline.depth": "4"})
    assert proc.pipeline_depth == 4


def test_collect_counts_is_cheap_and_idempotent(tmp_path):
    """collect_counts parses the packed vector once (the batch's only
    blocking read) and caches; Sync_CountsBytes reports its wire
    cost."""
    proc = _proc(tmp_path)
    h = proc.dispatch_batch(proc.encode_rows(_rows(10), 0), 1000)
    bc = h.collect_counts()
    assert bc.dataset_counts == {"Out": 10}
    assert bc.counts.nbytes < 1024  # a few hundred bytes, not tables
    assert h.collect_counts() is bc  # cached sync point
    _d, m = h.collect_tables()
    assert m["Sync_CountsBytes"] == float(bc.counts.nbytes)
    assert m["Output_Out_Events_Count"] == 10.0


def test_background_landing_rows_match_sync_collect(tmp_path):
    """Golden: counts-only sync on the dispatch thread + table landing
    on a background thread — with the NEXT batch already dispatched
    (transfer genuinely overlapped) — produces byte-identical rows and
    counts vs the synchronous collect() path."""
    bg = _proc(tmp_path / "bg")
    sync = _proc(tmp_path / "sync")
    seqs = [37, 301, 5, 301, 64]
    with ThreadPoolExecutor(1, thread_name_prefix="landing") as pool:
        prev = None  # (future of batch N-1's landing, golden datasets)
        for i, n in enumerate(seqs):
            t_ms = 1000 * (i + 1)
            rows = _rows(n, seed=i)
            golden, _gm = sync.process_batch(sync.encode_rows(rows, 0), t_ms)
            h = bg.dispatch_batch(bg.encode_rows(rows, 0), t_ms)
            h.collect_counts()  # the dispatch thread's only block
            fut = pool.submit(h.collect_tables)
            if prev is not None:
                batches, metrics = prev[0].result()
                assert batches["Out"].rows() == prev[1]["Out"]
                assert metrics["Sync_CountsBytes"] > 0
            prev = (fut, golden)
        batches, _m = prev[0].result()
        assert batches["Out"].rows() == prev[1]["Out"]
