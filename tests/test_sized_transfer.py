"""Sized output transfer + the device-resident result path.

Covers the transfer half of both tentpoles: the EWMA-driven
power-of-two capacity, the golden overflow guarantee (a batch whose
count exceeds the adaptive capacity returns EXACTLY the rows a
full-capacity fetch returns, via the two-phase counts_vec-detected
re-fetch) plus the post-overflow headroom boost, the per-array-type
``copy_to_host_async`` capability probe with per-table fallback
counting, the split ``collect_counts()``/``collect_tables()`` result
path (golden-equal to the synchronous ``collect()``, including with the
landing on a background thread and the donated A/B output slots
rotating), and the Transfer_*/Sync_* metric surface."""

import json
from concurrent.futures import ThreadPoolExecutor

import pytest

from data_accelerator_tpu.core.config import EngineException, SettingDictionary
from data_accelerator_tpu.runtime.processor import (
    OUTPUT_SLOT_BUFFERS,
    OVERFLOW_BOOST_BATCHES,
    FlowProcessor,
)

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
]})

TRANSFORM = (
    "--DataXQuery--\n"
    "Out = SELECT k, v FROM DataXProcessedInput\n"
)


def _proc(tmp_path, extra=None, capacity=4096):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text(TRANSFORM)
    d = {
        "datax.job.name": "SizedFlow",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": str(capacity),
    }
    d.update(extra or {})
    return FlowProcessor(SettingDictionary(d), output_datasets=["Out"])


def _rows(n):
    return [{"k": i, "v": float(i)} for i in range(n)]


def test_sized_transfer_engages_after_observation(tmp_path):
    proc = _proc(tmp_path / "a")
    assert proc.sized_transfer
    # first batch: no observations yet -> full-capacity fetch
    h1 = proc.dispatch_batch(proc.encode_rows(_rows(10), 0), 1000)
    assert h1.fetch_caps == {"Out": 4096}
    _d1, m1 = h1.collect()
    # second batch: EWMA seeded -> power-of-two sized fetch, floor 256
    h2 = proc.dispatch_batch(proc.encode_rows(_rows(10), 0), 2000)
    assert h2.fetch_caps == {"Out": 256}
    d2, m2 = h2.collect()
    assert len(d2["Out"]) == 10
    # the sized fetch moved measurably fewer bytes at higher efficiency
    assert m2["Transfer_D2HBytes"] < m1["Transfer_D2HBytes"] / 4
    assert m2["Transfer_Efficiency"] > m1["Transfer_Efficiency"]
    assert "Transfer_Overflow_Count" not in m2


def test_overflow_refetch_matches_full_capacity_fetch(tmp_path):
    """Golden: a batch whose output count exceeds the adaptive capacity
    must return exactly the same rows as a full-capacity fetch."""
    sized = _proc(tmp_path / "a")
    sized.transfer_ewma["Out"] = 1.0  # force a 256-row sized cap
    h = sized.dispatch_batch(sized.encode_rows(_rows(1000), 0), 1000)
    assert h.fetch_caps == {"Out": 256}  # undershoots the 1000 valid rows
    datasets, metrics = h.collect()

    full = _proc(tmp_path / "b", {
        "datax.job.process.pipeline.sizedtransfer": "false",
    })
    assert not full.sized_transfer
    golden, _ = full.process_batch(full.encode_rows(_rows(1000), 0), 1000)

    assert datasets["Out"] == golden["Out"]
    assert metrics["Transfer_Overflow_Count"] == 1.0
    # the overflow jumped the EWMA to the observed count, so the NEXT
    # batch's sized cap clears it
    h2 = sized.dispatch_batch(sized.encode_rows(_rows(1000), 0), 2000)
    assert h2.fetch_caps["Out"] >= 1000
    d2, m2 = h2.collect()
    assert d2["Out"] == golden["Out"]
    assert "Transfer_Overflow_Count" not in m2


def test_pipeline_depth_conf_validation(tmp_path):
    with pytest.raises(EngineException):
        _proc(tmp_path, {"datax.job.process.pipeline.depth": "0"})
    proc = _proc(tmp_path / "ok", {"datax.job.process.pipeline.depth": "4"})
    assert proc.pipeline_depth == 4


# ---------------------------------------------------------------------------
# device-resident result path: counts-only sync + background landing
# ---------------------------------------------------------------------------
def test_overflow_boosts_headroom_for_following_batches(tmp_path):
    """Satellite: an overflow re-fetch doubles the output's headroom
    factor for the next OVERFLOW_BOOST_BATCHES batches (on top of the
    EWMA jump), so back-to-back growing bursts can't thrash the
    two-phase fetch; the boost then expires."""
    proc = _proc(tmp_path)
    proc.transfer_ewma["Out"] = 1.0  # force a 256-row sized cap
    h = proc.dispatch_batch(proc.encode_rows(_rows(1000), 0), 1000)
    _d, m = h.collect()
    assert m["Transfer_Overflow_Count"] == 1.0
    # set at overflow, burned once by this batch's own observation
    assert proc.transfer_boost["Out"] == OVERFLOW_BOOST_BATCHES - 1
    big = 1 << 20
    boosted = proc.transfer_capacity("Out", big)
    proc.transfer_boost["Out"] = 0
    plain = proc.transfer_capacity("Out", big)
    assert boosted == 2 * plain  # doubled headroom, same pow2 ladder
    # expiry: after N observations the boost is gone
    proc.transfer_boost["Out"] = 2
    proc.observe_transfer_counts({"Out": 1000})
    proc.observe_transfer_counts({"Out": 1000})
    assert proc.transfer_boost["Out"] == 0
    assert proc.transfer_capacity("Out", big) == plain


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
    sync = _proc(tmp_path / "sync", {
        "datax.job.process.pipeline.outputslots": "false",
    })
    seqs = [37, 301, 5, 301, 64]
    with ThreadPoolExecutor(1, thread_name_prefix="landing") as pool:
        prev = None  # (future of batch N-1's landing, golden datasets)
        for i, n in enumerate(seqs):
            t_ms = 1000 * (i + 1)
            golden, _gm = sync.process_batch(
                sync.encode_rows(_rows(n), 0), t_ms
            )
            h = bg.dispatch_batch(bg.encode_rows(_rows(n), 0), t_ms)
            h.collect_counts()  # the dispatch thread's only block
            fut = pool.submit(h.collect_tables)
            if prev is not None:
                datasets, metrics = prev[0].result()
                assert datasets["Out"] == prev[1]["Out"]
                assert metrics["Sync_CountsBytes"] > 0
            prev = (fut, golden)
        datasets, _m = prev[0].result()
        assert datasets["Out"] == prev[1]["Out"]


def test_output_slots_rotate_and_stay_correct(tmp_path):
    """The donated A/B slot rotation: consecutive batches alternate
    slot parity per (output, capacity) and results stay golden-equal to
    a slotless processor across cap changes and reuse."""
    proc = _proc(tmp_path / "slots")
    plain = _proc(tmp_path / "plain", {
        "datax.job.process.pipeline.outputslots": "false",
        "datax.job.process.pipeline.sizedtransfer": "false",
    })
    assert proc.output_slots_enabled and not plain.output_slots_enabled
    for i, n in enumerate([10, 20, 30, 40, 50]):
        t_ms = 1000 * (i + 1)
        d, _ = proc.process_batch(proc.encode_rows(_rows(n), 0), t_ms)
        g, _ = plain.process_batch(plain.encode_rows(_rows(n), 0), t_ms)
        assert d["Out"] == g["Out"]
    # after the first (full-capacity) batch the sized cap settles at
    # 256: the (Out, 256) ring holds OUTPUT_SLOT_BUFFERS slots and the
    # parity cursor advanced once per batch
    assert ("Out", 256) in proc._slots
    assert len(proc._slots[("Out", 256)]) == OUTPUT_SLOT_BUFFERS
    # 5 batches alternated A/B: the cursor ends on the odd parity
    assert proc._slot_parity["Out"] % OUTPUT_SLOT_BUFFERS == 1
    # all landed batches released their slots for donation
    for slot in proc._slots[("Out", 256)]:
        assert slot is not None and slot[1].is_set()


def test_slot_contention_falls_back_to_fresh_buffers(tmp_path):
    """A slot whose previous transfer has NOT landed is never donated:
    the pack falls back to fresh buffers (counted) instead of
    clobbering the in-flight copy or blocking the dispatch loop."""
    proc = _proc(tmp_path)
    hs = []
    for i in range(OUTPUT_SLOT_BUFFERS + 1):
        # dispatch 3 batches without collecting: the third reuses the
        # first batch's parity while its landing event is still unset
        hs.append(proc.dispatch_batch(
            proc.encode_rows(_rows(8), 0), 1000 * (i + 1)
        ))
    results = [h.collect() for h in hs]
    # the shared counter drains into whichever collect runs first
    contended = sum(
        m.get("Transfer_SlotContended_Count", 0.0) for _d, m in results
    )
    assert contended == 1.0
    for d, _m in results:
        assert len(d["Out"]) == 8
