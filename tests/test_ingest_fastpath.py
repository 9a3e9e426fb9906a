"""Ingest fast path (native Kafka-v2 walker, SIMD scan, packed buffer
pool): golden native-vs-Python equality for the Kafka binary path,
malformed/truncated/corrupt/compressed record batches, shard parity,
the decode buffer pool, the decoderthreads conf knob + generation, the
calibrated host-decode latency term, and the CI guard that the native
library actually builds (so a silent g++ failure can't fake a pass).

NOTE: deliberately no module-level native skip — the first test IS the
native-build assertion.
"""

import json
import os
import struct

import numpy as np
import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.core.schema import Schema, StringDictionary
from data_accelerator_tpu.native import (
    NativeDecoder,
    PackedBufferPool,
    native_available,
)
from data_accelerator_tpu.runtime.kafka_wire import (
    UnsupportedCodecError,
    decode_record_batches,
    encode_record_batch,
    iter_batch_spans,
)
from data_accelerator_tpu.runtime.processor import (
    FlowProcessor,
    packed_raw_layout,
)

SCHEMA_JSON = json.dumps({
    "type": "struct",
    "fields": [
        {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
        {"name": "deviceType", "type": "string", "nullable": False,
         "metadata": {}},
        {"name": "temperature", "type": "double", "nullable": False,
         "metadata": {}},
        {"name": "online", "type": "boolean", "nullable": False,
         "metadata": {}},
    ],
})


def test_native_library_builds_in_ci():
    """CI guard (satellite): the native decoder must BUILD and load in
    the test environment — every native-gated test below would
    otherwise skip while the suite still passes. Set
    DATAX_ALLOW_NO_NATIVE=1 only on machines that genuinely have no
    toolchain."""
    if os.environ.get("DATAX_ALLOW_NO_NATIVE") == "1":
        pytest.skip("explicitly allowed to run without the native decoder")
    assert native_available(), (
        "native decoder failed to build/load (check g++ and "
        "native/decoder.cpp)"
    )


def test_library_is_named_by_a_hash_of_its_source_and_flags(monkeypatch):
    """The library loaded is the one built from THIS decoder.cpp with
    THESE flags: its name is their hash, under the ignored build dir —
    so changing either changes the name, and a stale or foreign .so is
    never picked up in its place."""
    from data_accelerator_tpu.native import decoder as dec

    path = dec._build_library()
    assert os.path.dirname(path) == dec._BUILD_DIR
    assert dec._BUILD_DIR.endswith(os.path.join("native", ".build"))
    assert os.path.exists(path)
    monkeypatch.setattr(dec, "_CXX", dec._CXX + ["-DDX_OTHER_BUILD"])
    assert os.path.basename(dec._build_library()) != os.path.basename(path)


def test_unbuildable_decoder_fails_host_start_with_compiler_message(
    tmp_path, monkeypatch,
):
    """A raw-bytes host whose native decoder cannot be built does not
    start: StreamingHost raises NativeBuildError carrying the compiler's
    stderr — no quiet switch to the per-row Python encoder — and the
    processor's own encode raises the same way."""
    from data_accelerator_tpu.native import NativeBuildError
    from data_accelerator_tpu.native import decoder as dec
    from data_accelerator_tpu.runtime.host import StreamingHost

    monkeypatch.setattr(dec, "_lib", None)
    monkeypatch.setattr(dec, "_lib_error", None)
    monkeypatch.setattr(dec, "_CXX", dec._CXX + ["--no-such-flag-dx"])
    conf = dict(_proc(tmp_path).dict.dict)
    conf.update({
        "datax.job.input.default.inputtype": "socket",
        "datax.job.process.observability.calibration": "false",
    })
    with pytest.raises(NativeBuildError, match="no-such-flag-dx") as err:
        StreamingHost(SettingDictionary(conf))
    assert "build failed" in str(err.value)
    proc = _proc(tmp_path)
    with pytest.raises(NativeBuildError, match="no-such-flag-dx"):
        proc.encode_json_bytes(b"{}\n", 1_700_000_000_000)
    assert proc.last_decoder_path is None


def _proc(tmp_path, capacity=32, extra=None):
    t = tmp_path / "fp.transform"
    if not t.exists():
        t.write_text(
            "--DataXQuery--\n"
            "Out = SELECT deviceId, deviceType, temperature, online "
            "FROM DataXProcessedInput\n"
        )
    conf = {
        "datax.job.name": "FastPath",
        "datax.job.input.default.inputtype": "kafka",
        "datax.job.input.default.blobschemafile": SCHEMA_JSON,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.projection": (
            "current_timestamp() AS eventTimeStamp\nRaw.*"
        ),
    }
    conf.update(extra or {})
    return FlowProcessor(
        SettingDictionary(conf), batch_capacity=capacity,
        output_datasets=["Out"],
    )


def _values(n, start=0):
    return [
        json.dumps({
            "deviceId": start + i,
            "deviceType": f"T{(start + i) % 3}",
            "temperature": 20.0 + (start + i),
            "online": (start + i) % 2 == 0,
        }).encode()
        for i in range(n)
    ]


def _rows_of(proc, table):
    """Materialize (deviceId, deviceType, temperature, online) for the
    VALID rows of an encoded raw batch (PackedRaw or TableData)."""
    from data_accelerator_tpu.runtime.processor import PackedRaw

    if isinstance(table, PackedRaw):
        table = table.unpack()
    cols = {c: np.asarray(v) for c, v in table.cols.items()}
    valid = np.asarray(table.valid)
    out = []
    for i in np.nonzero(valid)[0]:
        out.append((
            int(cols["deviceId"][i]),
            proc.dictionary.decode(int(cols["deviceType"][i])),
            round(float(cols["temperature"][i]), 3),
            bool(cols["online"][i]),
        ))
    return out


pytest_native = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable / native build failed"
)


@pytest_native
def test_kafka_fast_path_golden_vs_python_reference(tmp_path):
    """Acceptance: KafkaSource.poll_raw blobs route through
    encode_json_bytes(fmt="kafka-v2") with ZERO per-row Python objects
    (native walker), and the decoded batch equals the Python reference
    row encoder's output row for row — incl. malformed record values,
    which both drop and count."""
    vals = _values(12)
    vals.insert(3, b"{not json")      # malformed value
    vals.insert(7, b"")               # empty value
    blob = (
        encode_record_batch(0, vals[:8], timestamp_ms=1)
        + encode_record_batch(8, vals[8:], timestamp_ms=2)
    )

    native = _proc(tmp_path)
    raw_native = native.encode_json_bytes(
        blob, 1_700_000_000_000, fmt="kafka-v2"
    )
    assert native.last_decoder_path == "native-sharded"
    got_native = _rows_of(native, raw_native)
    native_malformed = native.ingest_stats.get("malformed_rows", 0)

    # the per-row Python encoder is the parity reference, called
    # directly — the served path never reaches it
    reference = _proc(tmp_path)
    raw_py = reference._encode_json_python(
        blob, 1_700_000_000_000, reference._spec(None), "kafka-v2"
    )
    got_py = _rows_of(reference, raw_py)

    assert got_native == got_py
    assert len(got_native) == 12
    assert native_malformed == 2
    assert reference.ingest_stats.get("malformed_rows", 0) == 2


@pytest_native
def test_kafka_walker_corrupt_truncated_and_split_batches(tmp_path):
    """Corrupt batches (CRC-32C mismatch) skip WHOLE and count into
    Input_CorruptBatch_Count instead of mis-parsing; a truncated
    trailing batch (the fetch-size boundary / split-across-poll case)
    is ignored; the intact batches still decode."""
    good1 = encode_record_batch(0, _values(4), timestamp_ms=1)
    bad = bytearray(encode_record_batch(4, _values(4, start=4)))
    bad[80] ^= 0xFF  # flip a record byte: CRC now mismatches
    good2 = encode_record_batch(8, _values(4, start=8), timestamp_ms=2)
    # a split-across-poll tail: the first half of another batch
    tail = encode_record_batch(12, _values(4, start=12))[: 40]
    blob = good1 + bytes(bad) + good2 + tail

    proc = _proc(tmp_path)
    raw = proc.encode_json_bytes(blob, 1_700_000_000_000, fmt="kafka-v2")
    got = _rows_of(proc, raw)
    assert [g[0] for g in got] == [0, 1, 2, 3, 8, 9, 10, 11]
    assert proc.ingest_stats.get("CorruptBatch") == 1
    # the python walker agrees batch-for-batch
    stats = {}
    recs, next_off = decode_record_batches(blob, stats=stats)
    assert [o for o, _t, _v in recs] == [0, 1, 2, 3, 8, 9, 10, 11]
    assert stats["corrupt_batches"] == 1
    assert next_off == 12  # past good2; the split tail is not covered


@pytest_native
def test_kafka_compressed_batch_rejected_typed(tmp_path):
    """A compressed batch aborts with the typed UnsupportedCodecError
    NAMING the codec — a configuration error, not garbage rows."""
    batch = bytearray(encode_record_batch(0, _values(2)))
    batch[21:23] = struct.pack(">h", 3)  # lz4 codec bits
    proc = _proc(tmp_path)
    with pytest.raises(UnsupportedCodecError, match="lz4"):
        proc.encode_json_bytes(
            bytes(batch), 1_700_000_000_000, fmt="kafka-v2"
        )
    # python walker: identical typed rejection
    with pytest.raises(UnsupportedCodecError, match="lz4"):
        decode_record_batches(bytes(batch))


@pytest_native
def test_kafka_source_poll_raw_routes_fast_path(tmp_path):
    """KafkaSource.poll_raw (injected raw-capable consumer) delivers
    whole record batches budgeted at batch granularity, with the
    un-acked FIFO redelivery contract and offsets that commit only on
    ack — and the blob round-trips through encode_json_bytes."""
    from data_accelerator_tpu.runtime.sources import KafkaSource

    b1 = encode_record_batch(0, _values(4))
    b2 = encode_record_batch(4, _values(4, start=4))
    b3 = encode_record_batch(8, _values(4, start=8))

    class RawConsumer:
        def __init__(self):
            self.fetches = [[("t", 0, 0, b1 + b2 + b3, 12)]]
            self.commits = []

        def fetch_raw(self, timeout=0.05):
            return self.fetches.pop(0) if self.fetches else []

        def commit(self, offsets):
            self.commits.append(offsets)

        def close(self):
            pass

    src = KafkaSource("b:9092", ["t"], consumer=RawConsumer())
    assert hasattr(src, "poll_raw")
    assert src.raw_format == "kafka-v2"
    # batch-granular budget: 6 requested -> one whole batch fits (4),
    # the second would overflow the budget
    blob, n, offsets = src.poll_raw(6)
    assert n == 4
    assert offsets == {("t", 0): (0, 4)}
    blob2, n2, offsets2 = src.poll_raw(100)
    assert n2 == 8
    assert offsets2 == {("t", 0): (4, 12)}

    # requeue: both un-acked deliveries come back byte-identical
    src.requeue_unacked()
    rblob, rn, roff = src.poll_raw(6)
    assert (rblob, rn, roff) == (blob, 4, offsets)
    rblob2, rn2, roff2 = src.poll_raw(100)
    assert (rblob2, rn2, roff2) == (blob2, 8, offsets2)
    # ack commits exactly the oldest batch's end offsets
    src.ack()
    assert src._consumer.commits == [offsets]

    proc = _proc(tmp_path)
    got = _rows_of(proc, proc.encode_json_bytes(
        rblob + rblob2, 1_700_000_000_000, fmt="kafka-v2"
    ))
    assert [g[0] for g in got] == list(range(12))


@pytest_native
def test_packed_pool_reuse_and_in_flight_protection(tmp_path):
    """The decode buffer pool: a slot acquired for an in-flight batch
    is NEVER handed to a new decode until that batch lands; after the
    landing the very next decode reuses it (Decode_BufferReuse_Count)."""
    proc = _proc(tmp_path, capacity=16)
    blob = b"\n".join(
        json.dumps({"deviceId": i, "deviceType": "a", "temperature": 1.0,
                    "online": True}).encode()
        for i in range(4)
    ) + b"\n"
    r1 = proc.encode_json_bytes(blob, 1_700_000_000_000, to_device=False)
    pool, m1 = r1._ingest_pool
    # while r1 is un-dispatched/un-landed its matrix must not be reused
    r2 = proc.encode_json_bytes(blob, 1_700_000_001_000, to_device=False)
    _pool2, m2 = r2._ingest_pool
    assert m1 is not m2
    assert pool.alloc_count == 2 and pool.reuse_count == 0

    h1 = proc.dispatch_batch(r1, batch_time_ms=1_700_000_000_000)
    h1.collect()  # lands -> releases m1
    r3 = proc.encode_json_bytes(blob, 1_700_000_002_000, to_device=False)
    _pool3, m3 = r3._ingest_pool
    assert m3 is m1  # reused, not re-allocated
    assert pool.reuse_count == 1

    # abandon releases too (the failure-requeue path)
    h2 = proc.dispatch_batch(r2, batch_time_ms=1_700_000_001_000)
    h2.abandon()
    r4 = proc.encode_json_bytes(blob, 1_700_000_003_000, to_device=False)
    assert r4._ingest_pool[1] is m2
    # the reuse counter drains into the Decode_* metrics at collect
    h3 = proc.dispatch_batch(
        {"default": r3, }, batch_time_ms=1_700_000_002_000
    )
    _d, m = h3.collect_tables()
    assert m.get("Decode_BufferReuse_Count") == 2.0
    assert m.get("Decode_Shards") is not None
    assert m.get("Decode_RowsPerSec", 0) > 0


@pytest_native
def test_packed_shard_parity_jsonl_and_kafka(tmp_path):
    """Sharded decode (threads=4) produces the same valid rows and
    dictionary SET as single-shard, on both the jsonl packed path and
    the Kafka walker's sharded value decode (>=8192 records)."""
    schema = Schema.from_spark_json(SCHEMA_JSON)
    n = 9000
    vals = _values(n)
    kblob = b"".join(
        encode_record_batch(i, vals[i: i + 1000])
        for i in range(0, n, 1000)
    )
    jblob = b"\n".join(vals) + b"\n"

    def decode(blob, fmt, threads):
        dd = StringDictionary()
        dec = NativeDecoder(schema, dd, threads=threads)
        pool = PackedBufferPool(len(schema.columns) + 1, n)
        mat = pool.acquire()
        col_rows = list(range(len(schema.columns)))
        if fmt == "kafka":
            rows, _stats = dec.decode_kafka_packed(
                kblob, mat, col_rows, len(schema.columns), 0
            )
        else:
            rows, _c = dec.decode_packed(
                jblob, mat, col_rows, len(schema.columns), 0
            )
        valid = mat[len(schema.columns)] != 0
        ids = mat[1][valid]  # deviceType dict ids
        return rows, [dd.decode(int(i)) for i in ids], set(dd.entries())

    for fmt in ("jsonl", "kafka"):
        r1, s1, e1 = decode(jblob, fmt, 1)
        r4, s4, e4 = decode(jblob, fmt, 4)
        assert r1 == r4 == n
        assert s1 == s4
        assert e1 == e4


def test_decoderthreads_conf_reaches_decoder(tmp_path):
    """datax.job.process.ingest.decoderthreads is a first-class flow
    conf: the processor passes it to the native decoder (overriding
    the engine default; DATAX_DECODER_THREADS env still wins)."""
    proc = _proc(tmp_path, extra={
        "datax.job.process.ingest.decoderthreads": "3",
    })
    assert proc.decoder_threads == 3
    if native_available():
        blob = b'{"deviceId":1,"deviceType":"a","temperature":1.0,' \
               b'"online":true}\n'
        proc.encode_json_bytes(blob, 1_700_000_000_000, to_device=False)
        dec = proc._native_decoders["default"]
        assert dec.threads == 3
        assert dec.shard_count() == 3
        os.environ["DATAX_DECODER_THREADS"] = "2"
        try:
            assert dec.shard_count() == 2  # operator override wins
        finally:
            del os.environ["DATAX_DECODER_THREADS"]
    with pytest.raises(Exception, match="decoderthreads"):
        _proc(tmp_path, extra={
            "datax.job.process.ingest.decoderthreads": "0",
        })


def test_decoderthreads_designer_knob_generates_conf(tmp_path):
    """The designer jobDecoderThreads knob lands in the generated conf
    as datax.job.process.ingest.decoderthreads (S400 token -> S650)."""
    from data_accelerator_tpu.core.config import parse_conf_lines
    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.scenarios import probe_deploy_gui
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    gui = probe_deploy_gui()
    gui.setdefault("process", {})["jobconfig"] = {"jobDecoderThreads": "5"}
    fo = FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "d")),
        LocalRuntimeStorage(str(tmp_path / "r")),
        fleet_admission=False,
    )
    fo.save_flow(gui)
    res = fo.generate_configs("probe-deploy")
    assert res.ok, res.errors
    props = parse_conf_lines(
        open(res.conf_paths[0], encoding="utf-8").readlines()
    )
    assert props["datax.job.process.ingest.decoderthreads"] == "5"


def test_latency_model_gains_calibrated_decode_term():
    """Cost-model satellite: a profile carrying decode_rows_per_sec
    prices a decodeMs term from the input-stage rows, and the DX520
    stage predictions gain a 'decode' key beside device-step/collect;
    without the calibrated rate the term stays silent."""
    from data_accelerator_tpu.analysis.costmodel import (
        latency_model,
        stage_latency_predictions,
    )

    stages = [
        {"name": "input:default", "kind": "input", "rows": 65536,
         "hbmBytes": 1 << 20, "flops": 0.0},
        {"name": "Out", "kind": "project", "rows": 65536,
         "hbmBytes": 1 << 20, "flops": 1e6},
    ]
    totals = {"d2hBytesPerBatch": 1 << 16}
    profile = {
        "hbm_read_gbps": 100.0, "hbm_write_gbps": 100.0,
        "flops_gflops": 100.0, "dispatch_overhead_us": 10.0,
        "d2h_gbps": 10.0, "decode_rows_per_sec": 4_000_000.0,
    }
    lm = latency_model(stages, totals, profile, profile_source="calibrated")
    assert lm["totals"]["decodeMs"] == pytest.approx(65536 / 4.0e6 * 1e3,
                                                    rel=1e-6)
    assert lm["totals"]["batchMs"] >= lm["totals"]["decodeMs"]
    preds = stage_latency_predictions(lm)
    assert "decode" in preds and "device-step" in preds
    # no calibrated rate -> silence (the missing-prediction posture)
    lm2 = latency_model(
        stages, totals, {**profile, "decode_rows_per_sec": None}
    )
    assert lm2["totals"]["decodeMs"] is None
    assert "decode" not in stage_latency_predictions(lm2)


def test_runtime_model_carries_input_rows():
    """The conf-embedded conformance model keeps stage rows so a
    running host can price the decode prediction from its OWN
    calibrated profile (bytes/rows travel, milliseconds are computed
    where the hardware is)."""
    from data_accelerator_tpu.analysis.costmodel import (
        model_input_rows,
        runtime_conformance_model,
    )

    model = runtime_conformance_model(
        {"d2hBytesPerBatch": 1}, stages=[
            {"name": "input:default", "kind": "input", "rows": 4096},
            {"name": "Out", "kind": "project", "rows": 4096},
        ],
    )
    assert model["stages"][0]["rows"] == 4096
    assert model_input_rows(model["stages"]) == 4096.0


@pytest_native
def test_iter_batch_spans_header_scan():
    b1 = encode_record_batch(5, _values(3))
    b2 = encode_record_batch(8, _values(2))
    spans = list(iter_batch_spans(b1 + b2 + b"\x00" * 30))
    assert [(s["base_offset"], s["next_offset"], s["record_count"])
            for s in spans] == [(5, 8, 3), (8, 10, 2)]
    assert spans[0]["start"] == 0 and spans[0]["end"] == len(b1)
    assert spans[1]["end"] == len(b1) + len(b2)
