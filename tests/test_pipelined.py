"""Tests for the pipelined dispatch/collect path (P6 overlap): results
must match the synchronous path, including across state-table batches."""

import json

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.host import StreamingHost
from data_accelerator_tpu.runtime.processor import FlowProcessor

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False,
     "metadata": {"allowedValues": [1, 2]}},
    {"name": "v", "type": "double", "nullable": False,
     "metadata": {"minValue": 0, "maxValue": 10}},
]})


def _proc(tmp_path, transform_text, outputs):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text(transform_text)
    return FlowProcessor(
        SettingDictionary({
            "datax.job.name": "PipeFlow",
            "datax.job.input.default.blobschemafile": SCHEMA,
            "datax.job.process.transform": str(t),
            "datax.job.process.batchcapacity": "16",
        }),
        output_datasets=outputs,
    )


def test_two_in_flight_matches_sequential(tmp_path):
    transform = (
        "--DataXQuery--\n"
        "Big = SELECT k, v FROM DataXProcessedInput WHERE v > 5\n"
    )
    rows1 = [{"k": 1, "v": 7.0}, {"k": 2, "v": 1.0}, {"k": 1, "v": 9.0}]
    rows2 = [{"k": 2, "v": 6.0}]

    seq = _proc(tmp_path / "a", transform, ["Big"])
    d1, m1 = seq.process_batch(seq.encode_rows(rows1, 0), 1000)
    d2, m2 = seq.process_batch(seq.encode_rows(rows2, 0), 2000)

    pipe = _proc(tmp_path / "b", transform, ["Big"])
    h1 = pipe.dispatch_batch(pipe.encode_rows(rows1, 0), 1000)
    h2 = pipe.dispatch_batch(pipe.encode_rows(rows2, 0), 2000)
    p1, pm1 = h1.collect()
    p2, pm2 = h2.collect()

    assert p1["Big"] == d1["Big"]
    assert p2["Big"] == d2["Big"]
    assert pm1["Output_Big_Events_Count"] == m1["Output_Big_Events_Count"] == 2.0
    assert pm2["Output_Big_Events_Count"] == 1.0


def test_pipelined_state_table_overwrite_uses_own_batch_state(tmp_path):
    """Batch N's A/B overwrite must see N's accumulation, not N+1's,
    even when N+1 was dispatched before N collected (state buffers are
    deliberately NOT donated for this reason)."""
    t = tmp_path / "t.transform"
    t.write_text(
        "--DataXQuery--\n"
        "merged = SELECT k, v FROM DataXProcessedInput "
        "UNION ALL SELECT k, v FROM acc\n"
        "--DataXQuery--\n"
        "acc = SELECT k, v FROM merged\n"
        "--DataXQuery--\n"
        "Out = SELECT k, v FROM DataXProcessedInput\n"
    )
    proc = FlowProcessor(
        SettingDictionary({
            "datax.job.name": "StateFlow",
            "datax.job.input.default.blobschemafile": SCHEMA,
            "datax.job.process.transform": str(t),
            "datax.job.process.batchcapacity": "16",
            "datax.job.process.statetable.acc.schema": "k long, v double",
            "datax.job.process.statetable.acc.location": str(tmp_path / "st"),
        }),
        output_datasets=["Out"],
    )
    h1 = proc.dispatch_batch(proc.encode_rows([{"k": 1, "v": 2.0}], 0), 1000)
    h2 = proc.dispatch_batch(proc.encode_rows([{"k": 1, "v": 3.0}], 0), 2000)
    h1.collect()
    proc.commit()
    h2.collect()
    proc.commit()
    # reload persisted state: both rows accumulated exactly once
    import numpy as np

    loaded = proc.state_tables["acc"].load(proc.dictionary)
    vals = sorted(
        float(v) for v, ok in zip(
            np.asarray(loaded.cols["v"]), np.asarray(loaded.valid)
        ) if ok
    )
    assert vals == [2.0, 3.0]


def test_streaming_host_run_pipelined(tmp_path):
    d = SettingDictionary({
        "datax.job.name": "HostPipe",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "64",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(tmp_path / "t.transform"),
        "datax.job.process.batchcapacity": "64",
        "datax.job.output.Hot.console.maxrows": "0",
    })
    (tmp_path / "t.transform").write_text(
        "--DataXQuery--\n"
        "Hot = SELECT k, v FROM DataXProcessedInput WHERE v > 5\n"
    )
    host = StreamingHost(d)
    host.run_pipelined(max_batches=3)
    assert host.batches_processed == 3


def test_streaming_host_depth2_smoke(tmp_path):
    """Tier-1 smoke: the streaming host at an explicit in-flight depth
    of 2 (conf process.pipeline.depth) runs a handful of batches with
    sized transfer on, emitting the pipeline/transfer metric family."""
    d = SettingDictionary({
        "datax.job.name": "Depth2Smoke",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "64",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(tmp_path / "t.transform"),
        "datax.job.process.batchcapacity": "64",
        "datax.job.process.pipeline.depth": "2",
        "datax.job.output.Hot.console.maxrows": "0",
    })
    (tmp_path / "t.transform").write_text(
        "--DataXQuery--\n"
        "Hot = SELECT k, v FROM DataXProcessedInput WHERE v > 5\n"
    )
    host = StreamingHost(d)
    assert host.processor.pipeline_depth == 2
    seen = {}
    orig = host.metric_logger.send_batch_metrics

    def spy(metrics, ts):
        seen.update(metrics)
        return orig(metrics, ts)

    host.metric_logger.send_batch_metrics = spy
    try:
        host.run_pipelined(max_batches=5)
    finally:
        host.stop()
    assert host.batches_processed == 5
    assert "Pipeline_Depth" in seen and seen["Pipeline_Depth"] >= 1.0
    assert "Pipeline_Stall_Ms" in seen
    assert "Transfer_D2HBytes" in seen
    assert 0.0 < seen["Transfer_Efficiency"] <= 1.0


def test_socket_source_depth2_inflight_ack_and_requeue():
    """A pipelined host holds two un-acked batches: polls must deliver
    NEW data (no duplicates), acks release oldest-first, and
    requeue_unacked re-delivers every un-acked batch in order."""
    import socket
    import time as _time

    from data_accelerator_tpu.runtime.sources import SocketSource

    src = SocketSource(port=0)
    try:
        conn = socket.create_connection(("127.0.0.1", src.port), timeout=5)
        conn.sendall(b'{"a": 1}\n{"a": 2}\n{"a": 3}\n{"a": 4}\n')
        deadline = _time.time() + 5
        while _time.time() < deadline and src.buffered_rows < 4:
            _time.sleep(0.01)

        b1, n1, _ = src.poll_raw(2)   # batch 1: a=1,2
        b2, n2, _ = src.poll_raw(2)   # batch 2: a=3,4 (NOT a repeat of 1)
        assert (n1, n2) == (2, 2)
        assert b1 != b2 and b'"a": 1' in b1 and b'"a": 3' in b2

        # failure with both in flight: requeue, then re-poll in order
        src.requeue_unacked()
        r1, _, _ = src.poll_raw(2)
        r2, _, _ = src.poll_raw(2)
        assert r1 == b1 and r2 == b2

        src.ack()   # releases batch 1
        src.ack()   # releases batch 2
        src.requeue_unacked()
        b3, n3, _ = src.poll_raw(2)
        assert n3 == 0  # nothing left to re-deliver
        conn.close()
    finally:
        src.close()


def test_run_pipelined_polls_exactly_max_batches(tmp_path):
    """The decode-ahead prefetch must not poll a batch it will never
    dispatch: an orphaned poll sits in the un-acked FIFO, where a later
    in-order ack would release (for Kafka: commit) it unprocessed."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text(
        "--DataXQuery--\n"
        "Out = SELECT k, v FROM DataXProcessedInput\n"
    )
    conf = SettingDictionary({
        "datax.job.name": "PollCount",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.transform": str(t),
        "datax.job.output.Out.console.maxrows": "0",
    })
    host = StreamingHost(conf)
    src = host.source
    polls = {"n": 0}
    orig = src.poll_columns

    def counting_poll(*a, **k):
        polls["n"] += 1
        return orig(*a, **k)

    src.poll_columns = counting_poll
    host.run_pipelined(max_batches=3)
    host.stop()
    assert host.batches_processed == 3
    assert polls["n"] == 3  # not 4: no orphaned prefetch
