"""A state table at the deployment's size: its capacity is the key bound
(``process.maxgroups``) where the conf sets one and four batch widths
where it does not, keys past the bound are counted, and a table with no
``location`` persists under its job's checkpoint directory. The A/B
contract holds there as everywhere: the pointers are committed only after
the batch's sinks succeeded, so a sink that fails, or a crash between the
standby write and the commit, leaves the last committed side active."""

import json
import os

import numpy as np
import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.host import StreamingHost
from data_accelerator_tpu.runtime.statetable import state_capacity

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
]})


def _conf(tmp_path, ckpt="ckpt", extra=None):
    t = tmp_path / "acc.transform"
    t.write_text(
        "--DataXQuery--\n"
        "merged = SELECT k, v FROM DataXProcessedInput "
        "UNION ALL SELECT k, v FROM totals\n"
        "--DataXQuery--\n"
        "totals = SELECT k, SUM(v) AS v FROM merged GROUP BY k\n"
        "--DataXQuery--\n"
        "Out = SELECT k, v FROM DataXProcessedInput\n"
    )
    d = {
        "datax.job.name": "AccFlow",
        "datax.job.input.default.inputtype": "file",
        "datax.job.input.default.blobpathregex": str(tmp_path / "in" / "*.json"),
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "100",
        "datax.job.input.default.eventhub.checkpointdir": str(tmp_path / ckpt),
        "datax.job.input.default.eventhub.checkpointinterval": "0 second",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.statetable.totals.schema": "k long, v double",
        "datax.job.output.Out.console.maxrows": "0",
    }
    d.update(extra or {})
    return SettingDictionary(d)


def _feed(tmp_path, name, keys):
    os.makedirs(tmp_path / "in", exist_ok=True)
    with open(tmp_path / "in" / f"{name}.json", "w") as f:
        for k in keys:
            f.write(json.dumps({"k": k, "v": 1.0}) + "\n")


def _totals(host):
    t = host.processor.state_tables["totals"].load(host.processor.dictionary)
    return {
        int(k): float(v) for k, v, ok in zip(
            np.asarray(t.cols["k"]), np.asarray(t.cols["v"]),
            np.asarray(t.valid)) if ok
    }


@pytest.mark.parametrize("maxgroups, capacity", [
    ("8", 8), ("131072", 131_072), (None, 64),
])
def test_the_capacity_is_the_key_bound_else_four_batch_widths(
        tmp_path, maxgroups, capacity):
    extra = {"datax.job.process.maxgroups": maxgroups} if maxgroups else {}
    host = StreamingHost(_conf(tmp_path, extra=extra))
    try:
        st = host.processor.state_tables["totals"]
        assert st.capacity == capacity
        assert host.processor.state_data["totals"].valid.shape == (capacity,)
    finally:
        host.stop()
    assert state_capacity(16, None) == 64
    assert state_capacity(16, 8) == 8


def test_keys_past_the_bound_are_counted_not_silently_dropped(tmp_path):
    host = StreamingHost(_conf(tmp_path, extra={
        "datax.job.process.maxgroups": "8"}))
    try:
        _feed(tmp_path, "a", range(1, 7))
        m = host.run_batch()
        assert (m["State_Rows"], m["State_GroupsDropped"]) == (6.0, 0.0)
        _feed(tmp_path, "b", range(5, 13))  # 12 keys in all, 8 held
        m = host.run_batch()
        assert m["State_Rows"] == 8.0
        assert m["State_GroupsDropped"] == 4.0
        assert m["State_Write_Bytes"] > 0
        # the table kept its shape: the step was not traced again
        assert "Retrace_Count" not in m
        assert len(_totals(host)) == 8
    finally:
        host.stop()


def test_a_flow_without_a_state_table_reports_none_of_it(tmp_path):
    t = tmp_path / "plain.transform"
    t.write_text("--DataXQuery--\nOut = SELECT k, v FROM DataXProcessedInput\n")
    conf = dict(_conf(tmp_path).dict)
    conf["datax.job.process.transform"] = str(t)
    del conf["datax.job.process.statetable.totals.schema"]
    host = StreamingHost(SettingDictionary(conf))
    try:
        _feed(tmp_path, "a", [1, 2])
        m = host.run_batch()
    finally:
        host.stop()
    assert not any(k in m for k in ("State_Rows", "State_Write_Bytes",
                                    "State_GroupsDropped"))
    assert not host.processor.pipeline.state_views


def test_the_state_statements_run_under_their_own_scope(tmp_path):
    host = StreamingHost(_conf(tmp_path))
    try:
        pipeline = host.processor.pipeline
        assert pipeline.state_views == {"merged", "totals"}
        proc = host.processor
        text = proc._step.lower(*proc._step_input_avals()).as_text(
            debug_info=True)
    finally:
        host.stop()
    assert "dx.state.totals" in text and "dx.state.merged" in text
    assert "dx.view.totals" not in text and "dx.view.merged" not in text


def test_the_default_location_is_under_the_checkpoint_directory(tmp_path):
    host = StreamingHost(_conf(tmp_path))
    try:
        assert host.processor.state_tables["totals"].location == str(
            tmp_path / "ckpt" / "state" / "totals")
        _feed(tmp_path, "a", [1, 2])
        host.run_batch()
    finally:
        host.stop()
    assert sorted(os.listdir(tmp_path / "ckpt" / "state" / "totals")) == [
        f"p{p:02d}" for p in range(16)]
    # an explicit location is honoured as it was
    other = StreamingHost(_conf(tmp_path, extra={
        "datax.job.process.statetable.totals.location":
            str(tmp_path / "elsewhere")}))
    try:
        assert other.processor.state_tables["totals"].location == str(
            tmp_path / "elsewhere")
    finally:
        other.stop()


def test_two_jobs_with_their_own_checkpoints_share_no_state(tmp_path):
    """Two hosts that name the same table and checkpoint in different
    directories keep two tables."""
    a = StreamingHost(_conf(tmp_path, ckpt="ckpt-a"))
    try:
        _feed(tmp_path, "a", [1, 2])
        a.run_batch()
    finally:
        a.stop()
    os.remove(tmp_path / "in" / "a.json")
    b = StreamingHost(_conf(tmp_path, ckpt="ckpt-b"))
    try:
        assert _totals(b) == {}
        _feed(tmp_path, "b", [3])
        b.run_batch()
        assert _totals(b) == {3: 1.0}
    finally:
        b.stop()
    again = StreamingHost(_conf(tmp_path, ckpt="ckpt-a"))
    try:
        assert _totals(again) == {1: 1.0, 2: 1.0}
    finally:
        again.stop()


def test_a_restart_on_the_same_conf_resumes_the_committed_side(tmp_path):
    host = StreamingHost(_conf(tmp_path))
    try:
        _feed(tmp_path, "a", [1, 2, 2])
        host.run_batch()
    finally:
        host.stop()
    _feed(tmp_path, "b", [2, 3])
    host2 = StreamingHost(_conf(tmp_path))
    try:
        # the committed side came back, and the new file adds to it
        assert _totals(host2) == {1: 1.0, 2: 2.0}
        m = host2.run_batch()
        assert m["Input_DataXProcessedInput_Events_Count"] == 2.0
        assert _totals(host2) == {1: 1.0, 2: 3.0, 3: 1.0}
    finally:
        host2.stop()


@pytest.mark.parametrize("fault", ["sink", "commit", "write"])
def test_a_failed_sink_or_a_crash_before_the_commit_keeps_the_old_side(
        tmp_path, monkeypatch, fault):
    """Batch 2 writes its standby side; then its sink fails (the host
    never commits) or the process dies between the standby write and the
    pointer commit; or the standby write itself fails, and the commit
    that waits for it fails before any pointer moves. Either way a
    restarted host loads batch 1's side."""
    host = StreamingHost(_conf(tmp_path))
    try:
        _feed(tmp_path, "a", [1, 2])
        host.run_batch()
        committed = _totals(host)
        _feed(tmp_path, "b", [2, 3])

        def dies(*_a, **_k):
            raise RuntimeError(f"{fault} failed")

        st = host.processor.state_tables["totals"]
        if fault == "sink":
            monkeypatch.setattr(host.dispatcher, "dispatch", dies)
        else:
            monkeypatch.setattr(
                st, "persist" if fault == "commit" else "overwrite", dies)
        with pytest.raises(RuntimeError, match="failed"):
            host.run_batch()
        if fault != "write":
            # the standby side of batch 2 is on disk, uncommitted
            assert st._pending
    finally:
        host.stop()
    restarted = StreamingHost(_conf(tmp_path))
    try:
        assert _totals(restarted) == committed == {1: 1.0, 2: 1.0}
    finally:
        restarted.stop()


def test_the_standby_write_runs_beside_the_sinks(tmp_path, monkeypatch):
    """The batch's standby write does not hold its rows back from the
    sinks: a write that cannot end before the sinks have run still lets
    the batch land, and its pointers are committed after both."""
    import threading

    host = StreamingHost(_conf(tmp_path))
    try:
        st = host.processor.state_tables["totals"]
        sunk = threading.Event()
        overwrite, dispatch = st.overwrite, host.dispatcher.dispatch

        def write_after_the_sinks(*a, **k):
            assert sunk.wait(30), "the sinks waited for the standby write"
            return overwrite(*a, **k)

        def sinks(*a, **k):
            dispatch(*a, **k)
            sunk.set()

        monkeypatch.setattr(st, "overwrite", write_after_the_sinks)
        monkeypatch.setattr(host.dispatcher, "dispatch", sinks)
        _feed(tmp_path, "a", [1, 2, 2])
        m = host.run_batch()
        assert m["State_Write_Bytes"] > 0
        assert not st._pending
    finally:
        host.stop()
    restarted = StreamingHost(_conf(tmp_path))
    try:
        assert _totals(restarted) == {1: 1.0, 2: 2.0}
    finally:
        restarted.stop()
