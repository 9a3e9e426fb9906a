"""Device-plan analyzer tests.

- cost-model unit tests: the closed forms against hand-computed shapes
- abstract-eval purity: ``--device`` analysis derives shapes without
  executing anything (no real arrays are produced)
- the tier-1 drift gate (acceptance criterion): for every baseline
  config shape — including the flow ``__graft_entry__._build``
  builds — the predicted per-stage HBM footprint
  matches the arrays a real batch materializes, within the stated
  bound: EXACT byte equality (0 tolerance); the closed-form model, the
  ``jax.eval_shape`` derivation and the materialized arrays must agree.
"""

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from data_accelerator_tpu.analysis.costmodel import (
    ici_bytes_group,
    ici_bytes_join,
    row_bytes,
    stage_flops,
    stage_transient_bytes,
    table_bytes,
    view_output_bytes,
)
from data_accelerator_tpu.analysis.deviceplan import (
    analyze_processor,
    flow_plan_from_processor,
    materialized_stage_bytes,
)
from data_accelerator_tpu.compile.planner import JoinSite, StagePlan
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.processor import FlowProcessor

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {"useCurrentTimeMillis": True}},
]})


# ---------------------------------------------------------------------------
# closed forms vs hand-computed shapes
# ---------------------------------------------------------------------------
class TestCostModelClosedForms:
    def test_table_bytes_by_width(self):
        # 100 rows: long 4B + double 4B + boolean 1B + valid 1B per row
        types = {"a": "long", "b": "double", "c": "boolean"}
        assert table_bytes(types, 100) == 400 + 400 + 100 + 100
        assert row_bytes(types) == 4 + 4 + 1 + 1

    def test_view_output_bytes_overflow_columns(self):
        types = {"k": "long", "c": "long"}
        rows = 64
        base = 4 * rows + 4 * rows + rows  # two int32 cols + valid
        grouped = StagePlan(kind="group", input_rows=256, output_rows=rows,
                            grouped=True, groups_bound=rows)
        # grouped: + __overflow.groups (int32 per row)
        assert view_output_bytes(types, grouped, rows) == base + 4 * rows
        site = JoinSite(kind="INNER", right_table="r", left_rows=256,
                        right_rows=64, out_rows=rows,
                        algorithm="sort-merge", n_eq_keys=1,
                        has_residual=False)
        joined = StagePlan(kind="project", input_rows=rows, output_rows=rows,
                           joins=(site,))
        # joined: + __overflow.joins
        assert view_output_bytes(types, joined, rows) == base + 4 * rows
        union = StagePlan(kind="union", input_rows=2 * rows,
                          output_rows=rows, joins=(site,), union_branches=2)
        # union concat keeps only schema columns
        assert view_output_bytes(types, union, rows) == base
        assert view_output_bytes(types, None, rows) == base

    def test_ici_group_closed_form(self):
        # N=1000 rows, 1 key + 2 aggregates shuffle at (C-1)/C; G=64
        # groups all-gather to C-1 peers at 13 B/row
        got = ici_bytes_group(1000, 1, 2, 64, 13, 16)
        assert got == pytest.approx(
            1000 * 4 * 3 * 15 / 16 + 64 * 13 * 15
        )
        assert ici_bytes_group(1000, 1, 2, 64, 13, 1) == 0.0

    def test_ici_join_closed_form(self):
        # sort-merge: (n+m) keys shuffle; out all-gathers
        got = ici_bytes_join(100, 900, 2, 50, 9, 8)
        assert got == pytest.approx(1000 * 4 * 2 * 7 / 8 + 50 * 9 * 7)
        # match-matrix: right side broadcasts whole rows instead
        got = ici_bytes_join(100, 900, 1, 50, 9, 8,
                             match_matrix=True, right_row_bytes=13)
        assert got == pytest.approx(900 * 13 * 7 + 50 * 9 * 7)

    def test_flops_match_matrix_dominates(self):
        site = JoinSite(kind="INNER", right_table="w", left_rows=1 << 12,
                        right_rows=1 << 14, out_rows=1 << 14,
                        algorithm="match-matrix", n_eq_keys=1,
                        has_residual=True)
        p = StagePlan(kind="project", input_rows=1 << 14,
                      output_rows=1 << 14, joins=(site,))
        # n*m*(eq+residual) pairs dominate the estimate
        assert stage_flops(p, 3) >= (1 << 26) * 2
        # the [n, m] bool mask + two int32 index grids are transient
        assert stage_transient_bytes(p) == (1 << 26) * (1 + 8)

    def test_flops_sort_merge_is_loglinear(self):
        site = JoinSite(kind="INNER", right_table="w", left_rows=1 << 12,
                        right_rows=1 << 14, out_rows=1 << 14,
                        algorithm="sort-merge", n_eq_keys=1,
                        has_residual=False)
        p = StagePlan(kind="project", input_rows=1 << 14,
                      output_rows=1 << 14, joins=(site,))
        nm = (1 << 12) + (1 << 14)
        # (n+m)log2(n+m) + out + projection — far off the n*m cliff
        assert stage_flops(p, 3) < nm * 20 + (1 << 14) + (1 << 14) * 3 + 1
        assert stage_transient_bytes(p) == 0


# ---------------------------------------------------------------------------
# baseline-config drift gate (tier-1 acceptance)
# ---------------------------------------------------------------------------
def _conf(tmp_path, transform, extra=None, capacity=64):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "flow.transform"
    t.write_text(transform)
    d = {
        "datax.job.name": "DevPlan",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": str(capacity),
    }
    d.update(extra or {})
    return SettingDictionary(d)


BASELINE_TRANSFORMS = {
    # config 1: projection -> threshold filter (the bench alerting shape)
    "filter": (
        "--DataXQuery--\n"
        "Hot = SELECT deviceId, temperature FROM DataXProcessedInput "
        "WHERE temperature > 50\n",
        {},
    ),
    # config 2: tumbling-window COUNT/AVG over the windowed table
    "window_agg": (
        "--DataXQuery--\n"
        "WinAgg = SELECT deviceId, COUNT(*) AS Cnt, "
        "AVG(temperature) AS AvgT "
        "FROM DataXProcessedInput_10seconds GROUP BY deviceId\n",
        {"datax.job.process.timewindow.DataXProcessedInput_10seconds"
         ".windowduration": "10 seconds"},
    ),
    # config 3: accumulator + sliding-window join (+ UNION)
    "state_join": (
        "--DataXQuery--\n"
        "peaks_in = SELECT deviceId, temperature AS peak "
        "FROM DataXProcessedInput WHERE temperature > 50\n"
        "--DataXQuery--\n"
        "merged = SELECT deviceId, peak FROM peaks_in "
        "UNION ALL SELECT deviceId, peak FROM peaks\n"
        "--DataXQuery--\n"
        "peaks = SELECT deviceId, MAX(peak) AS peak FROM merged "
        "GROUP BY deviceId\n"
        "--DataXQuery--\n"
        "Joined = SELECT a.deviceId, a.temperature, "
        "b.temperature AS prior "
        "FROM DataXProcessedInput a INNER JOIN "
        "DataXProcessedInput_5seconds b ON a.deviceId = b.deviceId "
        "WHERE b.temperature < a.temperature\n",
        {"datax.job.process.timewindow.DataXProcessedInput_5seconds"
         ".windowduration": "5 seconds",
         "datax.job.process.statetable.peaks.schema":
             "deviceId long, peak double"},
    ),
    # config 5: high-fanout group-by under a conf'd maxgroups bound
    "fanout_groupby": (
        "--DataXQuery--\n"
        "Fanout = SELECT deviceId, COUNT(*) AS Cnt, "
        "SUM(temperature) AS S FROM DataXProcessedInput "
        "GROUP BY deviceId\n",
        {"datax.job.process.maxgroups": "32"},
    ),
}


@pytest.mark.parametrize("shape", sorted(BASELINE_TRANSFORMS),
                         ids=sorted(BASELINE_TRANSFORMS))
def test_predicted_hbm_matches_materialized(tmp_path, shape):
    """Acceptance gate: predicted per-stage HBM (closed-form model AND
    eval_shape derivation) equals the bytes a real batch materializes.
    Stated bound: exact equality, every stage."""
    transform, extra = BASELINE_TRANSFORMS[shape]
    st = {k: v for k, v in extra.items()}
    if "datax.job.process.statetable.peaks.schema" in st:
        st["datax.job.process.statetable.peaks.location"] = str(
            tmp_path / "state"
        )
    proc = FlowProcessor(_conf(tmp_path / shape, transform, st))
    report = analyze_processor(proc, chips=16)
    assert report.ok, [d.render() for d in report.errors]

    bundle = flow_plan_from_processor(proc)
    measured = materialized_stage_bytes(bundle)  # real arrays, real run
    assert set(measured) == {s.name for s in report.stages}
    for s in report.stages:
        assert s.hbm_bytes == measured[s.name], (
            f"{shape}/{s.name}: eval_shape {s.hbm_bytes} != "
            f"materialized {measured[s.name]}"
        )
        assert s.model_bytes == measured[s.name], (
            f"{shape}/{s.name}: closed-form {s.model_bytes} != "
            f"materialized {measured[s.name]}"
        )


def test_bench_flow_model_matches_materialized():
    """The flow __graft_entry__._build builds (both
    the single-source headline flow and the two-source windowed-join
    variant) passes the same exact-byte drift gate."""
    import __graft_entry__ as ge

    for multi in (False, True):
        proc = ge._build(batch_capacity=64, multi=multi)
        report = analyze_processor(proc, chips=16)
        assert report.ok, [d.render() for d in report.errors]
        bundle = flow_plan_from_processor(proc)
        measured = materialized_stage_bytes(bundle)
        for s in report.stages:
            assert s.hbm_bytes == measured[s.name] == s.model_bytes, (
                f"multi={multi} {s.name}: model {s.model_bytes}, "
                f"lowered {s.hbm_bytes}, real {measured[s.name]}"
            )
        # the cost report covers every pipeline view by name
        view_names = {v.name for v in proc.pipeline.views}
        assert view_names <= {s.name for s in report.stages}


def test_abstract_eval_produces_no_arrays(tmp_path):
    """--device analysis must not execute: every derived stage shape
    comes from jax.eval_shape (ShapeDtypeStructs), never from device
    buffers. Guarded by running under a trace-blocking callback."""
    transform, extra = BASELINE_TRANSFORMS["window_agg"]
    proc = FlowProcessor(_conf(tmp_path, transform, extra))

    calls = {"n": 0}
    orig = jax.eval_shape

    def counting_eval_shape(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    jax.eval_shape = counting_eval_shape
    try:
        report = analyze_processor(proc)
    finally:
        jax.eval_shape = orig
    # one eval_shape per compiled view (projection + transform), and one
    # for the combined groups of a window held as partial aggregates
    n_views = sum(len(v) for v in proc.projection_views.values()) + len(
        proc.pipeline.views
    )
    assert len(proc.window_states) == 1
    assert calls["n"] == n_views + len(proc.window_states)
    assert report.stages


def test_sampled_cardinality_feeds_device_lints():
    """Schema inference records sampled value sets as ``allowedValues``
    metadata; a flow built on the inferred schema trips DX200/DX202
    when its configured capacities sit below the SAMPLED cardinality —
    the designer path: infer schema -> save flow -> Validate."""
    from data_accelerator_tpu.analysis import analyze_flow_device
    from data_accelerator_tpu.serve.schemainference import infer_schema

    events = [
        {"site": f"site{i % 8}", "deviceId": i % 40, "temperature": 1.0 * i}
        for i in range(100)
    ]
    schema = infer_schema(events)
    by = {f["name"]: f for f in schema["fields"]}
    assert len(by["site"]["metadata"]["allowedValues"]) == 8
    assert len(by["deviceId"]["metadata"]["allowedValues"]) == 40

    gui = {
        "name": "sampled",
        "input": {"mode": "streaming", "type": "local", "properties": {
            "inputSchemaFile": json.dumps(schema),
            "normalizationSnippet": "Raw.*",
        }},
        "process": {
            "queries": [
                "--DataXQuery--\nAgg = SELECT site, deviceId, COUNT(*) AS c "
                "FROM DataXProcessedInput GROUP BY site, deviceId;\n"
                "OUTPUT Agg TO Metrics;"
            ],
            "jobconfig": {
                "jobBatchCapacity": "1024",
                "maxGroups": "16",  # sampled cardinality 8*40 = 320
                "stringDictionaryMaxSize": "4",  # 8 sampled site strings
            },
        },
        "outputs": [{"id": "Metrics", "type": "metric", "properties": {}}],
    }
    report = analyze_flow_device(gui)
    codes = [d.code for d in report.diagnostics]
    assert "DX200" in codes, codes
    assert "DX202" in codes, codes


def test_device_report_ici_scales_with_chips(tmp_path):
    """The ICI model is a closed form over the chip count: 1 chip moves
    nothing, and the gather term grows with (chips - 1)."""
    transform, extra = BASELINE_TRANSFORMS["window_agg"]
    proc = FlowProcessor(_conf(tmp_path, transform, extra))
    r1 = analyze_processor(proc, chips=1)
    r16 = analyze_processor(proc, chips=16)
    r32 = analyze_processor(proc, chips=32)
    assert r1.totals()["iciBytesPerBatch"] == 0.0
    assert 0 < r16.totals()["iciBytesPerBatch"] < r32.totals()["iciBytesPerBatch"]
