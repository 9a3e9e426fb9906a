"""BENCHMARK.json and every file it points to, held to the rules that
refuse a manifest before any run (PR 22 was refused for a layer's name)."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}\Z")
LAYERS = {"generator", "host_loop", "source_decode", "device_step",
          "kernels", "result_path", "sinks_checkpoint", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


MANIFEST = load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def reporting(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark_checks"]
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    # the full check with all 24 cells a benchmark may grow to has to fit
    runs = 2 + 14 * 24
    assert runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_every_name_obeys_the_one_rule(kind):
    names = [e["name"] for e in MANIFEST[kind]]
    assert len(set(names)) == len(names)
    for n in names:
        assert NAME.match(n), n
    for e in MANIFEST[kind]:
        for key in ("config", "traffic", "layer", "moves"):
            if key in e:
                assert NAME.match(e[key]), (e["name"], key, e[key])
        for r in e.get("reduced", []):
            assert NAME.match(r), r
        for key in ("why", "source", "layer"):
            if key in e:
                assert 1 <= len(e[key]) <= 200, (e["name"], key)
                assert "\n" not in e[key] and "\t" not in e[key]


def test_entries_have_just_the_keys_of_the_contract():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for e in MANIFEST["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in MANIFEST["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in SOURCES
    for m in METRICS:
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_layers_are_the_eight_of_perf_md():
    assert {p["layer"] for p in MANIFEST["per_layer"]} <= LAYERS
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        perf = f.read()
    for layer in {p["layer"] for p in MANIFEST["per_layer"]}:
        assert f"`{layer}`" in perf, layer


def test_each_layer_metric_moves_one_end_to_end_metric_its_cells_report():
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    for p in MANIFEST["per_layer"]:
        assert p["moves"] in e2e, p["name"]
        assert reporting(p), p["name"]
        for cell in reporting(p):
            assert cell in CELLS, (p["name"], cell)
            assert cell in reporting(e2e[p["moves"]]), (p["name"], cell)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    assert "workloads" not in next(
        e for e in MANIFEST["end_to_end"] if e["name"] == "setup_s")
    for cell in CELLS:
        e2e = [e["name"] for e in MANIFEST["end_to_end"]
               if cell in reporting(e)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in reporting(p) for p in MANIFEST["per_layer"]), cell


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_files(config):
    assert config["file"].startswith("benchmark/configs/")
    assert PATH.match(config["file"])
    body = load(os.path.join(ROOT, config["file"]))
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in body, key  # the file states every cut it lists
    assert set(body["guarantees"]) == {"delivery", "window_contents",
                                       "watermark"}
    assert os.path.exists(os.path.join(BENCH, "flows", body["flow"] + ".py"))
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_files(cell):
    mix = load(os.path.join(BENCH, "workloads", cell["name"] + ".json"))
    assert mix["config"] == cell["config"]
    assert mix["chips"] == cell["chips"]
    assert mix["why"] == cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    assert mix["traffic"]["mode"] in ("paced", "backlog")
    if mix["traffic"]["mode"] == "paced":
        # the rate its sweep found, and the sweep's readings
        # (0.8 x the knee, unless the file says why it stands lower)
        found = mix["sweep"]
        share = found.get("offered_share", 0.8)
        assert 0.7 <= share <= 0.8
        assert share == 0.8 or found["why_not_0.8"]
        assert mix["traffic"]["rate_events_per_s"] == pytest.approx(
            share * found["highest_sustained_events_per_s"], rel=0.01)
        assert len(found["readings"]) >= 4


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda p: p["name"])
def test_layer_reader_files(metric):
    base = os.path.join(BENCH, "layers", metric["name"])
    assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
    if os.path.exists(base + ".json"):
        body = load(base + ".json")
        for key in ("name", "unit", "layer", "moves", "workloads", "source"):
            assert body[key] == metric[key], (metric["name"], key)
        assert body["reader"]["from"] in (
            "span", "measurement", "generator", "trace", "roofline")


def test_files_under_paths_are_named_from_a_names_characters():
    for top in MANIFEST["paths"]:
        for folder, _dirs, files in os.walk(os.path.join(ROOT, top)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert re.match(r"[A-Za-z0-9_./-]+\Z", rel), rel
