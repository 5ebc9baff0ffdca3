"""A new configuration, traffic mix and per-layer metric need only new
files under bench/: the harness finds each by the name BENCHMARK.json
gives it. Every declared metric has its reader, and names only declared
cells."""
import json
import os

import pytest

import harness
from conftest import ROOT, make_root

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = {w["name"] for w in SPEC["workloads"]}
ENGINE_STAGES = [f"{s}_us.batch" for s in
                 ("pop", "grad", "rank", "measure", "insert", "loop",
                  "init")] + ["unscoped_share.batch"]


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_its_reader_and_names_known_cells(metric):
    path = os.path.join(ROOT, "bench", "metrics", metric["name"] + ".py")
    assert callable(harness.load_module(path).read)
    assert metric["workloads"] and set(metric["workloads"]) <= CELLS


@pytest.mark.parametrize("metric", SPEC["end_to_end"],
                         ids=[m["name"] for m in SPEC["end_to_end"]])
def test_end_to_end_metric_names_known_cells(metric):
    assert set(metric.get("workloads", CELLS)) <= CELLS


@pytest.mark.parametrize("name", ENGINE_STAGES)
def test_engine_stage_metric_is_declared_for_every_batch_cell(name):
    metric = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert (metric["layer"], metric["source"], metric["moves"]) == (
        "engine", "device_trace", "qps")
    assert set(metric["workloads"]) == {c for c in CELLS
                                        if c.endswith(".batch")}


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(str(tmp_path))
    with open(os.path.join(root, "bench/configs/deepfm-twitch.json")) as f:
        cfg = json.load(f)
    new_files = {
        "bench/configs/new-cfg.json": json.dumps(dict(
            cfg, name="new-cfg",
            corpus=dict(cfg["corpus"], items=1500, users=300))),
        "bench/traffic/new-mix.json": json.dumps(
            {"driver": "closed_batch", "batch": 64}),
        "bench/metrics/new_metric.py": "def read(ctx):\n    return 42.0\n"}
    for rel, body in new_files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(body)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append(dict(spec["configs"][0], name="new-cfg",
                                file="bench/configs/new-cfg.json"))
    spec["workloads"].append({"name": "new-cfg.new-mix",
                              "config": "new-cfg", "traffic": "new-mix",
                              "chips": 1, "why": "discovery test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "qps",
                              "workloads": ["new-cfg.new-mix"]})
    for m in spec["end_to_end"]:
        if m["name"] == "qps":
            m["workloads"].append("new-cfg.new-mix")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.find_cell("new-cfg.new-mix", root)
    assert cell.config["corpus"]["items"] == 1500
    assert cell.traffic["batch"] == 64
    # recall_at_10 lists its cells, and a new cell is not among them
    assert [m["name"] for m in cell.end_to_end] == ["qps", "setup_s"]
    assert list(cell.readers) == ["new_metric"]

    res, lines = harness.run_cell("new-cfg.new-mix", 3, 0.5, True, 0.0,
                                  root=root, require_tpu=False)
    assert res["correct"], lines
    assert res["metrics"]["new_metric"] == {"value": 42.0, "unit": "%"}
    res, _ = harness.run_cell("new-cfg.new-mix", 3, 0.5, False, 0.0,
                              root=root, require_tpu=False)
    assert set(res["metrics"]) == {"qps", "setup_s"}


def test_driver_that_never_marks_its_last_answer_is_refused(tmp_path):
    """The log line of the window's compiles is written by
    ``env.last_answer()``; a driver that returns without calling it
    fails the run instead of dropping the line."""
    root = make_root(str(tmp_path))
    new_files = {
        "bench/traffic/silent.json": json.dumps({"driver": "silent"}),
        "bench/drivers/silent.py": "def run(env):\n    return {}\n"}
    for rel, body in new_files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(body)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "deepfm-twitch.silent",
                              "config": "deepfm-twitch", "traffic": "silent",
                              "chips": 1, "why": "discovery test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with pytest.raises(RuntimeError, match="last_answer"):
        harness.run_cell("deepfm-twitch.silent", 3, 0.5, False, 0.0,
                         root=root, require_tpu=False)
