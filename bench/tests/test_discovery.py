"""A new configuration, traffic mix and per-layer metric need only new
files under bench/: the harness finds each by the name BENCHMARK.json
gives it."""
import json
import os

import harness
from conftest import make_root


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
    assert [m["name"] for m in cell.end_to_end] == [
        "qps", "recall_at_10", "setup_s"]
    assert list(cell.readers) == ["new_metric"]

    res, lines = harness.run_cell("new-cfg.new-mix", 3, 0.5, True, 0.0,
                                  root=root, require_tpu=False)
    assert res["correct"], lines
    assert res["metrics"]["new_metric"] == {"value": 42.0, "unit": "%"}
    res, _ = harness.run_cell("new-cfg.new-mix", 3, 0.5, False, 0.0,
                              root=root, require_tpu=False)
    assert set(res["metrics"]) == {"qps", "recall_at_10", "setup_s"}
