"""Fixtures of the harness's self-tests: a throwaway checkout root that
holds a copy of bench/ and a BENCHMARK.json whose cells are the real ones
cut to a CPU size. Run them from the repository root with

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"items": 3000, "users": 500}

SERVE = "deepfm-twitch.serve-poisson"
# The open-loop serving cell, proven correct on the chip but held out of
# BENCHMARK.json because its p95 does not hold still from run to run
# (PERF.md, Open questions 0). Its driver, traffic file and readers stay
# under bench/, and these tests run it as if it were declared.
HELD_OUT = {
    "workloads": [
        {"name": SERVE, "config": "deepfm-twitch", "traffic": "serve-poisson",
         "chips": 1, "why": "online service: open-loop Poisson users into "
         "the continuous runtime"}],
    "end_to_end": [
        {"name": "p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": [SERVE]}],
    "per_layer": [
        {"name": name, "unit": "ms" if name.endswith("ms.serve") else "%",
         "better": "lower", "source": source, "layer": layer,
         "moves": "p95_ms", "workloads": [SERVE]}
        for name, source, layer in (
            ("idle_share.serve", "device_trace", "device"),
            ("queue_p95_ms.serve", "program_span", "serving runtime"),
            ("gen_late_p95_ms.serve", "host_clock", "load generator"))],
}


def make_root(dst: str) -> str:
    """A checkout root under ``dst``: BENCHMARK.json (with the HELD_OUT
    cell) and bench/ without caches and tests, every configuration cut to
    TINY items and users and the serving rate to what a CPU sustains."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "tests",
                                                  "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for key, entries in HELD_OUT.items():
        spec[key] += entries
    for c in spec["configs"]:
        path = os.path.join(dst, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["corpus"].update(TINY)
        with open(path, "w") as f:
            json.dump(cfg, f)
    for name in os.listdir(os.path.join(dst, "bench", "traffic")):
        path = os.path.join(dst, "bench", "traffic", name)
        with open(path) as f:
            tr = json.load(f)
        if "rate_qps" in tr:
            tr.update(rate_qps=20, drain_s=5)
        with open(path, "w") as f:
            json.dump(tr, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("checkout")))
