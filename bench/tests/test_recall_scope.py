"""recall_at_10 is read only in the cells its entry lists: a cell that
does not report it pays no exhaustive top-k, while every number that
decides ``correct`` is still read.
Driven through whole runs at a CPU size, on a checkout whose
BENCHMARK.json lists one batch cell under recall_at_10."""
import json
import math
import os

import pytest

import harness
from conftest import make_root

LISTED, UNLISTED = "deepfm-twitch.batch", "mlp-twitch.batch"
CHECKS = {"score_gap", "search_miss", "bad_rows", "missing"}


@pytest.fixture(scope="module")
def scoped_root(tmp_path_factory):
    """recall_at_10 lists LISTED alone."""
    root = make_root(str(tmp_path_factory.mktemp("scoped")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for m in spec["end_to_end"]:
        if m["name"] == "recall_at_10":
            m["workloads"] = [LISTED]
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


def _run(root, cell):
    return harness.run_cell(cell, 2**32 + 9, 1.0, False, 0.0, root=root,
                            require_tpu=False)


def test_unlisted_cell_runs_no_exhaustive_top_k(scoped_root, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("exhaustive top-k in a cell without recall")
    monkeypatch.setattr(harness.Reference, "topk", refuse)
    res, lines = _run(scoped_root, UNLISTED)
    text = "\n".join(lines)
    assert res["correct"], text
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert set(res["checks"]) == CHECKS
    assert all(math.isfinite(c["value"]) for c in res["checks"].values())
    assert lines == [f"check {k}: {c['value']} limit {c['limit']}"
                     for k, c in res["checks"].items()]


def test_listed_cell_still_gets_recall(scoped_root, monkeypatch):
    scanned = []
    topk = harness.Reference.topk

    def spy(self, params, queries, k, *args, **kwargs):
        scanned.append(len(queries))
        return topk(self, params, queries, k, *args, **kwargs)
    monkeypatch.setattr(harness.Reference, "topk", spy)
    res, lines = _run(scoped_root, LISTED)
    assert res["correct"], "\n".join(lines)
    assert 0 < res["metrics"]["recall_at_10"]["value"] <= 1
    assert set(res["checks"]) == CHECKS
    # the exhaustive top-k covers score_gap's sample
    assert scanned == [min(harness.SAMPLE, res["attempted"])]
