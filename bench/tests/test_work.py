"""bench/work.py against hand counts at both configurations' widths."""
import json
import os

import pytest

import harness
import work
from conftest import BENCH

# DeepFM, fm 8 / deep 32, MLP 64-64-1 over [q_deep, x_deep] (64 wide):
#   FM dot 2*8 + 2*64*64 + 2*64*64 + 2*64*1 = 16 + 8192 + 8192 + 128
# MLP measure over [x, q] (80 wide), 64-64-1:
#   2*80*64 + 2*64*64 + 2*64*1 = 10240 + 8192 + 128
HAND_FORWARD = {"deepfm-twitch": 16528, "mlp-twitch": 18560}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    ref = harness.load_module(os.path.join(
        BENCH, "references", cfg["measure"]["family"] + ".py"))
    return cfg, ref


@pytest.mark.parametrize("name", sorted(HAND_FORWARD))
def test_forward_flops(name):
    cfg, ref = _config(name)
    assert work.forward_flops(ref, cfg["measure"]) == HAND_FORWARD[name]


@pytest.mark.parametrize("name", sorted(HAND_FORWARD))
def test_role_work(name):
    cfg, ref = _config(name)
    f = HAND_FORWARD[name]
    counts = {"lane_steps": 3, "n_eval": 5, "n_grad": 7}
    # rank: per lane-step 48 rows x (5 x 40) + 2 x 40 FLOPs; bytes: 48 item
    # rows of 160 B, the node and its gradient (2 x 160 B), 48 scores
    assert work.role_work("rank", ref, cfg["measure"], counts, 48) == (
        3 * (48 * 200 + 80), 3 * (48 * 160 + 320 + 48 * 4))
    # score: item row + user row (40 + 40 floats) + one score
    assert work.role_work("score", ref, cfg["measure"], counts, 48) == (
        5 * f, 5 * 324)
    # grad: both rows, the value and the 40-wide gradient row
    assert work.role_work("grad", ref, cfg["measure"], counts, 48) == (
        7 * 2 * f, 7 * 484)
    assert work.measure_flops(ref, cfg["measure"], 5, 7) == 5 * f + 14 * f


def test_least_time_and_share():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s(50, 1, peak) == (0.5, "compute")
    assert work.least_time_s(1, 50, peak) == (5.0, "memory")

    cfg, ref = _config("deepfm-twitch")
    traced = {"lane_steps": 1000, "n_eval": 0, "n_grad": 0}
    flops, nbytes = work.role_work("rank", ref, cfg["measure"], traced, 48)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t_kernel = 4 * nbytes / 819e9
    cell = harness.Cell("c", 1, cfg, {}, None, ref, None, [], [], {}, "")
    ctx = harness.MetricContext(cell, {"traced": traced},
                                {"op_s": {"neighbor_rank": t_kernel}},
                                peak, 48)
    assert work.roofline_share(ctx, "neighbor_rank") == pytest.approx(25.0)
    assert work.roofline_share(ctx, "mlp_score") is None
    ctx.trace["op_s"] = {}
    assert work.roofline_share(ctx, "neighbor_rank") is None
