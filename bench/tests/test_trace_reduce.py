"""bench/trace_reduce.py on a trace recorded on a TPU v5e: the batch
driver at 3,000 items and 32 queries a batch, one traced batch
(tests/data/batch_tiny.*; its engine counters beside it)."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "batch_tiny.xplane.pb.gz")) as f:
        return ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def reduced(profile):
    return tr.reduce_profile(profile)


@pytest.mark.parametrize("name,base", [
    ("%neighbor_rank.6 = f32[256,48]{1,0:T(8,128)S(1)} custom-call(f32[256,"
     "40]{1,0:T(8,128)S(1)} %x)", "neighbor_rank"),
    ("%deepfm_score.8 = f32[2048,1]{1,0} custom-call(...)", "deepfm_score"),
    ("%while.6 = (f32[2]) while(%t), condition=%c, body=%b", "while"),
    ("%copy-start.21 = (f32[64]) copy-start(f32[64] %a)", "copy-start"),
    ("%fusion.154 = pred[16384]{0:T(1024)} fusion(pred[256,8] %g), "
     "kind=kCustom", "fusion")])
def test_op_base(name, base):
    assert tr.op_base(name) == base


def test_union():
    assert tr.union([(3, 4), (0, 2), (1, 2.5), (4, 5)]) == [(0, 2.5), (3, 5)]


def test_window_and_busy(profile, reduced):
    w, busy = reduced["window_s"], reduced["busy_s"]
    assert 0 < busy <= w
    # an independent bound: the union of the device's program executions
    # (the XLA Modules line) in the same window covers every op
    mods = []
    w0 = w1 = None
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tr.WINDOW:
                    w0, w1 = ev.start_ns, ev.start_ns + ev.duration_ns
    for plane in profile.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(max(e.start_ns, w0),
                              min(e.start_ns + e.duration_ns, w1))
                             for e in line.events
                             if e.start_ns + e.duration_ns > w0
                             and e.start_ns < w1]
    busy_mod = sum(e - s for s, e in tr.union(mods)) * 1e-9
    assert busy <= busy_mod * (1 + 1e-9)
    assert busy >= 0.9 * busy_mod


def test_kernels_and_breakdown(reduced):
    ops = reduced["op_s"]
    for k in ("neighbor_rank", "deepfm_score", "deepfm_grad"):
        assert 0 < ops[k] < reduced["busy_s"]
    assert "while" not in ops
    for key in ("device_ops", "idle_gaps"):
        vals = [v for _, v in reduced[key]]
        assert 0 < len(vals) <= 10
        assert vals == sorted(vals, reverse=True)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(v for _, v in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-6, abs=1e-9)


def test_roofline_shares_stay_under_100(reduced):
    import harness
    import work
    with open(os.path.join(DATA, "batch_tiny.traced.json")) as f:
        traced = json.load(f)
    cell = harness.find_cell("deepfm-twitch.batch")
    peak = harness.load_peak("TPU v5 lite")
    ctx = harness.MetricContext(cell, {"traced": traced}, reduced, peak, 48)
    for k in ("neighbor_rank", "deepfm_score", "deepfm_grad"):
        share = work.roofline_share(ctx, k)
        assert 0 < share < 100


@pytest.mark.parametrize("spans, own", [
    # a loop (container) around two ops and a gap, then an op after it
    ([(0, 10, True), (1, 4, False), (6, 9, False), (10, 12, False)],
     [4, 3, 3, 2]),
    # a loop in a loop: each gives up only what lies directly inside it
    ([(0, 10, True), (1, 8, True), (2, 5, False), (8, 9, False)],
     [2, 4, 3, 1]),
    # an op that overlaps another op takes nothing from it
    ([(0, 10, True), (1, 6, False), (2, 4, False)], [3, 5, 2]),
    # children that overlap in a loop leave it a negative own time
    ([(0, 4, True), (0, 3, False), (1, 4, False)], [-2, 3, 3]),
])
def test_own_times(spans, own):
    assert tr._own_times(spans) == own


def test_instruction_time_adds_up_to_the_busy_time(reduced):
    assert reduced["overlap_s"] == 0
    assert sum(reduced["instr_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    ops = sum(v for (_, name), v in reduced["instr_s"].items()
              if tr.op_base(name) not in tr.CONTAINERS)
    assert ops == pytest.approx(sum(reduced["op_s"].values()), rel=1e-9)
