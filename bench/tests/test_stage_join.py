"""Device time per engine stage, joined from the recorded TPU v5e trace
(tests/data/batch_tiny.xplane.pb.gz: the batch driver at 3,000 items, 32
queries a batch) and the stage map of the same search compiled for a
described v5e (tests/data/batch_tiny.stages.json, from
``repro.obs.profile.stage_map`` of ``ExpansionEngine.compiled_text`` at the
trace's shapes). A trace names each op by its HLO instruction and each
program by its module, so the map prices every op of the window
(``trace_reduce``'s ``instr_s`` through ``stages.stage_seconds``), and the
per-stage readers read it."""
import gzip
import json
import os
import re

import pytest

import harness
import stages as st
import trace_reduce as tr
from conftest import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")
LOOP = ("pop", "grad", "rank", "measure", "insert", "loop")
STAGES = LOOP + ("init",)
READERS = [f"{s}_us.batch" for s in STAGES] + ["unscoped_share.batch"]


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "batch_tiny.xplane.pb.gz")) as f:
        return ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def stages():
    with open(os.path.join(DATA, "batch_tiny.stages.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced():
    with open(os.path.join(DATA, "batch_tiny.traced.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def steps(traced):
    return traced["steps"]


@pytest.fixture(scope="module")
def reduced(profile):
    return tr.reduce_profile(profile)


@pytest.fixture(scope="module")
def stage_of(stages):
    """The map as the batch driver hands it to the readers."""
    return {(stages["module"], n): s for n, s in stages["stages"].items()}


@pytest.fixture(scope="module")
def joined(reduced, stage_of):
    return st.stage_seconds(reduced["instr_s"], stage_of)


def test_every_op_of_the_search_is_in_the_map(reduced, stages):
    ops = [name for mod, name in reduced["instr_s"]
           if mod == stages["module"]]
    assert len(ops) > 100
    assert [n for n in ops if n not in stages["stages"]] == []


def test_stage_time_adds_up_to_the_busy_time(reduced, joined, steps):
    """Stage µs per engine step times the steps, plus the unscoped time,
    is the window's busy time: every op's time, and each loop's own time
    between its ops, is in one stage or unscoped. Every loop stage has
    some."""
    busy = reduced["busy_s"]
    assert set(joined) <= set(STAGES) | {st.UNSCOPED}
    per_step = {k: 1e6 * v / steps for k, v in joined.items()
                if k != st.UNSCOPED}
    total = sum(per_step.values()) * steps * 1e-6 + joined[st.UNSCOPED]
    assert total == pytest.approx(busy, rel=1e-3)
    for stage in LOOP:
        assert per_step[stage] > 0, stage
    assert 100 * joined[st.UNSCOPED] / busy < 5


def test_readers_on_the_recorded_trace(reduced, stage_of, traced):
    """The eight engine-stage readers of a batch cell read the recorded
    trace: the stages per step plus the unscoped time per step are the
    step's wall time less its idle share."""
    cell = harness.find_cell("deepfm-twitch.batch", ROOT)
    ctx = harness.MetricContext(cell, {"traced": traced, "stages": stage_of},
                                reduced, None, 48)
    got = {n: cell.readers[n].read(ctx) for n in READERS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    step = cell.readers["step_us.batch"].read(ctx)
    idle = cell.readers["idle_share.batch"].read(ctx)
    unscoped = (got["unscoped_share.batch"] / 100 * reduced["busy_s"] * 1e6
                / traced["steps"])
    total = sum(got[f"{s}_us.batch"] for s in STAGES) + unscoped
    assert total == pytest.approx(step * (1 - idle / 100), rel=0.01)
    assert got["unscoped_share.batch"] < 1


def test_readers_read_nothing_untraced():
    cell = harness.find_cell("deepfm-twitch.batch", ROOT)
    ctx = harness.MetricContext(cell, {}, None, None, 48)
    for name in READERS:
        assert cell.readers[name].read(ctx) is None


def test_the_neighbour_gather_is_the_rank_stage(profile, reduced, stages):
    # 32 lanes x 48 neighbours of 40 floats, gathered from the corpus
    names = {n for mod, n in reduced["instr_s"]
             if mod == stages["module"]}
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    gathers = {tr.op_instr(ev.name)
               for ln in plane.lines if ln.name == tr.OPS_LINE
               for ev in ln.events
               if re.search(r"= f32\[1536,40\]\S* fusion\(f32\[3000,40\]",
                            ev.name)}
    assert gathers and gathers <= names
    assert {stages["stages"][g] for g in gathers} == {"rank"}
