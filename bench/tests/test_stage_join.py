"""Device time per engine stage, joined from the recorded TPU v5e trace
(tests/data/batch_tiny.xplane.pb.gz: the batch driver at 3,000 items, 32
queries a batch) and the stage map of the same search compiled for a
described v5e (tests/data/batch_tiny.stages.json, from
``repro.obs.profile.stage_map`` of ``ExpansionEngine.compiled_text`` at the
trace's shapes). A trace names each op by its HLO instruction and each
program by its module, so the map prices every op of the window."""
import bisect
import collections
import gzip
import json
import os
import re

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
LOOP = ("pop", "grad", "rank", "measure", "insert", "loop")
_INSTR = re.compile(r"^%?([^\s=]+)")


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
def steps():
    with open(os.path.join(DATA, "batch_tiny.traced.json")) as f:
        return json.load(f)["steps"]


def instruction_seconds(profile) -> dict:
    """Device seconds per (module, instruction) inside the traced window,
    the module read from the device's ``XLA Modules`` line, containers
    left out (as ``trace_reduce`` counts ops)."""
    w0 = w1 = None
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tr.WINDOW:
                    w0, w1 = ev.start_ns, ev.start_ns + ev.duration_ns
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(")[0]) for e in lines["XLA Modules"])
    starts = [m[0] for m in mods]
    out = collections.Counter()
    for ev in lines[tr.OPS_LINE]:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e <= w0 or s >= w1 or tr.op_base(ev.name) in tr.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
        out[(mod, _INSTR.match(ev.name).group(1))] += \
            (min(e, w1) - max(s, w0)) * 1e-9
    return out


@pytest.fixture(scope="module")
def joined(profile, stages):
    by_stage = collections.Counter()
    for (mod, name), sec in instruction_seconds(profile).items():
        stage = (stages["stages"].get(name, "unscoped")
                 if mod == stages["module"] else "unscoped")
        by_stage[stage] += sec
    return by_stage


def test_every_op_of_the_search_is_in_the_map(profile, stages):
    ops = [name for mod, name in instruction_seconds(profile)
           if mod == stages["module"]]
    assert len(ops) > 100
    assert [n for n in ops if n not in stages["stages"]] == []


def test_stage_time_adds_up_to_the_busy_time(profile, joined, steps):
    """Stage µs per engine step times the steps, plus the unscoped time,
    is the window's busy time; every loop stage has some."""
    busy = tr.reduce_profile(profile)["busy_s"]
    per_step = {k: 1e6 * v / steps for k, v in joined.items()
                if k != "unscoped"}
    total = sum(per_step.values()) * steps * 1e-6 + joined["unscoped"]
    assert total == pytest.approx(busy, rel=0.01)
    for stage in LOOP:
        assert per_step[stage] > 0, stage
    assert 100 * joined["unscoped"] / busy < 5


def test_the_neighbour_gather_is_the_rank_stage(profile, stages):
    # 32 lanes x 48 neighbours of 40 floats, gathered from the corpus
    names = {n for mod, n in instruction_seconds(profile)
             if mod == stages["module"]}
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    gathers = {_INSTR.match(ev.name).group(1)
               for ln in plane.lines if ln.name == tr.OPS_LINE
               for ev in ln.events
               if re.search(r"= f32\[1536,40\]\S* fusion\(f32\[3000,40\]",
                            ev.name)}
    assert gathers and gathers <= names
    assert {stages["stages"][g] for g in gathers} == {"rank"}
