"""The engine's stage scopes and ``obs.profile.stage_map``, which reads
them back from compiled HLO (DESIGN.md §13).

The scopes are trace-time metadata: a search compiled with them and one
compiled with every ``jax.named_scope`` made a no-op must run the same
program (equal HLO text once metadata is stripped) and return the same
ids and scores. The map must place every instruction of the compiled
search loop in one of the loop's stages, so no device time in the loop
goes unattributed.
"""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EngineOptions, SearchConfig, build_engine,
                        make_family_measure, mlp_measure)
from repro.graph import build_l2_graph
from repro.obs.profile import UNSCOPED, Stage, stage_map
from repro.serving import ContinuousRuntime

LOOP_STAGES = {"pop", "grad", "rank", "measure", "insert", "loop"}


@pytest.fixture(scope="module")
def system():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(500, 16)).astype(np.float32)
    queries = rng.normal(size=(8, 16)).astype(np.float32)
    graph = build_l2_graph(base, m=8, k_construction=24)
    return dict(base=jnp.asarray(base), queries=jnp.asarray(queries),
                graph=graph, nbrs=jnp.asarray(graph.neighbors),
                entries=jnp.full((8,), graph.entry, jnp.int32))


def _engine(kind: str):
    if kind == "deepfm":
        m = make_family_measure("deepfm", jax.random.PRNGKey(1), 16)
        return m, build_engine(m, SearchConfig(k=5, ef=24, budget=6,
                                               alpha=1.1))
    if kind == "fused":
        m = make_family_measure("deepfm", jax.random.PRNGKey(1), 16)
        return m, build_engine(m, SearchConfig(k=5, ef=24, budget=6,
                                               alpha=1.1),
                               EngineOptions(fused=True, tile="tile"))
    m = mlp_measure(jax.random.PRNGKey(2), 16, 16, hidden=(32,))
    return m, build_engine(m, SearchConfig(k=5, ef=24, mode="sl2g"))


def _computation(text: str, name: str) -> list:
    """Instruction names of one computation of an HLO module's text."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines)
                 if re.match(rf"^%?{re.escape(name)} \(", l))
    out = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            return out
        m = re.match(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ", line)
        if m:
            out.append(m.group(1))
    raise AssertionError(f"computation {name} never closes")


def _loop_computations(text: str) -> list:
    whiles = re.findall(r"while\(.*condition=%?([\w.\-]+), "
                        r"body=%?([\w.\-]+)", text)
    assert whiles, "no while loop in the compiled program"
    return [c for pair in whiles for c in pair]


def _strip(text: str) -> str:
    """The program without metadata: computations and instructions only
    (the stack-frame tables and every ``metadata={...}`` dropped)."""
    keep = [l for l in text.splitlines()
            if l.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(keep))


@pytest.mark.parametrize("kind", ["deepfm", "fused", "sl2g"])
def test_stage_map_places_the_search_loop_in_its_stages(system, kind):
    m, eng = _engine(kind)
    text = eng.compiled_text(m.params, system["base"], system["nbrs"],
                             system["queries"], system["entries"])
    stages = stage_map(text)
    module = re.match(r"HloModule (\S+?),", text).group(1)
    assert {mod for mod, _ in stages} == {module}
    for comp in _loop_computations(text):
        for name in _computation(text, comp):
            assert stages[(module, name)].stage in LOOP_STAGES, \
                (comp, name, stages[(module, name)])
    found = {s.stage for s in stages.values()}
    expect = {"pop", "rank", "measure", "insert", "loop", "init"}
    if kind != "sl2g":
        expect.add("grad")
    assert expect <= found


def test_stage_map_places_the_runtime_tick_in_its_stages(system):
    m, eng = _engine("deepfm")
    rt = ContinuousRuntime(eng, m.params, system["base"], system["nbrs"],
                           n_lanes=8, query_dim=16,
                           entry=system["graph"].entry, steps_per_tick=2)
    text = rt._tick_fn.lower(m.params, rt.store, rt.neighbors,
                             system["queries"],
                             rt._state).compile().as_text()
    stages = stage_map(text)
    module = re.match(r"HloModule (\S+?),", text).group(1)
    for comp in _loop_computations(text):
        for name in _computation(text, comp):
            assert stages[(module, name)].stage in LOOP_STAGES


def test_stage_scopes_change_no_program_and_no_answer(system, monkeypatch):
    m, eng = _engine("deepfm")
    args = (m.params, system["base"], system["nbrs"], system["queries"],
            system["entries"])
    scoped_text = eng.compiled_text(*args)
    scoped = eng.search(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = dataclasses.replace(eng)            # fresh jit caches
    bare_text = bare.compiled_text(*args)
    plain = bare.search(*args)
    assert "repro_loop" in scoped_text and "repro_init" in scoped_text
    assert "repro_" not in bare_text
    assert _strip(scoped_text) == _strip(bare_text)
    np.testing.assert_array_equal(scoped.ids, plain.ids)
    np.testing.assert_array_equal(scoped.scores, plain.scores)
    np.testing.assert_array_equal(scoped.n_eval, plain.n_eval)


HLO = """HloModule jit_t, entry_computation_layout={(f32[4]{0})->(f32[4]{0})}

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(t)/repro_grad/mul"}
  ROOT %n = f32[4]{0} negate(%m), metadata={op_name="jit(t)/repro_rank/neg"}
}

%fused_b (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p0.1, %p0.1), metadata={op_name="jit(t)/repro_insert/add"}
  ROOT %b = f32[4]{0} negate(%a)
}

%body (s: (f32[4])) -> (f32[4]) {
  %s = (f32[4]{0}) parameter(0)
  %g = f32[4]{0} get-tuple-element(%s), index=0
  %f1 = f32[4]{0} fusion(%g), kind=kLoop, calls=%fused_a
  %f2 = f32[4]{0} fusion(%f1), kind=kLoop, calls=%fused_b
  %d = f32[4]{0} abs(%f2), metadata={op_name="jit(t)/repro_loop/while/body/repro_pop/abs"}
  ROOT %t = (f32[4]{0}) tuple(%d)
}

%cond (s.1: (f32[4])) -> pred[] {
  %s.1 = (f32[4]{0}) parameter(0)
  ROOT %c = pred[] constant(true), metadata={op_name="jit(t)/repro_loop/lt"}
}

ENTRY %main (x: f32[4]) -> (f32[4]) {
  %x = f32[4]{0} parameter(0)
  %init = (f32[4]{0}) tuple(%x)
  ROOT %w = (f32[4]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(t)/repro_loop/while"}
}
"""


@pytest.mark.parametrize("name,stage", [
    ("m", Stage("grad", False)),        # its own scope
    ("d", Stage("pop", False)),         # the innermost of two scopes
    ("f1", Stage("rank", True)),        # no metadata: its body's root; the
    #                                     body also holds grad -> mixed
    ("f2", Stage("insert", False)),     # root unscoped: the body's stage
    ("g", Stage("loop", False)),        # no scope: its caller's (the while)
    ("t", Stage("loop", False)),
    ("c", Stage("loop", False)),
    ("w", Stage("loop", False)),
    ("x", Stage(UNSCOPED, False)),      # no scope anywhere
    ("init", Stage(UNSCOPED, False))])
def test_stage_map_rules(name, stage):
    assert stage_map(HLO)[("jit_t", name)] == stage
