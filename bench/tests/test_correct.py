"""The comparison that decides ``correct``, driven through whole runs of
the real cells at a CPU size with the chip check skipped:

- a sound run of each cell is correct;
- the control, the plain reference in bfloat16 put in the program's
  place (its scores in every answer), makes the run come out not correct
  on the score_gap limit;
- each fault the cells can have, planted under the timed path, makes the
  run come out not correct: a step that returns its state unchanged,
  half of the batch left out, an answer altered where it is produced,
  the rank stage handed the reversed gradient (over the search_miss
  limit), the gradient zeroed (seen by search_miss; on the chip it reads
  over the limit, at this size under it). (The exchange between chips is
  no fault of these one-chip cells.)
"""
import contextlib

import jax
import jax.numpy as jnp
import pytest

import control
import harness
from repro.core.engine import ExpansionEngine
from repro.serving.runtime import ContinuousRuntime

CELLS = ["deepfm-twitch.batch", "mlp-twitch.batch",
         "deepfm-twitch.serve-poisson"]


def run(root, cell, seed=11):
    res, lines = harness.run_cell(cell, seed, 1.0, False, 0.0, root=root,
                                  require_tpu=False)
    return res, "\n".join(lines)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res, lines = run(tiny_root, cell)
    assert res["correct"], lines
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["search_miss"]["value"] == 0, lines
    # the exhaustive top-k runs only where the cell reports recall_at_10
    scan = harness.reports_recall(harness.find_cell(cell, tiny_root), 10)
    assert scan == cell.endswith(".batch")
    assert ("recall_at_10" in res["metrics"]) == scan
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", ["deepfm-twitch.batch", "mlp-twitch.batch"])
def test_control_fails_the_limit(tiny_root, cell, monkeypatch):
    compare = harness.compare

    def control_compare(env, out, ref):
        return compare(env, control.bf16_answers(env, out, ref), ref)
    monkeypatch.setattr(harness, "compare", control_compare)
    res, lines = run(tiny_root, cell)
    assert not res["correct"], lines
    check = res["checks"]["score_gap"]
    assert check["value"] > 3 * check["limit"], lines


def _unchanged(self, params, store, neighbors, queries, qs_flat, state):
    return state._replace(done=jnp.ones_like(state.done))


_step = ExpansionEngine.step


def _half(self, params, store, neighbors, queries, qs_flat, state):
    new = _step(self, params, store, neighbors, queries, qs_flat, state)
    keep = jnp.arange(state.done.shape[0]) % 2 == 0      # every other row
    old = state._replace(done=jnp.ones_like(state.done))

    def pick(n, o):
        m = keep.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(pick, new, old)


_result = ExpansionEngine._result
_harvest = ContinuousRuntime._harvest


def _altered_result(self, final):
    r = _result(self, final)
    return r._replace(ids=r.ids.at[:, 0].set(r.ids[:, 1]))


def _altered_harvest(self, now):
    out = _harvest(self, now)
    for c in out:
        c.ids[1] = c.ids[0] + 1
    return out


FAULTS = {
    "state_unchanged": [("step", _unchanged)],
    "half_batch": [("step", _half)],
    "answer_altered": [("_result", _altered_result),
                       ("_harvest", _altered_harvest)],
}


_set_seed = harness.Env.set_seed


def _stage_fault(name):
    def set_seed(self, seed):
        _set_seed(self, seed)
        self.engine = control.FAULTS[name](self.engine)
    return [("set_seed", set_seed)]


STAGE_FAULTS = {name: _stage_fault(name) for name in control.FAULTS}


@contextlib.contextmanager
def planted(fault, monkeypatch):
    for name, fn in {**FAULTS, **STAGE_FAULTS}[fault]:
        cls = {"_harvest": ContinuousRuntime,
               "set_seed": harness.Env}.get(name, ExpansionEngine)
        monkeypatch.setattr(cls, name, fn)
    jax.clear_caches()
    try:
        yield
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(tiny_root, cell, fault, monkeypatch):
    with planted(fault, monkeypatch):
        res, lines = run(tiny_root, cell)
    assert not res["correct"], lines


@pytest.mark.parametrize("fault", sorted(STAGE_FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_stage_fault_is_seen_by_search_miss(tiny_root, cell, fault,
                                            monkeypatch):
    """A search that returns other, well-formed ids with their exact
    scores: search_miss reads it (a sound run reads 0 here)."""
    with planted(fault, monkeypatch):
        res, lines = run(tiny_root, cell)
    assert res["checks"]["search_miss"]["value"] >= 0.1, lines


@pytest.mark.parametrize("cell", CELLS)
def test_reversed_rank_is_not_correct(tiny_root, cell, monkeypatch):
    """The rank stage handed the reversed gradient returns none of the
    reference search's ids: search_miss passes its limit."""
    with planted("rank_reversed", monkeypatch):
        res, lines = run(tiny_root, cell)
    check = res["checks"]["search_miss"]
    assert check["value"] > check["limit"], lines
    assert not res["correct"], lines
