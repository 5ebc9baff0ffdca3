"""Expansion-engine tests: oracle parity, legacy parity, Pallas-vs-ref rank
agreement inside a full search, and the batch-major fused-measure invariant
(one (Q·C, D) evaluation per iteration, observed via a stage double)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EngineOptions, SearchConfig, brute_force_topk,
                        build_engine, deepfm_measure, deepfm_numpy_fns,
                        faithful_search_batch, inner_product_measure,
                        l2_measure, mlp_measure, recall, search_legacy,
                        search_measure)
from repro.core.engine import EngineState, default_insert_stage
from repro.graph import build_l2_graph
from repro.models import deepfm as deepfm_lib


@pytest.fixture(scope="module")
def deepfm_system():
    """Small synthetic DeepFM setup (the paper's measure, untrained weights
    over clustered vectors — enough structure for recall to be meaningful)."""
    cfg_m = deepfm_lib.DeepFMConfig()
    params, _ = deepfm_lib.init_measure(jax.random.PRNGKey(0), cfg_m)
    measure = deepfm_measure(params, cfg_m)
    rng = np.random.default_rng(3)
    base = rng.normal(size=(500, cfg_m.vec_dim)).astype(np.float32) * 0.5
    queries = rng.normal(size=(8, cfg_m.vec_dim)).astype(np.float32) * 0.5
    graph = build_l2_graph(base, m=10, k_construction=32)
    true_ids, _ = brute_force_topk(measure, jnp.asarray(base),
                                   jnp.asarray(queries), 10)
    return dict(params=params, cfg_m=cfg_m, measure=measure, base=base,
                queries=queries, graph=graph, true_ids=np.asarray(true_ids))


def _jarrs(sys):
    g = sys["graph"]
    Q = sys["queries"].shape[0]
    return (jnp.asarray(sys["base"]), jnp.asarray(g.neighbors),
            jnp.asarray(sys["queries"]), jnp.full((Q,), g.entry, jnp.int32))


def test_engine_matches_faithful_oracle(deepfm_system):
    """Recall within 0.02 of the dynamic-set oracle on the DeepFM setup, and
    the engine's #NN/#Grad accounting obeys the static-budget semantics."""
    sys = deepfm_system
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    cfg = SearchConfig(k=10, ef=48, mode="guitar", budget=8, alpha=1.1)
    res = search_measure(sys["measure"], base_j, nbrs_j, queries_j, entries,
                         cfg)
    r_engine = recall(res.ids, sys["true_ids"])

    score_np, grad_np = deepfm_numpy_fns(sys["params"], sys["cfg_m"])
    ids_f, _, stats = faithful_search_batch(
        score_np, grad_np, sys["base"], sys["graph"].neighbors,
        sys["queries"], sys["graph"].entry, k=10, ef=48, mode="guitar",
        alpha=1.1)
    r_faithful = recall(jnp.asarray(ids_f), sys["true_ids"])

    assert abs(r_engine - r_faithful) <= 0.02, (r_engine, r_faithful)
    # accounting: one grad per expansion; effective evals bounded by the
    # static budget (+1 entry eval)
    n_eval = np.asarray(res.n_eval)
    n_grad = np.asarray(res.n_grad)
    n_iters = np.asarray(res.n_iters)
    assert (n_grad == n_iters).all()
    assert (n_eval <= 1 + cfg.budget * n_iters).all()
    assert stats.n_grad > 0 and stats.n_eval > 0


@pytest.mark.parametrize("rank_by", ["angle", "projection"])
def test_engine_matches_legacy(deepfm_system, rank_by):
    """Engine vs the original lane-major searcher on identical inputs."""
    sys = deepfm_system
    m = sys["measure"]
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    cfg = SearchConfig(k=10, ef=32, mode="guitar", budget=6, alpha=1.1,
                       rank_by=rank_by)
    res_e = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg)
    res_l = search_legacy(m.score_fn, m.params, base_j, nbrs_j, queries_j,
                          entries, cfg)
    ids_e, ids_l = np.asarray(res_e.ids), np.asarray(res_l.ids)
    overlap = np.mean([
        len(set(ids_e[i]) & set(ids_l[i])) / cfg.k
        for i in range(ids_e.shape[0])])
    assert overlap >= 0.9, overlap
    np.testing.assert_allclose(np.asarray(res_e.n_eval),
                               np.asarray(res_l.n_eval), atol=2)
    np.testing.assert_allclose(np.asarray(res_e.n_grad),
                               np.asarray(res_l.n_grad), atol=2)


def test_engine_sl2g_matches_legacy(deepfm_system):
    sys = deepfm_system
    m = sys["measure"]
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    cfg = SearchConfig(k=10, ef=32, mode="sl2g")
    res_e = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg)
    res_l = search_legacy(m.score_fn, m.params, base_j, nbrs_j, queries_j,
                          entries, cfg)
    ids_e, ids_l = np.asarray(res_e.ids), np.asarray(res_l.ids)
    overlap = np.mean([
        len(set(ids_e[i]) & set(ids_l[i])) / cfg.k
        for i in range(ids_e.shape[0])])
    assert overlap >= 0.9, overlap
    assert (np.asarray(res_e.n_grad) == 0).all()


@pytest.mark.parametrize("rank_by", ["angle", "projection"])
def test_engine_pallas_rank_matches_ref(deepfm_system, rank_by):
    """The Pallas neighbor_rank path (interpret mode on CPU) and the jnp ref
    fallback must agree inside a full engine search."""
    sys = deepfm_system
    m = sys["measure"]
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    cfg = SearchConfig(k=10, ef=32, mode="guitar", budget=6, alpha=1.1,
                       rank_by=rank_by)
    res_p = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg,
                           EngineOptions(rank_impl="pallas", interpret=True))
    res_r = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg,
                           EngineOptions(rank_impl="ref"))
    ids_p, ids_r = np.asarray(res_p.ids), np.asarray(res_r.ids)
    overlap = np.mean([
        len(set(ids_p[i]) & set(ids_r[i])) / cfg.k
        for i in range(ids_p.shape[0])])
    assert overlap >= 0.95, overlap
    np.testing.assert_allclose(np.asarray(res_p.n_eval),
                               np.asarray(res_r.n_eval), atol=2)


def test_engine_deepfm_kernel_measure_stage(deepfm_system):
    """Fused Pallas deepfm_score measure stage == generic vmap stage."""
    sys = deepfm_system
    m = sys["measure"]
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    cfg = SearchConfig(k=10, ef=32, mode="guitar", budget=6, alpha=1.1)
    res_k = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg,
                           EngineOptions(measure_impl="pallas",
                                         interpret=True))
    res_v = search_measure(m, base_j, nbrs_j, queries_j, entries, cfg,
                           EngineOptions(measure_impl="vmap"))
    ids_k, ids_v = np.asarray(res_k.ids), np.asarray(res_v.ids)
    overlap = np.mean([
        len(set(ids_k[i]) & set(ids_v[i])) / cfg.k
        for i in range(ids_k.shape[0])])
    assert overlap >= 0.95, overlap


@pytest.mark.parametrize("mode", ["guitar", "sl2g"])
def test_engine_one_fused_measure_call_per_iteration(deepfm_system, mode):
    """The batch-major invariant: after the entry-seeding call, every
    iteration issues exactly ONE measure evaluation, flattened to
    (Q·C, D) — C = budget for GUITAR, C = max degree for SL2G."""
    sys = deepfm_system
    m = sys["measure"]
    base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    Q = queries_j.shape[0]
    cfg = SearchConfig(k=5, ef=16, mode=mode, budget=4, alpha=1.1,
                       max_iters=40)
    eng = build_engine(m, cfg, EngineOptions(rank_impl="ref",
                                             measure_impl="vmap"))
    calls = []
    inner = eng.measure

    def counting_measure(params, vecs, qs):
        calls.append((vecs.shape, qs.shape))
        return inner(params, vecs, qs)

    counted = dataclasses.replace(eng, measure=counting_measure)
    steps = []
    res = counted.search_debug(m.params, base_j, nbrs_j, queries_j, entries,
                               on_step=lambda i, s: steps.append(i),
                               jit_steps=False)
    C = cfg.budget if mode == "guitar" else nbrs_j.shape[1]
    D = base_j.shape[1]
    assert len(calls) == len(steps) + 1          # +1 entry seeding
    assert calls[0][0] == (Q, D)
    assert all(c[0] == (Q * C, D) and c[1] == (Q * C, D)
               for c in calls[1:])
    assert int(res.n_iters.max()) == len(steps)
    # the debug path is the same algorithm as the jitted path
    res_jit = eng.search(m.params, base_j, nbrs_j, queries_j, entries)
    assert (np.asarray(res.ids) == np.asarray(res_jit.ids)).all()


@pytest.mark.parametrize("family", ["deepfm", "mlp"])
def test_search_debug_bit_matches_jitted_search(deepfm_system, family):
    """The eager host loop (`search_debug`) is the SAME program as the
    jitted `search` — ids AND scores bit-identical, counters included —
    for both servable bundles, unfused and fused (the fused path routes
    the debug loop through the tile/rowwise plan too)."""
    if family == "deepfm":
        sys = deepfm_system
        m = sys["measure"]
        base_j, nbrs_j, queries_j, entries = _jarrs(sys)
    else:
        m = mlp_measure(jax.random.PRNGKey(2), 12, 12, hidden=(16,))
        rng = np.random.default_rng(11)
        base = rng.normal(size=(300, 12)).astype(np.float32)
        queries = rng.normal(size=(6, 12)).astype(np.float32)
        graph = build_l2_graph(base, m=8, k_construction=24)
        base_j, nbrs_j = jnp.asarray(base), jnp.asarray(graph.neighbors)
        queries_j = jnp.asarray(queries)
        entries = jnp.full((6,), graph.entry, jnp.int32)
    cfg = SearchConfig(k=8, ef=24, mode="guitar", budget=5, alpha=1.1,
                       max_iters=48)
    for options in (EngineOptions(), EngineOptions(fused=True)):
        eng = build_engine(m, cfg, options)
        res_j = eng.search(m.params, base_j, nbrs_j, queries_j, entries)
        res_d = eng.search_debug(m.params, base_j, nbrs_j, queries_j,
                                 entries)
        np.testing.assert_array_equal(np.asarray(res_j.ids),
                                      np.asarray(res_d.ids))
        np.testing.assert_array_equal(np.asarray(res_j.scores),
                                      np.asarray(res_d.scores))
        for field in ("n_eval", "n_grad", "n_iters"):
            np.testing.assert_array_equal(
                np.asarray(getattr(res_j, field)),
                np.asarray(getattr(res_d, field)))


def test_brute_force_topk_batched_matches_naive():
    """The blocked (Qb, Nb) scorer must equal per-query exhaustive scoring,
    including across base-block boundaries."""
    m = mlp_measure(jax.random.PRNGKey(1), 6, 6, hidden=(16,))
    rng = np.random.default_rng(0)
    base = rng.normal(size=(333, 6)).astype(np.float32)
    queries = rng.normal(size=(9, 6)).astype(np.float32)
    ids, scores = brute_force_topk(m, jnp.asarray(base), jnp.asarray(queries),
                                   7, batch=100, q_block=4)
    naive = np.asarray(jax.vmap(
        lambda q: jax.vmap(lambda x: m.score_fn(m.params, x, q))(
            jnp.asarray(base)))(jnp.asarray(queries)))
    for i in range(queries.shape[0]):
        order = np.argsort(-naive[i])[:7]
        assert set(np.asarray(ids)[i]) == set(order)
        np.testing.assert_allclose(np.asarray(scores)[i],
                                   np.sort(naive[i])[::-1][:7], rtol=1e-5)


def test_engine_budget_and_counters():
    """Engine keeps the legacy counter semantics on cheap measures."""
    rng = np.random.default_rng(7)
    base = rng.normal(size=(400, 8)).astype(np.float32)
    queries = rng.normal(size=(6, 8)).astype(np.float32)
    graph = build_l2_graph(base, m=8, k_construction=24)
    m = l2_measure()
    base_j, nbrs_j = jnp.asarray(base), jnp.asarray(graph.neighbors)
    queries_j = jnp.asarray(queries)
    entries = jnp.full((6,), graph.entry, jnp.int32)
    res_g = search_measure(m, base_j, nbrs_j, queries_j, entries,
                           SearchConfig(k=5, ef=24, mode="guitar", budget=4))
    res_s = search_measure(m, base_j, nbrs_j, queries_j, entries,
                           SearchConfig(k=5, ef=24, mode="sl2g"))
    assert float(res_g.n_eval.mean()) < float(res_s.n_eval.mean())
    assert (np.asarray(res_g.n_eval)
            <= 1 + 4 * np.asarray(res_g.n_iters)).all()
    m2 = inner_product_measure()
    res2 = search_measure(m2, base_j, nbrs_j, queries_j, entries,
                          SearchConfig(k=5, ef=24, mode="guitar", budget=4))
    assert np.isfinite(np.asarray(res2.scores)).all()


def _merge_case(rng, Q, ef, C):
    """A desc pool with -inf tails and a candidate block, on a coarse score
    grid (ties are common, -0.0 beside 0.0) with masked candidates."""
    levels = rng.integers(2, 10)

    def grid(shape):
        return (rng.integers(-levels, levels, shape) / 4).astype(np.float32)

    pool = grid((Q, ef))
    pool[rng.random((Q, ef)) < 0.1] = -0.0
    pool = -np.sort(-pool, axis=1, kind="stable")
    for q, n in enumerate(rng.integers(0, ef + 1, Q)):
        pool[q, n:] = -np.inf
    scores = grid((Q, C))
    scores[rng.random((Q, C)) < 0.1] = -0.0
    mask = rng.random((Q, C)) < 0.7
    state = EngineState(
        jnp.asarray(pool),
        jnp.asarray(rng.integers(0, 10_000, (Q, ef)), jnp.int32),
        jnp.asarray(rng.random((Q, ef)) < 0.5), jnp.zeros((Q, 1), jnp.uint32),
        *[jnp.zeros((Q,), jnp.int32)] * 3, jnp.zeros((Q,), jnp.bool_),
        jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), jnp.float32))
    ids = rng.integers(0, 10_000, (Q, C)).astype(np.int32)
    return state, ids, scores, mask


@pytest.mark.parametrize("ef", [16, 64])
@pytest.mark.parametrize("C", [1, 8, 48])
def test_insert_matches_stable_sort_oracle(C, ef):
    """The gather-free pool merge equals, bit for bit in all three arrays,
    a stable descending sort of [pool | candidates] cut to ef: pool first
    on ties, then candidate order; masked candidates score -inf, id -1,
    and count as expanded."""
    rng = np.random.default_rng(1000 * C + ef)
    merge = jax.jit(default_insert_stage)
    for _ in range(8):
        state, ids, scores, mask = _merge_case(rng, 16, ef, C)
        out = merge(state, jnp.asarray(ids), jnp.asarray(scores),
                    jnp.asarray(mask))
        all_s = np.concatenate(
            [np.asarray(state.pool_scores),
             np.where(mask, scores, np.float32(-np.inf))], axis=1)
        all_i = np.concatenate(
            [np.asarray(state.pool_ids), np.where(mask, ids, -1)], axis=1)
        all_e = np.concatenate([np.asarray(state.pool_expanded), ~mask],
                               axis=1)
        order = np.argsort(-all_s, axis=1, kind="stable")[:, :ef]
        for got, arr in ((out.pool_scores, all_s), (out.pool_ids, all_i),
                         (out.pool_expanded, all_e)):
            want = np.take_along_axis(arr, order, axis=1)
            assert np.asarray(got).dtype == want.dtype
            assert np.asarray(got).tobytes() == want.tobytes()
