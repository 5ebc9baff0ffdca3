"""Compat search API for fast neural ranking — SL2G baseline + GUITAR.

The hot path now lives in ``core/engine.py`` (see DESIGN.md §3): a staged,
batch-major ExpansionEngine that runs the whole query batch through one
iteration-major loop and issues a single flattened (Q·C, D) measure
evaluation per step. This module keeps the original public surface:

- ``search`` / ``search_measure`` keep their signatures and ``SearchResult``
  counters but dispatch to the engine;
- ``search_legacy`` is the original per-query ``lax.while_loop`` vmapped
  over lanes (kept for A/B benchmarking — see benchmarks/kernels_micro.py);
- ``rank_and_prune`` is the single-lane Eq. 3/4 ranking primitive (the
  engine uses the batched ``neighbor_rank`` kernel / ref instead);
- ``brute_force_topk`` is the exact ground-truth labeler, batched over both
  queries and the corpus (DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import (  # noqa: F401  (re-exported compat surface)
    EngineOptions, ExpansionEngine, SearchConfig, SearchResult, build_engine,
    build_engine_from_fn, engine_search,
)
from repro.core.measures import Measure


class _State(NamedTuple):
    pool_scores: jax.Array    # (ef,) f32 desc-sorted
    pool_ids: jax.Array       # (ef,) i32
    pool_expanded: jax.Array  # (ef,) bool
    visited: jax.Array        # (ceil(N/32),) uint32
    n_eval: jax.Array
    n_grad: jax.Array
    n_iters: jax.Array
    done: jax.Array


# ---------------------------------------------------------------------------
# visited bitmap (single-lane; the engine has batched twins)
# ---------------------------------------------------------------------------

def _bit_test(bitmap: jax.Array, ids: jax.Array) -> jax.Array:
    safe = jnp.maximum(ids, 0)
    word = safe >> 5
    bit = safe & 31
    return ((bitmap[word] >> bit) & 1).astype(jnp.bool_)


def _bit_set(bitmap: jax.Array, ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Set bits for ids where mask. ids within one call must be distinct and
    currently unset (guaranteed: neighbors of a node are distinct and we only
    set ids that passed the not-visited test) — so scatter-add acts as OR."""
    safe = jnp.maximum(ids, 0)
    word = safe >> 5
    bit = safe & 31
    updates = jnp.where(mask, jnp.uint32(1) << bit.astype(jnp.uint32), jnp.uint32(0))
    return bitmap.at[word].add(updates, mode="drop")


# ---------------------------------------------------------------------------
# pool ops
# ---------------------------------------------------------------------------

def _pool_insert(state: _State, new_scores, new_ids, new_valid) -> _State:
    """Merge candidates into the sorted pool (desc by score)."""
    ns = jnp.where(new_valid, new_scores, -jnp.inf)
    ni = jnp.where(new_valid, new_ids, -1)
    scores = jnp.concatenate([state.pool_scores, ns])
    ids = jnp.concatenate([state.pool_ids, ni])
    expanded = jnp.concatenate(
        [state.pool_expanded, jnp.ones_like(new_valid)])
    expanded = expanded.at[state.pool_scores.shape[0]:].set(~new_valid)
    # sort desc by score; ties broken arbitrarily
    order = jnp.argsort(-scores)
    ef = state.pool_scores.shape[0]
    return state._replace(
        pool_scores=scores[order][:ef],
        pool_ids=ids[order][:ef],
        pool_expanded=expanded[order][:ef],
    )


# ---------------------------------------------------------------------------
# neighbor ranking (the paper's Eq. 3 / Eq. 4), single lane
# ---------------------------------------------------------------------------

def rank_and_prune(diffs: jax.Array, grad: jax.Array, valid: jax.Array,
                   budget: int, alpha: float, rank_by: str, adaptive: bool
                   ) -> Tuple[jax.Array, jax.Array]:
    """diffs: (B, D) = x' - x; grad: (D,) = ∂f/∂x; valid: (B,) bool.

    Returns (sel_idx (C,), sel_mask (C,)): the top-C neighbor slots by the
    ranking criterion and the adaptive α-mask over them."""
    eps = 1e-12
    gnorm = jnp.linalg.norm(grad) + eps
    dot = diffs @ grad                              # (B,)
    dnorm = jnp.linalg.norm(diffs, axis=-1) + eps
    if rank_by == "angle":
        cosv = jnp.clip(dot / (dnorm * gnorm), -1.0, 1.0)
        angle = jnp.arccos(cosv)                    # smaller = better
        key = jnp.where(valid, angle, jnp.inf)
        theta = jnp.min(key)                        # best angle
        in_range = key <= alpha * theta + eps
        neg_key = -key
    else:  # projection (Eq. 4): larger projection = better
        proj = dot / gnorm
        key = jnp.where(valid, proj, -jnp.inf)
        theta = jnp.max(key)
        # paper: proj >= theta / alpha; guard negative-theta corner by
        # flipping the bound when theta < 0 (tolerance must *relax*).
        bound = jnp.where(theta >= 0, theta / alpha, theta * alpha)
        in_range = key >= bound - eps
        neg_key = key
    C = min(budget, diffs.shape[0])
    _, sel_idx = jax.lax.top_k(neg_key, C)          # best-C slots
    sel_mask = valid[sel_idx]
    if adaptive:
        sel_mask = sel_mask & in_range[sel_idx]
    return sel_idx, sel_mask


# ---------------------------------------------------------------------------
# legacy search loop (single query; vmapped by `search_legacy`)
# ---------------------------------------------------------------------------

def _search_one(score_fn, measure_params, base, neighbors, q, entry,
                cfg: SearchConfig) -> SearchResult:
    N, D = base.shape
    B = neighbors.shape[1]
    ef = cfg.ef
    nwords = (N + 31) // 32

    def score1(x):
        return score_fn(measure_params, x, q).astype(jnp.float32)

    score_many = jax.vmap(score1)

    # --- init: seed pool with the entry point
    e_score = score1(base[entry])
    pool_scores = jnp.full((ef,), -jnp.inf).at[0].set(e_score)
    pool_ids = jnp.full((ef,), -1, jnp.int32).at[0].set(entry)
    pool_expanded = jnp.ones((ef,), jnp.bool_).at[0].set(False)
    visited = _bit_set(jnp.zeros((nwords,), jnp.uint32),
                       jnp.array([entry]), jnp.array([True]))
    state = _State(pool_scores, pool_ids, pool_expanded, visited,
                   jnp.int32(1), jnp.int32(0), jnp.int32(0),
                   jnp.bool_(False))

    def cond(s: _State):
        return ~s.done

    def body(s: _State):
        # pop best unexpanded
        cand = jnp.where(s.pool_expanded, -jnp.inf, s.pool_scores)
        slot = jnp.argmax(cand)
        has_frontier = jnp.isfinite(cand[slot])
        fid = s.pool_ids[slot]
        fid_safe = jnp.maximum(fid, 0)
        s = s._replace(pool_expanded=s.pool_expanded.at[slot].set(True))

        x = base[fid_safe]
        nbr = neighbors[fid_safe]                      # (B,)
        nbr_safe = jnp.maximum(nbr, 0)
        valid = (nbr >= 0) & ~_bit_test(s.visited, nbr) & has_frontier
        nvecs = base[nbr_safe]                         # (B, D)

        if cfg.mode == "guitar":
            _, grad = jax.value_and_grad(score1)(x)
            sel_idx, sel_mask = rank_and_prune(
                nvecs - x[None, :], grad, valid,
                cfg.budget, cfg.alpha, cfg.rank_by, cfg.adaptive)
            sel_ids = nbr[sel_idx]
            sel_vecs = nvecs[sel_idx]
            scores = score_many(sel_vecs)
            n_grad = s.n_grad + jnp.where(has_frontier, 1, 0)
        else:  # sl2g: evaluate everything
            sel_ids, sel_mask, scores = nbr, valid, score_many(nvecs)
            n_grad = s.n_grad

        scores = jnp.where(sel_mask, scores, -jnp.inf)
        visited = _bit_set(s.visited, sel_ids, sel_mask)
        s = s._replace(visited=visited, n_grad=n_grad,
                       n_eval=s.n_eval + jnp.sum(sel_mask.astype(jnp.int32)),
                       n_iters=s.n_iters + jnp.where(has_frontier, 1, 0))
        s = _pool_insert(s, scores, sel_ids, sel_mask)
        done = ~jnp.any(~s.pool_expanded & jnp.isfinite(s.pool_scores))
        done = done | (s.n_iters >= cfg.iters()) | ~has_frontier
        return s._replace(done=done)

    # gate every update on ~done so vmapped lanes that converged stay frozen
    def gated_body(s: _State):
        s2 = body(s)
        return jax.tree_util.tree_map(
            lambda new, old: jnp.where(s.done, old, new), s2, s)

    final = jax.lax.while_loop(cond, gated_body, state)
    return SearchResult(
        ids=final.pool_ids[: cfg.k],
        scores=final.pool_scores[: cfg.k],
        n_eval=final.n_eval,
        n_grad=final.n_grad,
        n_iters=final.n_iters,
    )


@functools.partial(jax.jit, static_argnames=("score_fn", "cfg"))
def search_legacy(score_fn, measure_params, base: jax.Array,
                  neighbors: jax.Array, queries: jax.Array,
                  entries: jax.Array, cfg: SearchConfig) -> SearchResult:
    """The original lane-major searcher (per-query while_loop, vmapped)."""
    return jax.vmap(
        lambda q, e: _search_one(score_fn, measure_params, base, neighbors,
                                 q, e, cfg)
    )(queries, entries)


# ---------------------------------------------------------------------------
# public API — engine-backed
# ---------------------------------------------------------------------------

def search(score_fn, measure_params, base: jax.Array, neighbors: jax.Array,
           queries: jax.Array, entries: jax.Array, cfg: SearchConfig,
           options: Optional[EngineOptions] = None) -> SearchResult:
    """Batched fast-neural-ranking search (engine path).

    score_fn: (params, x (D,), q (Dq,)) -> scalar (static callable)
    base: (N, D); neighbors: (N, B) int32 -1-padded; queries: (Q, Dq);
    entries: (Q,) int32 entry points. Returns SearchResult with (Q, ...)."""
    eng = build_engine_from_fn(score_fn, cfg, options or EngineOptions())
    return eng.search(measure_params, base, neighbors, queries, entries)


def search_measure(measure: Measure, base, neighbors, queries, entries,
                   cfg: SearchConfig,
                   options: Optional[EngineOptions] = None) -> SearchResult:
    """Like ``search`` but measure-aware: DeepFM measures route their fused
    (Q·C, D) evaluation through the Pallas ``deepfm_score`` kernel on TPU."""
    eng = build_engine(measure, cfg, options or EngineOptions())
    return eng.search(measure.params, base, neighbors, queries, entries)


# ---------------------------------------------------------------------------
# ground truth + metrics
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _bf_merge_step(score_fn):
    """Jitted (Qb, Nb) blocked scorer + running top-k merge, cached per
    measure fn (shape-specialized compiles per distinct block shape)."""
    @jax.jit
    def step(params, qb, xs, col0, best_s, best_i):
        scores = jax.vmap(lambda q: jax.vmap(
            lambda x: score_fn(params, x, q))(xs))(qb).astype(jnp.float32)
        ids = col0 + jnp.arange(xs.shape[0], dtype=jnp.int32)
        cs = jnp.concatenate([best_s, scores], axis=1)
        ci = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids[None, :], scores.shape)], axis=1)
        v, ix = jax.lax.top_k(cs, best_s.shape[1])
        return v, jnp.take_along_axis(ci, ix, axis=1)
    return step


def brute_force_topk(measure: Measure, base: jax.Array, queries: jax.Array,
                     k: int, batch: int = 8192, q_block: int = 128
                     ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k by exhaustive measure evaluation (ground-truth labels —
    the paper's label protocol). Batched over queries AND corpus blocks: one
    jitted (Qb, Nb) scorer with a streaming top-k merge, instead of the old
    per-query Python loop. Matmuls run at ``highest`` precision: the TPU's
    default passes float32 operands through bfloat16, and a reference must
    be float32."""
    base = jnp.asarray(base)
    queries = jnp.asarray(queries)
    step = _bf_merge_step(measure.score_fn)
    outs_i, outs_s = [], []
    with jax.default_matmul_precision("highest"):
        for q0 in range(0, queries.shape[0], q_block):
            qb = queries[q0: q0 + q_block]
            best_s = jnp.full((qb.shape[0], k), -jnp.inf, jnp.float32)
            best_i = jnp.full((qb.shape[0], k), -1, jnp.int32)
            for s in range(0, base.shape[0], batch):
                best_s, best_i = step(measure.params, qb, base[s: s + batch],
                                      jnp.int32(s), best_s, best_i)
            outs_i.append(best_i)
            outs_s.append(best_s)
    return jnp.concatenate(outs_i), jnp.concatenate(outs_s)


def recall(found_ids: jax.Array, true_ids: jax.Array) -> float:
    """Mean |A ∩ B| / |B| over queries."""
    hits = 0
    Q, k = true_ids.shape
    fi = jax.device_get(found_ids)
    ti = jax.device_get(true_ids)
    for i in range(Q):
        hits += len(set(map(int, fi[i])) & set(map(int, ti[i])))
    return hits / (Q * k)
