"""Plain reference of GUITAR's graph search (arXiv:2312.16828, Sec. 3):
a best-first walk over the L2 graph with a pool of ``ef`` candidates. Each
step expands the best unexpanded pool entry x, takes the measure's
gradient g = df/dx there, ranks x's unvisited neighbours x' by the angle
between x' - x and g, keeps the ``budget`` smallest angles that lie within
``alpha`` times the smallest (the adaptive band), scores them with the
measure, marks them visited and merges them into the pool. A query stops
when no unexpanded entry is left or after ``max_iters`` expansions; its
answer is the pool's first ``k``.

Ties are broken as a stable sort breaks them: the pool before new
candidates, and lower neighbour positions first. Everything runs in
float32 at full precision on the deployment's index as stored (items,
neighbour lists, entry point), with the family's plain measure. Imports
nothing of the program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12
HIGHEST = jax.lax.Precision.HIGHEST


def make_search(family, m: dict, search: dict):
    """``run(params, items, neighbors, entry, queries) -> (ids, scores)``,
    (Q, k) each, for the search settings of a configuration."""
    if search.get("mode", "guitar") != "guitar" or \
            search.get("rank_by", "angle") != "angle":
        raise ValueError("the reference searches in mode guitar, by angle")
    k, ef, c = search["k"], search["ef"], search["budget"]
    alpha, cap = search["alpha"], search["max_iters"]
    adaptive = search["adaptive"]

    def score(params, x, q):
        return family.pair_scores(params, x, q, m)

    def grad(params, x, q):
        return jax.grad(lambda xx: score(params, xx[None], q[None])[0])(x)

    def run(params, items, neighbors, entry, queries):
        n_q, n = queries.shape[0], items.shape[0]
        rows = jnp.arange(n_q)
        entries = jnp.full((n_q,), entry, jnp.int32)
        pool_s = jnp.full((n_q, ef), -jnp.inf, jnp.float32).at[:, 0].set(
            score(params, items[entries], queries))
        pool_i = jnp.full((n_q, ef), -1, jnp.int32).at[:, 0].set(entries)
        pool_e = jnp.ones((n_q, ef), bool).at[:, 0].set(False)
        visited = jnp.zeros((n_q, n), bool).at[rows, entries].set(True)
        state = (pool_s, pool_i, pool_e, visited,
                 jnp.zeros((n_q,), jnp.int32), jnp.zeros((n_q,), bool))

        def step(st):
            pool_s, pool_i, pool_e, visited, iters, done = st
            # expand the best unexpanded entry
            cand = jnp.where(pool_e, -jnp.inf, pool_s)
            slot = jnp.argmax(cand, axis=1)
            active = jnp.isfinite(cand[rows, slot]) & ~done
            fid = jnp.maximum(pool_i[rows, slot], 0)
            pool_e = pool_e.at[rows, slot].set(pool_e[rows, slot] | active)
            nbr = neighbors[fid]                                # (Q, B)
            safe = jnp.maximum(nbr, 0)
            valid = (nbr >= 0) & ~visited[rows[:, None], safe] \
                & active[:, None]
            # rank the neighbours by the angle to the gradient
            x = items[fid]
            g = jax.vmap(lambda xx, q: grad(params, xx, q))(x, queries)
            diffs = items[safe] - x[:, None, :]
            dot = jnp.einsum("qbd,qd->qb", diffs, g, precision=HIGHEST)
            cos = dot / ((jnp.linalg.norm(diffs, axis=-1) + EPS)
                         * (jnp.linalg.norm(g, axis=-1)[:, None] + EPS))
            key = jnp.where(valid, jnp.arccos(jnp.clip(cos, -1.0, 1.0)),
                            jnp.inf)
            theta = jnp.min(key, axis=1, keepdims=True)
            band = valid & (key <= alpha * theta + EPS)
            _, sel = jax.lax.top_k(
                jnp.where(jnp.isfinite(key), -key, -jnp.inf), c)
            mask = jnp.take_along_axis(band if adaptive else valid, sel,
                                       axis=1)
            ids = jnp.take_along_axis(nbr, sel, axis=1)         # (Q, C)
            s = score(params, items[jnp.maximum(ids, 0)].reshape(n_q * c, -1),
                      jnp.repeat(queries, c, axis=0)).reshape(n_q, c)
            s = jnp.where(mask, s, -jnp.inf)
            visited = visited.at[rows[:, None], jnp.where(mask, ids, n)].set(
                True, mode="drop")
            # merge: a stable descending sort of [pool | candidates]
            all_s = jnp.concatenate([pool_s, s], axis=1)
            order = jnp.argsort(-all_s, axis=1, stable=True)[:, :ef]
            pool_s = jnp.take_along_axis(all_s, order, axis=1)
            pool_i = jnp.take_along_axis(
                jnp.concatenate([pool_i, jnp.where(mask, ids, -1)], axis=1),
                order, axis=1)
            pool_e = jnp.take_along_axis(
                jnp.concatenate([pool_e, ~mask], axis=1), order, axis=1)
            iters = iters + active
            left = jnp.any(~pool_e & jnp.isfinite(pool_s), axis=1)
            done_new = done | ~left | (iters >= cap) | ~active
            new = (pool_s, pool_i, pool_e, visited, iters, done_new)
            # a query that was done keeps its state
            return tuple(jnp.where(done.reshape((-1,) + (1,) * (a.ndim - 1)),
                                   b, a) if i != 3 else a
                         for i, (a, b) in enumerate(zip(new, st)))

        final = jax.lax.while_loop(lambda st: ~jnp.all(st[5]), step, state)
        return final[1][:, :k], final[0][:, :k]

    return run
