"""Corpus-sharded distributed search (the 1000+-node serving story).

The corpus (base vectors + subgraph) is partitioned over the ``model`` mesh
axis; every device runs the *same* batched GUITAR search over its local
partition for the full query block of its ``data`` row, then the per-shard
top-k are all-gathered along ``model`` and merged. Queries shard over
``data`` (and ``pod``). Measure params are replicated (tiny relative to the
corpus).

Partition-local graphs lose cross-partition edges; with random partitioning
the per-shard subcorpus stays uniformly distributed so per-shard recall is
preserved (validated in tests) — this is the standard sharded-ANN design
(e.g. distributed HNSW / ScaNN serving).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.corpus import ResidencyPolicy, make_corpus_store
from repro.core.engine import EngineOptions, build_engine_from_fn
from repro.core.measures import Measure
from repro.core.search import SearchConfig, SearchResult
from repro.graph.build import GraphIndex, build_l2_graph


@dataclasses.dataclass
class ShardedIndex:
    """Host-side container: per-partition padded arrays stacked on axis 0."""
    base: np.ndarray        # (S, Np, D)
    neighbors: np.ndarray   # (S, Np, B)
    entries: np.ndarray     # (S,)
    global_ids: np.ndarray  # (S, Np) partition row -> corpus id
    n_shards: int


def build_sharded_index(base: np.ndarray, n_shards: int, m: int = 24,
                        k_construction: int = 64, seed: int = 0,
                        impl: str = "blocked") -> ShardedIndex:
    rng = np.random.default_rng(seed)
    n = base.shape[0]
    perm = rng.permutation(n)
    per = -(-n // n_shards)
    bases, nbrs, entries, gids = [], [], [], []
    for s in range(n_shards):
        ids = perm[s * per: (s + 1) * per]
        pad = per - ids.size
        if pad:  # pad vectors by repeating row 0 of the shard...
            ids = np.concatenate([ids, np.repeat(ids[:1], pad)])
        sub = base[ids]
        if pad:  # ...but padded rows get global id -1, never row 0's id —
            # otherwise the all-gather merge can return the same corpus id
            # twice (one real, one padding alias), inflating recall
            ids = ids.copy()
            ids[per - pad:] = -1
        g = build_l2_graph(sub, m=m, k_construction=k_construction,
                           seed=seed + s, impl=impl)
        bases.append(g.base)
        nbrs.append(g.neighbors)
        entries.append(g.entry)
        gids.append(ids.astype(np.int32))
    B = max(x.shape[1] for x in nbrs)
    nbrs = [np.pad(x, ((0, 0), (0, B - x.shape[1])), constant_values=-1)
            for x in nbrs]
    return ShardedIndex(
        base=np.stack(bases), neighbors=np.stack(nbrs),
        entries=np.array(entries, np.int32), global_ids=np.stack(gids),
        n_shards=n_shards)


def merge_topk(all_ids: jax.Array, all_scores: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k: (Q, S, k) ids/scores -> (Q, k).

    Invalid candidates (id < 0: pool padding or partition-padding rows) are
    scored -inf so they can never displace a real result; slots that still
    hold -inf after the merge report id -1. Real ids appear at most once
    across shards (partitions are disjoint), so the output is duplicate-free.
    """
    Q = all_ids.shape[0]
    flat_i = all_ids.reshape(Q, -1)
    flat_s = jnp.where(flat_i < 0, -jnp.inf, all_scores.reshape(Q, -1))
    v, ix = jax.lax.top_k(flat_s, k)
    ids = jnp.take_along_axis(flat_i, ix, axis=1)
    return jnp.where(jnp.isfinite(v), ids, -1), v


def empty_topk(k: int) -> Tuple[np.ndarray, np.ndarray]:
    """The canonical no-result top-k (ids -1, scores -inf): what
    ``merge_topk`` reports when every candidate in the window is invalid,
    and what timed-out / shed / all-shards-failed completions carry
    (DESIGN.md §12). One definition so the contracts can't drift."""
    return (np.full((k,), -1, np.int32),
            np.full((k,), -np.inf, np.float32))


def make_sharded_search(score_fn, mesh: Mesh, cfg: SearchConfig,
                        options: EngineOptions = EngineOptions(),
                        meta=None):
    """Returns a jitted fn(measure_params, sh_base, sh_nbrs, sh_entries,
    sh_gids, queries) -> SearchResult under shard_map: merged global ids /
    scores (Q, k) plus per-query counters (n_eval/n_grad summed over
    shards, n_iters max — see ``local_search``). ``measure_params`` is an
    ordinary (replicated) pytree argument so the whole service step can be
    lowered abstractly for the dry-run. ``meta`` is the measure's
    ``(family, *args)`` tuple — it resolves the per-shard engine's kernel
    bundle exactly as in the single-partition path (None = generic
    vmap/autodiff stages)."""
    axis = "model"
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    engine = build_engine_from_fn(score_fn, cfg, options, meta=meta)

    def local_search(measure_params, base, nbrs, entry, gids, queries):
        # shard_map blocks: base (1, Np, D), queries (Qlocal, Dq).
        # Batch-major engine: the whole local query block runs through one
        # staged expansion loop against the local partition.
        base, nbrs, gids = base[0], nbrs[0], gids[0]
        entries = jnp.full((queries.shape[0],), entry[0], jnp.int32)
        res = engine.search(measure_params, base, nbrs, queries, entries)
        local_ids = jnp.where(res.ids >= 0, gids[jnp.maximum(res.ids, 0)], -1)
        # gather candidates from all corpus shards, merge top-k
        all_ids = jax.lax.all_gather(local_ids, axis, axis=1)     # (Q, S, k)
        all_scores = jax.lax.all_gather(res.scores, axis, axis=1)
        ids, scores = merge_topk(all_ids, all_scores, cfg.k)
        # per-query counters survive the merge (SLA metrics / straggler
        # analysis): evals and grads SUM over shards (total work billed to
        # the query), iterations take the MAX (shards expand in parallel —
        # the per-query critical path)
        n_eval = jax.lax.psum(res.n_eval, axis)
        n_grad = jax.lax.psum(res.n_grad, axis)
        n_iters = jax.lax.pmax(res.n_iters, axis)
        return SearchResult(ids, scores, n_eval, n_grad, n_iters)

    def specs_like(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    def fn(measure_params, base, nbrs, entries, gids, queries):
        wrapped = jax.shard_map(
            local_search, mesh=mesh,
            in_specs=(specs_like(measure_params),
                      P(axis, None, None), P(axis, None, None), P(axis),
                      P(axis, None), P(batch_axes, None)),
            out_specs=SearchResult(
                ids=P(batch_axes, None), scores=P(batch_axes, None),
                n_eval=P(batch_axes), n_grad=P(batch_axes),
                n_iters=P(batch_axes)),
            check_vma=False)
        return wrapped(measure_params, base, nbrs, entries, gids, queries)

    return jax.jit(fn)


def place_index(index: ShardedIndex, mesh: Mesh) -> Tuple[jax.Array, ...]:
    """Put each shard's (base, neighbors, entry, global ids) on the device
    that owns it along the mesh's ``model`` axis — the layout
    ``make_sharded_search`` takes, so no device ever holds the whole
    corpus."""
    def put(x):
        spec = P("model", *([None] * (np.ndim(x) - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return tuple(put(x) for x in (index.base, index.neighbors,
                                  index.entries, index.global_ids))


def sharded_search_host(measure: Measure, index: ShardedIndex,
                        queries: np.ndarray, cfg: SearchConfig,
                        mesh: Mesh,
                        options: EngineOptions = EngineOptions()
                        ) -> SearchResult:
    """Host convenience wrapper: place shards, run, fetch. Returns a full
    ``SearchResult`` (numpy leaves) — merged ids/scores plus the per-query
    counters. ``options`` passes straight through to the per-shard engine —
    index-fused stages and bf16/int8 corpus residency apply per partition
    (each shard quantizes its own rows; row scales keep the format
    partition-local) — and the measure's ``meta`` resolves the kernel
    bundle per shard (registry routing is shard-transparent)."""
    fn = make_sharded_search(measure.score_fn, mesh, cfg, options,
                             meta=getattr(measure, "meta", None))
    args = (measure.params, *place_index(index, mesh), jnp.asarray(queries))
    return SearchResult(*[np.asarray(x) for x in fn(*args)])


# ---------------------------------------------------------------------------
# residency-aware sharded search (host merge over per-shard stores)
# ---------------------------------------------------------------------------

def shard_stores(index: ShardedIndex, corpus_dtype: str = "float32",
                 residency: ResidencyPolicy | None = None) -> List[Any]:
    """Per-shard corpus stores under a residency policy: each partition
    quantizes its own rows (row scales stay partition-local, exactly like
    the shard_map path) and, when ``residency.kind == 'paged'``, pages its
    rows independently — S pagers, each with its own LRU budget."""
    return [make_corpus_store(index.base[s], corpus_dtype,
                              residency=residency)
            for s in range(index.n_shards)]


def sharded_search_stores(measure: Measure, stores: List[Any],
                          index: ShardedIndex, queries: np.ndarray,
                          cfg: SearchConfig,
                          options: EngineOptions = EngineOptions(),
                          iter_caps=None, taus=None) -> SearchResult:
    """Sharded search against pre-built per-shard stores — the path paged
    residency takes (a host pager cannot cross a ``shard_map`` boundary, so
    the per-shard searches run as ordinary jitted calls and the merge runs
    on host). Same math as ``local_search``: per-shard ``engine.search``,
    global-id remap with padded rows -> -1, ``merge_topk``, counters
    summed (n_eval/n_grad) and maxed (n_iters) over shards — bit-identical
    merged results to ``sharded_search_host`` when the stores hold the
    same payload. ``iter_caps`` (Q,) per-lane iteration budgets and
    ``taus`` (Q,) per-lane adaptive angle cutoffs broadcast to every shard
    (a query's SLA tier applies to all of its partition searches)."""
    engine = build_engine_from_fn(measure.score_fn, cfg, options,
                                  meta=tuple(m) if (
                                      m := getattr(measure, "meta", None))
                                  is not None else None)
    queries = jnp.asarray(queries)
    Q = queries.shape[0]
    per_ids, per_scores = [], []
    n_eval = jnp.zeros((Q,), jnp.int32)
    n_grad = jnp.zeros((Q,), jnp.int32)
    n_iters = jnp.zeros((Q,), jnp.int32)
    for s, store in enumerate(stores):
        entries = jnp.full((Q,), int(index.entries[s]), jnp.int32)
        res = engine.search(measure.params, store,
                            jnp.asarray(index.neighbors[s]), queries,
                            entries, iter_caps=iter_caps, taus=taus)
        gids = jnp.asarray(index.global_ids[s])
        per_ids.append(jnp.where(res.ids >= 0,
                                 gids[jnp.maximum(res.ids, 0)], -1))
        per_scores.append(res.scores)
        n_eval = n_eval + res.n_eval
        n_grad = n_grad + res.n_grad
        n_iters = jnp.maximum(n_iters, res.n_iters)
    ids, scores = merge_topk(jnp.stack(per_ids, axis=1),
                             jnp.stack(per_scores, axis=1), cfg.k)
    return SearchResult(*[np.asarray(x) for x in
                          (ids, scores, n_eval, n_grad, n_iters)])
