"""Batch-major staged expansion engine for fast neural ranking (DESIGN.md §3).

The paper's observation is that measure evaluation dominates search cost.
The original searcher (`core/search.py`, kept as the legacy path) ran a
per-query ``lax.while_loop`` vmapped over lanes, scoring at most ``budget``
vectors per lane per step — tiny, lane-fragmented measure calls. This module
restructures the search as ONE iteration-major loop over the whole query
batch, with each algorithmic phase a swappable *stage*:

    pop      batched frontier pop over the (Q, ef) pools
    grad     one batched value+gradient over the (Q, D) frontier (GUITAR) —
             an analytic forward+backward kernel when the measure family
             registers one (bit-identical to ``vmap(jax.value_and_grad)``
             at fp32), the generic autodiff stage otherwise
    rank     Eq. 3/4 neighbor ranking — Pallas ``neighbor_rank`` kernel on
             TPU, pure-jnp ``ref`` fallback elsewhere
    measure  a single flattened (Q·C, D) evaluation per step — a Pallas
             scoring kernel when the measure family registers one
    insert   batched pool insert + packed visited-bitmap update

Measure→stage dispatch flows exclusively through the ``MeasureKernelBundle``
registry (core/bundles.py): a measure advertises ``meta = (family, *args)``
and ``_build`` resolves its score/grad stages (and their index-fused
variants) from the registered bundle, with the generic vmap/``jax.grad``
stages as the universal fallback. New measures arrive as a bundle
registration, never as an engine change.

Strategies are *configurations* of the same engine rather than branches in
the loop body: SL2G = no grad stage + select-all rank; GUITAR = grad stage +
angle/projection rank with the adaptive α·θ mask. Custom stages (caching,
quantized measures, learned pruners) plug in via ``dataclasses.replace``.

Two execution paths share the exact same stage code:

- ``ExpansionEngine.search``       jitted ``lax.while_loop`` (serving path);
- ``ExpansionEngine.search_debug`` host loop, one Python call per
  iteration — jitted per step by default (ids AND scores bit-identical to
  ``search``); ``jit_steps=False`` for plain-Python stage observability
  (call-counting doubles, tracing).

Index-fused corpus residency (DESIGN.md §8): with ``EngineOptions(fused=
True)`` the rank, measure, and (when the bundle registers one) grad stages
take ``(store, idx)`` instead of pre-gathered vectors — the row gather
happens inside the Pallas kernels (scalar-prefetch indexing) or fuses into
the jnp ref — so the (Q, B, D) neighbor block, the flattened (Q·C, D)
candidate block, and the (Q, D) frontier block never hit HBM.
``EngineOptions(corpus_dtype=...)`` holds the corpus resident in fp32,
bf16, or per-row-scaled int8 (dequantize-on-gather); the fp32 fused path
is bit-identical to the pre-gathered stages (tests pin it).

Counter semantics match the legacy searcher: ``n_eval`` counts *effective*
(α-mask-surviving) measure evaluations, ``n_grad`` gradient computations,
``n_iters`` expansions — the paper's Table-2 accounting
(Total = #NN + 2·#Grad).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro.core.bundles import (  # noqa: F401  (re-exported compat surface)
    MeasureKernelBundle, make_grad_stage, make_vmap_measure_fused_stage,
    make_vmap_measure_stage, register_bundle, resolve_stages,
    use_pallas_impl,
)
from repro.core.corpus import (CorpusStore, as_corpus_store,
                               bit_test_global)
from repro.kernels import autotune
from repro.kernels.neighbor_rank import neighbor_rank
from repro.kernels.neighbor_rank.ref import neighbor_rank_ref
from repro.kernels.neighbor_rank_fused import neighbor_rank_fused


# ---------------------------------------------------------------------------
# config / results (canonical home; core/search.py re-exports for compat)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SearchConfig:
    k: int = 10                 # results to return
    ef: int = 64                # pool (beam) size; >= k
    budget: int = 8             # C: measure evals per expansion (guitar)
    alpha: float = 1.01         # adaptive tolerance (>= 1)
    mode: str = "guitar"        # guitar | sl2g
    rank_by: str = "angle"      # angle | projection
    adaptive: bool = True       # apply the alpha*theta mask
    max_iters: int = 0          # 0 -> 4 * ef

    def iters(self) -> int:
        return self.max_iters if self.max_iters > 0 else 4 * self.ef


class SearchResult(NamedTuple):
    ids: jax.Array       # (Q, k) int32
    scores: jax.Array    # (Q, k) float32
    n_eval: jax.Array    # (Q,) effective measure evaluations
    n_grad: jax.Array    # (Q,) gradient computations
    n_iters: jax.Array   # (Q,) expansions


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Backend knobs; hashable so engines can be cached per (fn, cfg, opts).

    rank_impl:    'auto' (Pallas on TPU, ref elsewhere) | 'pallas' | 'ref'
    measure_impl: routing for the score stages: 'auto' resolves the
                  measure's registered kernel bundle (Pallas on TPU, its
                  jnp ref elsewhere), 'pallas' forces the Pallas path,
                  'vmap' forces the generic vmapped-score fallback
                  (bypasses the bundle)
    grad_impl:    same trichotomy for the gradient stages: 'auto' resolves
                  the bundle's analytic forward+backward kernel
                  (bit-identical to vmap(jax.value_and_grad) at fp32),
                  'pallas' forces Pallas, 'vmap' forces generic autodiff
    interpret:    force Pallas interpret mode (None = auto per backend)
    fused:        index-fused rank/measure/grad stages — gathers happen
                  inside the kernels (or fuse into the jnp ref); the
                  (Q, B, D) / (Q·C, D) / (Q, D) pre-gathered blocks are
                  never materialized
    corpus_dtype: 'float32' | 'bfloat16' | 'int8' corpus residency;
                  non-fp32 dequantizes on gather (see core/corpus.py)
    tile:         fused-path tiling override (kernels/autotune.py spec:
                  'tile' | 'rowwise' plan, ':<bt>' rows-per-grid-step, or
                  'plan:<bt>'); None resolves the autotune cache / shipped
                  defaults per shape at trace time
    adaptive:     'off' | 'angle' — angle-based adaptive candidate-set
                  sizing (the paper's § adaptive |C|): the rank stage sizes
                  each hop's candidate set by angle geometry (the α·θ band
                  plus an absolute per-lane cutoff ``angle_tau``) instead
                  of top-``budget`` truncation. Realized as a static
                  ``c_max`` block plus a per-lane prefix mask fed to the
                  measure stage — shapes stay fixed, tile/autotune plans
                  still apply, and 'off' is bit-identical to the
                  pre-adaptive engine. Requires mode='guitar' and
                  rank_by='angle'.
    c_max:        adaptive block width (the static C the dynamic |C| is
                  masked inside); 0 falls back to cfg.budget. Inert when
                  adaptive='off'.
    angle_tau:    default absolute angle cutoff (radians) applied on top
                  of the α·θ band; candidates whose gradient/offset angle
                  exceeds it are masked. <= 0 disables the absolute cutoff
                  (band-only sizing). Per-lane overrides flow through
                  ``search(..., taus=)`` / ``reset_lanes(..., taus=)`` —
                  the serving SLA tiers' C policy. Inert when
                  adaptive='off'.
    """
    rank_impl: str = "auto"
    measure_impl: str = "auto"
    interpret: Optional[bool] = None
    block_q: int = 8
    fused: bool = False
    corpus_dtype: str = "float32"
    grad_impl: str = "auto"
    tile: Optional[str] = None
    adaptive: str = "off"
    c_max: int = 0
    angle_tau: float = 0.0


# ---------------------------------------------------------------------------
# batched state + packed visited bitmap
# ---------------------------------------------------------------------------

class EngineState(NamedTuple):
    pool_scores: jax.Array    # (Q, ef) f32 desc-sorted
    pool_ids: jax.Array       # (Q, ef) i32
    pool_expanded: jax.Array  # (Q, ef) bool
    visited: jax.Array        # (Q, ceil(N/32)) uint32
    n_eval: jax.Array         # (Q,) i32
    n_grad: jax.Array         # (Q,) i32
    n_iters: jax.Array        # (Q,) i32
    done: jax.Array           # (Q,) bool
    iter_cap: jax.Array       # (Q,) i32 per-lane expansion budget (SLA
    #                           tiers / anytime search; cfg.iters() default)
    angle_tau: jax.Array      # (Q,) f32 per-lane adaptive angle cutoff
    #                           (radians; <= 0 disables — carried but unread
    #                           when EngineOptions.adaptive='off')


class PopOut(NamedTuple):
    slot: jax.Array      # (Q,) pool slot popped
    fid: jax.Array       # (Q,) frontier node id, clamped >= 0
    active: jax.Array    # (Q,) lane expands this step (has frontier & ~done)


def bit_test_rows(bitmap: jax.Array, ids: jax.Array) -> jax.Array:
    """bitmap: (Q, W) uint32; ids: (Q, B) int32 -> (Q, B) bool."""
    safe = jnp.maximum(ids, 0)
    word = safe >> 5
    bit = (safe & 31).astype(jnp.uint32)
    w = jnp.take_along_axis(bitmap, word, axis=1)
    return ((w >> bit) & 1).astype(jnp.bool_)


def bit_set_rows(bitmap: jax.Array, ids: jax.Array, mask: jax.Array) -> jax.Array:
    """Set bits rowwise. Within a row, masked-in ids are distinct and unset
    (neighbor lists are duplicate-free and we only set fresh ids), so
    scatter-add acts as OR — ids sharing a word accumulate distinct bits."""
    Q = bitmap.shape[0]
    safe = jnp.maximum(ids, 0)
    word = safe >> 5
    bit = (safe & 31).astype(jnp.uint32)
    updates = jnp.where(mask, jnp.uint32(1) << bit, jnp.uint32(0))
    rows = jnp.broadcast_to(jnp.arange(Q)[:, None], ids.shape)
    return bitmap.at[rows, word].add(updates, mode="drop")


def _freeze_done(done: jax.Array, new: Any, old: Any) -> Any:
    """Keep converged lanes' state frozen (lane-granular early exit).

    ``visited`` is exempt: a done lane pops with ``active=False``, so every
    bit update is already masked to a no-op (``bit_set_rows`` adds zero
    words) — the new bitmap is value-identical to the old one. Skipping the
    select lets XLA keep the scatter-add in place instead of carrying two
    (Q, N/32) bitmap buffers (plus a full-bitmap select) through every
    ``while_loop`` iteration — at N=200k that's ~10 MB/step of pure copy
    traffic removed from the serving hot loop."""
    def pick(n, o):
        d = done.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(d, o, n)
    frozen = jax.tree_util.tree_map(pick, new, old)
    return frozen._replace(visited=new.visited)


# ---------------------------------------------------------------------------
# Stage protocols — the engine is a pipeline of these callables
# ---------------------------------------------------------------------------

class PopStage(Protocol):
    def __call__(self, state: EngineState) -> Tuple[EngineState, PopOut]:
        """Pop one frontier node per lane; mark its slot expanded."""


class GradStage(Protocol):
    def __call__(self, params: Any, x: jax.Array, q: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """(Q, D) frontier, (Q, Dq) queries -> ((Q,) values, (Q, D) grads)."""


class FusedGradStage(Protocol):
    def __call__(self, params: Any, store: CorpusStore, fid: jax.Array,
                 q: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Index-fused gradient: store, (Q,) frontier ids, (Q, Dq) queries
        -> ((Q,) values, (Q, D) grads, (Q, D) dequantized frontier rows).
        The frontier gather happens inside the stage (scalar-prefetch +
        dequant-on-gather); the returned ``x`` rows feed the rank stage so
        the engine never gathers the frontier itself."""


class RankStage(Protocol):
    def __call__(self, x: jax.Array, grad: Optional[jax.Array],
                 nvecs: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """Pick candidates: (Q,D), (Q,D)|None, (Q,B,D), (Q,B) ->
        (sel_idx (Q,C) i32 slots into B, sel_mask (Q,C) bool)."""


class FusedRankStage(Protocol):
    def __call__(self, x: jax.Array, grad: Optional[jax.Array],
                 store: CorpusStore, idx: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        """Index-fused candidate pick: (Q,D), (Q,D)|None, store, (Q,B) ids,
        (Q,B) -> (sel_idx (Q,C) i32 slots into B, sel_mask (Q,C) bool).
        The neighbor rows are gathered inside the stage, never by the
        engine."""


class MeasureStage(Protocol):
    def __call__(self, params: Any, vecs: jax.Array, qs: jax.Array
                 ) -> jax.Array:
        """Flattened batch scorer: (M, D), (M, Dq) -> (M,) f32."""


class FusedMeasureStage(Protocol):
    def __call__(self, params: Any, store: CorpusStore, idx: jax.Array,
                 qs: jax.Array) -> jax.Array:
        """Index-fused flattened scorer: store, (M,) row ids, (M, Dq) ->
        (M,) f32. Candidate rows are gathered (and dequantized) inside."""


class InsertStage(Protocol):
    def __call__(self, state: EngineState, ids: jax.Array, scores: jax.Array,
                 mask: jax.Array) -> EngineState:
        """Merge (Q, C) candidates into the sorted pools."""


# ---------------------------------------------------------------------------
# default stage implementations
# ---------------------------------------------------------------------------

def default_pop_stage(state: EngineState) -> Tuple[EngineState, PopOut]:
    Q = state.pool_scores.shape[0]
    cand = jnp.where(state.pool_expanded, -jnp.inf, state.pool_scores)
    slot = jnp.argmax(cand, axis=1)
    best = jnp.take_along_axis(cand, slot[:, None], axis=1)[:, 0]
    active = jnp.isfinite(best) & ~state.done
    fid = jnp.take_along_axis(state.pool_ids, slot[:, None], axis=1)[:, 0]
    fid = jnp.maximum(fid, 0)
    marked = state.pool_expanded.at[jnp.arange(Q), slot].set(True)
    expanded = jnp.where(active[:, None], marked, state.pool_expanded)
    return state._replace(pool_expanded=expanded), PopOut(slot, fid, active)


# the shared backend-routing predicate (core/bundles.py owns it)
_use_pallas = use_pallas_impl


def _select_top_c(key, in_range, valid, cfg: SearchConfig,
                  c_max: Optional[int] = None, tau=None):
    """Static top-C over ranking keys + the adaptive α·θ mask — the part of
    the rank stage shared by the pre-gathered and index-fused variants.

    Adaptive sizing (``c_max``/``tau`` set): the block widens to ``c_max``
    and the mask adds a per-lane absolute cutoff ``key <= tau`` (tau <= 0
    disables it). ``top_k`` orders the block ascending by key, and band,
    cutoff, and validity are all monotone in the sorted key, so the
    per-lane mask is a PREFIX of the block — the dynamic |C| is a count,
    which is what lets the fused measure kernels skip whole tail tiles
    without any shape change (the mask-not-reshape contract)."""
    C = min(c_max if c_max else cfg.budget, key.shape[1])
    neg_key = jnp.where(jnp.isfinite(key), -key, -jnp.inf)
    _, sel_idx = jax.lax.top_k(neg_key, C)
    base_mask = in_range if cfg.adaptive else valid
    sel_mask = jnp.take_along_axis(base_mask, sel_idx, axis=1)
    if tau is not None:
        tau = tau[:, None]
        sel_key = jnp.take_along_axis(key, sel_idx, axis=1)
        sel_mask = sel_mask & ((tau <= 0) | (sel_key <= tau))
    return sel_idx, sel_mask


def _adaptive_c_max(cfg: SearchConfig, options) -> Optional[int]:
    """The static adaptive block width, or None when adaptive is off."""
    if getattr(options, "adaptive", "off") != "angle":
        return None
    return options.c_max if options.c_max else cfg.budget


def make_guitar_rank_stage(cfg: SearchConfig,
                           options: EngineOptions = EngineOptions()
                           ) -> RankStage:
    """Eq. 3 (angle) / Eq. 4 (projection) + static top-C + adaptive α·θ mask.
    Backed by the Pallas ``neighbor_rank`` kernel or its jnp ref. The
    optional trailing ``tau`` ((Q,) f32) is passed by the engine only when
    ``EngineOptions.adaptive='angle'`` — 4-arg callers (and custom stage
    doubles) are untouched."""
    c_max = _adaptive_c_max(cfg, options)

    def stage(x, grad, nvecs, valid, tau=None):
        if _use_pallas(options.rank_impl):
            key, in_range = neighbor_rank(
                x, grad, nvecs, valid, alpha=cfg.alpha, rank_by=cfg.rank_by,
                block_q=options.block_q, interpret=options.interpret)
        else:
            key, in_range = neighbor_rank_ref(
                x, grad, nvecs, valid, alpha=cfg.alpha, rank_by=cfg.rank_by)
        return _select_top_c(key, in_range, valid, cfg, c_max, tau)
    return stage


def make_guitar_rank_fused_stage(cfg: SearchConfig,
                                 options: EngineOptions = EngineOptions()
                                 ) -> FusedRankStage:
    """Index-fused Eq. 3/4: ranking keys straight off the resident corpus
    via the ``neighbor_rank_fused`` kernel (or its gather-fused jnp ref)."""
    c_max = _adaptive_c_max(cfg, options)

    def stage(x, grad, store, idx, valid, tau=None):
        key, in_range = neighbor_rank_fused(
            x, grad, store, idx, valid, alpha=cfg.alpha, rank_by=cfg.rank_by,
            use_pallas=_use_pallas(options.rank_impl),
            interpret=options.interpret, tile=options.tile)
        return _select_top_c(key, in_range, valid, cfg, c_max, tau)
    return stage


def select_all_rank_stage(x, grad, nvecs, valid):
    """SL2G: no pruning — every fresh neighbor is a candidate (C = B)."""
    Q, B, _ = nvecs.shape
    sel_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (Q, B))
    return sel_idx, valid


def select_all_rank_fused_stage(x, grad, store, idx, valid):
    """SL2G, index-fused: no pruning and no gather at all — the measure
    stage scores every fresh neighbor by id."""
    Q, B = idx.shape
    sel_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (Q, B))
    return sel_idx, valid


def default_insert_stage(state: EngineState, ids: jax.Array,
                         scores: jax.Array, mask: jax.Array) -> EngineState:
    """Sorted-pool merge WITHOUT a general sort. The pool is desc-sorted and
    only C ≪ ef candidates arrive per step, so (1) candidates are ordered by
    a comparison-counted rank realized as a one-hot permutation (XLA's
    generic sort and scatter are both far slower on CPU than these dense
    ops), and (2) each output slot gathers from pool or sorted candidates by
    merge-path counting — O(ef·C) vectorized comparisons total. Tie-breaking
    is pool-first then candidate index order, i.e. bit-exact with a stable
    desc sort of [pool | candidates]."""
    Q, ef = state.pool_scores.shape
    C = scores.shape[1]
    ns = jnp.where(mask, scores, -jnp.inf)               # (Q, C)
    ni = jnp.where(mask, ids, -1)
    ne = ~mask
    p = state.pool_scores                                # (Q, ef) desc
    # stable desc rank within candidates (unique) -> permutation via one-hot
    gt = ns[:, :, None] < ns[:, None, :]                 # cand[k] > cand[j]
    eq_earlier = (ns[:, :, None] == ns[:, None, :]) \
        & (jnp.arange(C)[None, :] < jnp.arange(C)[:, None])[None]
    rank = jnp.sum(gt | eq_earlier, axis=2)              # (Q, C)
    onehot = (rank[:, :, None]
              == jnp.arange(C)[None, None, :]).astype(jnp.float32)
    perm = jnp.einsum("qjc,j->qc", onehot,
                      jnp.arange(C, dtype=jnp.float32)).astype(jnp.int32)
    ns = jnp.take_along_axis(ns, perm, axis=1)           # (Q, C) desc
    ni = jnp.take_along_axis(ni, perm, axis=1)
    ne = jnp.take_along_axis(ne, perm, axis=1)
    # merged position of sorted cand j: j + #(pool >= cand_j)
    pos_c = jnp.arange(C)[None, :] + jnp.sum(
        p[:, None, :] >= ns[:, :, None], axis=2)         # (Q, C)
    # slot-major gather: n_c(t) candidates land before output slot t, so
    # slot t holds cand[n_c] if its position is exactly t, else pool[t - n_c]
    t = jnp.arange(ef)[None, :]
    n_c = jnp.sum(pos_c[:, None, :] < t[:, :, None], axis=2)   # (Q, ef)
    ip = t - n_c
    jc = jnp.clip(n_c, 0, C - 1)
    from_c = jnp.take_along_axis(pos_c, jc, axis=1) == t

    def pick(pool_v, cand_v):
        a = jnp.take_along_axis(pool_v, jnp.clip(ip, 0, ef - 1), axis=1)
        b = jnp.take_along_axis(cand_v, jc, axis=1)
        return jnp.where(from_c, b, a)

    return state._replace(
        pool_scores=pick(p, ns),
        pool_ids=pick(state.pool_ids, ni),
        pool_expanded=pick(state.pool_expanded, ne))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class ExpansionEngine:
    """A staged, batch-major graph searcher. Stages are swappable callables;
    use ``dataclasses.replace(engine, measure=...)`` to instrument or extend.
    ``grad=None`` skips the gradient phase (SL2G and other no-prune modes).

    When ``rank_fused`` / ``measure_fused`` / ``grad_fused`` are set
    (``EngineOptions(fused=True)``) the engine hands those stages ``(store,
    idx)`` and never materializes the (Q, B, D) neighbor, (Q·C, D)
    candidate, or (Q, D) frontier blocks; the corpus is held resident per
    ``corpus_dtype`` (see core/corpus.py). ``grad_fused`` also returns the
    dequantized frontier rows, so the engine skips its own frontier gather.

    The fused step's *dataflow plan* is autotuned (kernels/autotune.py):
    ``rowwise`` is the in-kernel-gather shape above; ``tile`` — the
    CPU winner, shipped as the committed CPU default — performs ONE
    combined ``[frontier | neighbors]`` gather per step behind
    ``jax.lax.optimization_barrier`` and runs the pre-gathered stages on
    slices of it (XLA:CPU otherwise re-inlines the gather into every
    consumer inside the ``while_loop`` body). The tile plan only applies
    when the fused stages route to jnp refs (``pallas_fused=False``) —
    bit-identical at fp32 to both the rowwise fused refs and the unfused
    stages, since the gather values and stage math are the same.
    """
    cfg: SearchConfig
    pop: PopStage
    rank: RankStage
    measure: MeasureStage
    insert: InsertStage
    grad: Optional[GradStage] = None
    rank_fused: Optional[FusedRankStage] = None
    measure_fused: Optional[FusedMeasureStage] = None
    corpus_dtype: str = "float32"
    grad_fused: Optional[FusedGradStage] = None
    tile: Optional[str] = None      # EngineOptions.tile override spec
    pallas_fused: bool = False      # fused stages routed to Pallas kernels
    adaptive: str = "off"           # EngineOptions.adaptive policy
    c_max: int = 0                  # adaptive block width (0 -> cfg.budget)
    angle_tau: float = 0.0          # default per-lane cutoff (<= 0 = band
    #                                 only); search(taus=) overrides per lane

    # -- candidates per expansion (static; fixes the flattened batch shape)
    def n_candidates(self, max_degree: int) -> int:
        if self.grad is None:
            return max_degree
        c = self.cfg.budget
        if self.adaptive == "angle" and self.c_max:
            c = self.c_max
        return min(c, max_degree)

    # -- the corpus in this engine's residency format and layout: fused
    #    stages that run as Pallas kernels gather from the lane-padded
    #    kernel layout (core/corpus.py), built here once so no search or
    #    serving tick pays for it. Paged stores pass through (init_state
    #    refuses them with Pallas fused stages).
    def prepare_store(self, base) -> CorpusStore:
        store = as_corpus_store(base, self.corpus_dtype)
        if self.pallas_fused and not store.is_paged:
            store = store.with_kernel_layout()
        return store

    # -- state init: seed pools with the entry points (one measure call).
    #    iter_caps: optional (Q,) per-lane expansion budgets (defaults to
    #    cfg.iters() — the pre-existing uniform cap).
    def init_state(self, params, store: CorpusStore, neighbors, queries,
                   entries, iter_caps=None, taus=None) -> EngineState:
        Q = queries.shape[0]
        N = store.n
        ef = self.cfg.ef
        nwords = (N + 31) // 32
        if store.is_paged and self.pallas_fused:
            raise ValueError(
                "paged residency requires ref-routed fused stages: Pallas "
                "fused kernels gather from the device-resident payload "
                "(store.data), which a paged store does not hold; use "
                "rank_impl/measure_impl/grad_impl='ref' (the tile plan) or "
                "whole residency")
        if self.measure_fused is not None and not store.is_paged:
            e_scores = self.measure_fused(params, store, entries, queries)
        else:
            # paged stores seed through take() — ONE pager callback — and
            # the fp32 measure math is identical, so fused/unfused seeding
            # is bit-identical either way
            e_scores = self.measure(params, store.take(entries), queries)
        if store.tombstones is not None:
            # a tombstoned entry must never surface in results; the lane
            # simply exhausts (mutate.delete_rows reassigns live entries,
            # so this only triggers for callers bypassing it)
            e_scores = jnp.where(bit_test_global(store.tombstones, entries),
                                 -jnp.inf, e_scores)
        pool_scores = jnp.full((Q, ef), -jnp.inf,
                               jnp.float32).at[:, 0].set(e_scores)
        pool_ids = jnp.full((Q, ef), -1, jnp.int32).at[:, 0].set(entries)
        pool_expanded = jnp.ones((Q, ef), jnp.bool_).at[:, 0].set(False)
        visited = bit_set_rows(jnp.zeros((Q, nwords), jnp.uint32),
                               entries[:, None], jnp.ones((Q, 1), jnp.bool_))
        zeros = jnp.zeros((Q,), jnp.int32)
        if iter_caps is None:
            iter_caps = jnp.full((Q,), self.cfg.iters(), jnp.int32)
        else:
            iter_caps = jnp.asarray(iter_caps, jnp.int32)
        if taus is None:
            taus = jnp.full((Q,), self.angle_tau, jnp.float32)
        else:
            taus = jnp.asarray(taus, jnp.float32)
        return EngineState(pool_scores, pool_ids, pool_expanded, visited,
                           zeros + 1, zeros, zeros,
                           jnp.zeros((Q,), jnp.bool_), iter_caps, taus)

    # -- lane-scoped lifecycle: re-initialize a subset of lanes in place.
    #    The continuous-batching runtime (serving/runtime.py) treats the Q
    #    lanes as slots — when a lane's query converges, a freshly admitted
    #    query is swapped in WITHOUT recompiling: same shapes, the masked
    #    lanes get exactly the state ``init_state`` would give them (entry
    #    seed score, reset pool, zeroed visited slice, reset counters),
    #    every other lane's state passes through untouched. Idle lanes are
    #    parked with ``done=True`` (``idle_state``): pop sees active=False,
    #    so they cost no measure evaluations and stay frozen.
    def reset_lanes(self, params, store: CorpusStore, queries, entries,
                    state: EngineState, mask: jax.Array,
                    iter_caps=None, taus=None) -> EngineState:
        """queries/entries (and optional per-lane ``iter_caps`` /
        adaptive ``taus``): full (Q, Dq)/(Q,) arrays with the NEW values
        already merged into the masked rows; mask: (Q,) bool — True lanes
        are re-initialized, False lanes keep ``state``. Lane-for-lane
        equivalent to ``init_state`` on the masked rows (the parity the
        serving tests pin)."""
        fresh = self.init_state(params, store, None, queries, entries,
                                iter_caps, taus)

        def pick(n, o):
            m = mask.reshape((-1,) + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)

        return jax.tree_util.tree_map(pick, fresh, state)

    def idle_state(self, n_lanes: int, n_corpus: int) -> EngineState:
        """An all-lanes-parked state (done=True everywhere): the runtime's
        starting point before any query is admitted. Shapes match
        ``init_state`` so ``reset_lanes`` / ``step`` apply unchanged."""
        ef = self.cfg.ef
        nwords = (n_corpus + 31) // 32
        # explicit dtypes everywhere: these leaves must carry the same
        # (strongly-typed) avals as jitted step/reset outputs, or the
        # runtime's first steady-state call retraces — a one-off ~quarter
        # second compile spike in the middle of serving traffic
        zeros = jnp.zeros((n_lanes,), jnp.int32)
        return EngineState(
            pool_scores=jnp.full((n_lanes, ef), -jnp.inf, jnp.float32),
            pool_ids=jnp.full((n_lanes, ef), -1, jnp.int32),
            pool_expanded=jnp.ones((n_lanes, ef), jnp.bool_),
            visited=jnp.zeros((n_lanes, nwords), jnp.uint32),
            n_eval=zeros, n_grad=zeros, n_iters=zeros,
            done=jnp.ones((n_lanes,), jnp.bool_), iter_cap=zeros,
            angle_tau=jnp.zeros((n_lanes,), jnp.float32))

    # -- does this step run the fused tile plan? Static per trace: the
    #    plan comes from the autotune cache (or the EngineOptions.tile
    #    override) at the concrete (Q, B, D, dtype) shape. Requires the
    #    fused path (the unfused engine already runs pre-gathered stages)
    #    with ref routing (Pallas fused kernels gather in-kernel — the
    #    rowwise shape — and tiling there is the kernels' own ``bt``), and
    #    the pre-gathered ``grad`` stage when a grad phase exists (always
    #    true for registry-built engines; custom replacements may drop it).
    def _use_tile_plan(self, store: CorpusStore, n_degree: int,
                       Q: int) -> bool:
        fused_on = (self.rank_fused is not None
                    or self.measure_fused is not None
                    or self.grad_fused is not None)
        if not fused_on or self.pallas_fused:
            return False
        if self.grad_fused is not None and self.grad is None:
            return False
        if store.is_paged:
            # paged residency always tiles: ONE combined [frontier |
            # neighbors] gather per step means ONE pager callback instead
            # of three — and the tile plan is already pinned bit-identical
            # to every other fused-ref plan at fp32
            return True
        cfg_t = autotune.resolve(
            "engine_step", q=Q, m=n_degree, d=store.dim,
            dtype=self.corpus_dtype,
            override=autotune.parse_tile(self.tile))
        return cfg_t.plan == "tile"

    # -- one iteration over the whole batch: pop → grad → rank → measure →
    #    insert. qs_flat is the (Q·C, Dq) repeated query block, hoisted out
    #    of the loop because C is static. The fused variants hand (store,
    #    idx) to the stages — neighbor/candidate rows are gathered (and
    #    dequantized) inside them, never staged by the engine — unless the
    #    tuned plan is ``tile``, which gathers the whole step's rows ONCE
    #    (frontier + neighbors, dequant included) into a (Q, 1+B, D) tile
    #    pinned by ``optimization_barrier`` and feeds every pre-gathered
    #    stage from slices of it.
    def step(self, params, store: CorpusStore, neighbors, queries, qs_flat,
             state: EngineState) -> EngineState:
        # jax.named_scope labels the HLO per stage (visible in --profile-dir
        # captures and compiled dumps); trace-time metadata only — the
        # emitted program and its numerics are bit-identical
        Q = queries.shape[0]
        with jax.named_scope("repro_pop"):
            s, pop = self.pop(state)

            nbr = neighbors[pop.fid]                   # (Q, B)
            nbr_safe = jnp.maximum(nbr, 0)
            valid = (nbr >= 0) & ~bit_test_rows(s.visited, nbr) \
                & pop.active[:, None]

        use_tile = self._use_tile_plan(store, neighbors.shape[1], Q)
        with jax.named_scope("repro_grad"):
            if use_tile:
                ids = jnp.concatenate([pop.fid[:, None], nbr_safe], axis=1)
                tile = jax.lax.optimization_barrier(
                    store.take(ids, in_bounds=True))
                x = tile[:, 0, :]                      # (Q, D) f32
                nvecs = tile[:, 1:, :]                 # (Q, B, D)
                if self.grad is not None:
                    _, g = self.grad(params, x, queries)
                    n_grad = s.n_grad + pop.active.astype(jnp.int32)
                else:
                    g, n_grad = None, s.n_grad
            elif self.grad_fused is not None:
                # the fused grad stage gathers (and dequantizes) the
                # frontier rows in-kernel and hands them back for the rank
                # stage — the (Q, D) block never stages through fp32 HBM
                _, g, x = self.grad_fused(params, store, pop.fid, queries)
                n_grad = s.n_grad + pop.active.astype(jnp.int32)
            elif self.grad is not None:
                x = store.take(pop.fid)                # (Q, D) f32
                _, g = self.grad(params, x, queries)
                n_grad = s.n_grad + pop.active.astype(jnp.int32)
            else:
                x = store.take(pop.fid)                # (Q, D) f32
                g, n_grad = None, s.n_grad

        with jax.named_scope("repro_rank"):
            # per-lane adaptive cutoff: the trailing tau arg exists ONLY on
            # the adaptive path, so adaptive='off' emits the identical call
            # graph (and keeps 4/5-arg custom stage doubles working)
            targs = (state.angle_tau,) if self.adaptive == "angle" else ()
            if self.rank_fused is not None and not use_tile:
                sel_idx, sel_mask = self.rank_fused(x, g, store, nbr_safe,
                                                    valid, *targs)
                nvecs = None
            else:
                if not use_tile:
                    nvecs = store.take(nbr_safe)       # (Q, B, D)
                sel_idx, sel_mask = self.rank(x, g, nvecs, valid,
                                              *targs)   # (Q, C)
            sel_ids = jnp.take_along_axis(nbr, sel_idx, axis=1)

        C = sel_idx.shape[1]
        with jax.named_scope("repro_measure"):
            if self.measure_fused is not None and not use_tile:
                # adaptive: the per-lane prefix mask rides into the fused
                # kernel so fully-masked candidate tiles skip their score
                # math via the kernels' tail-masking grid (masked rows come
                # back -inf either way; the where below is then idempotent)
                mkw = ({"mask": sel_mask.reshape(Q * C)}
                       if self.adaptive == "angle" else {})
                flat_scores = self.measure_fused(
                    params, store,
                    jnp.maximum(sel_ids, 0).reshape(Q * C), qs_flat, **mkw)
            else:
                # sel_idx comes from top-k over axis 1, so it's in-bounds
                # by construction — the tile plan drops the out-of-bounds
                # select
                mode = "clip" if use_tile else None
                sel_vecs = jnp.take_along_axis(nvecs, sel_idx[..., None],
                                               axis=1, mode=mode)
                flat_scores = self.measure(params,
                                           sel_vecs.reshape(Q * C, -1),
                                           qs_flat)
            scores = jnp.where(sel_mask, flat_scores.reshape(Q, C),
                               -jnp.inf)
            if store.tombstones is not None:
                # streaming deletes: tombstoned candidates score -inf —
                # the padded-row convention of the sharded merge — so they
                # stay traversable (their edges still route) but never
                # enter results
                scores = jnp.where(
                    bit_test_global(store.tombstones, sel_ids),
                    -jnp.inf, scores)

        with jax.named_scope("repro_insert"):
            s = s._replace(
                visited=bit_set_rows(s.visited, sel_ids, sel_mask),
                n_grad=n_grad,
                n_eval=s.n_eval + jnp.sum(sel_mask, axis=1).astype(jnp.int32),
                n_iters=s.n_iters + pop.active.astype(jnp.int32))
            s = self.insert(s, sel_ids, scores, sel_mask)

            exhausted = ~jnp.any(
                ~s.pool_expanded & jnp.isfinite(s.pool_scores), axis=1)
            done = state.done | exhausted | (s.n_iters >= s.iter_cap) \
                | ~pop.active
        return s._replace(done=done)

    def _result(self, final: EngineState) -> SearchResult:
        k = self.cfg.k
        return SearchResult(ids=final.pool_ids[:, :k],
                            scores=final.pool_scores[:, :k],
                            n_eval=final.n_eval, n_grad=final.n_grad,
                            n_iters=final.n_iters)

    # -- jitted whole-search path (serving / benchmarks)
    @functools.cached_property
    def _run_jit(self):
        def run(params, base, neighbors, queries, entries, iter_caps, taus):
            store = self.prepare_store(base)
            state = self.init_state(params, store, neighbors, queries,
                                    entries, iter_caps, taus)
            C = self.n_candidates(neighbors.shape[1])
            qs_flat = jnp.repeat(queries, C, axis=0)

            def cond(s):
                return ~jnp.all(s.done)

            def body(s):
                s2 = self.step(params, store, neighbors, queries, qs_flat, s)
                return _freeze_done(s.done, s2, s)

            return self._result(jax.lax.while_loop(cond, body, state))
        return jax.jit(run)

    def search(self, params, base, neighbors, queries, entries,
               iter_caps=None, taus=None) -> SearchResult:
        """base: (N, D) array or a pre-built ``CorpusStore`` (the serving
        path quantizes once up front; a raw array is converted — one fused
        pass — per call); neighbors: (N, B) int32 -1-padded; queries:
        (Q, Dq); entries: (Q,) int32; iter_caps: optional (Q,) per-query
        expansion budgets (anytime/SLA-tier search — defaults to the
        uniform cfg cap); taus: optional (Q,) per-query adaptive angle
        cutoffs (adaptive='angle' only — defaults to the engine's
        ``angle_tau``). Returns SearchResult with (Q, ...) leaves."""
        if iter_caps is None:
            iter_caps = jnp.full((queries.shape[0],), self.cfg.iters(),
                                 jnp.int32)
        if taus is None:
            taus = jnp.full((queries.shape[0],), self.angle_tau, jnp.float32)
        from repro.obs.profile import annotate
        with annotate("repro/search"):
            return self._run_jit(params, base, neighbors, queries, entries,
                                 jnp.asarray(iter_caps, jnp.int32),
                                 jnp.asarray(taus, jnp.float32))

    # -- host loop: same stage code, one Python call per iteration. By
    #    default each (init, step) runs through a cached jax.jit so the
    #    compiled arithmetic is the program `search` runs — ids AND scores
    #    bit-identical (eager op-by-op dispatch rounds differently where
    #    XLA fuses, e.g. mul+add → FMA on CPU). Pass jit_steps=False for
    #    plain-Python stage observability — wrap stages (e.g. a
    #    call-counting double via dataclasses.replace) to assert batching
    #    invariants; jitted stages would only record at trace time.
    @functools.cached_property
    def _debug_jits(self):
        def init(params, store, neighbors, queries, entries, iter_caps,
                 taus):
            return self.init_state(params, store, neighbors, queries,
                                   entries, iter_caps, taus)

        def one(params, store, neighbors, queries, qs_flat, state):
            s2 = self.step(params, store, neighbors, queries, qs_flat, state)
            return _freeze_done(state.done, s2, state)
        return jax.jit(init), jax.jit(one)

    def search_debug(self, params, base, neighbors, queries, entries,
                     max_steps: Optional[int] = None,
                     on_step: Optional[Callable[[int, EngineState], None]]
                     = None, iter_caps=None, taus=None,
                     jit_steps: bool = True) -> SearchResult:
        entries = jnp.asarray(entries, jnp.int32)
        store = self.prepare_store(base)
        if jit_steps:
            init_fn, step_fn = self._debug_jits
            caps = jnp.full((queries.shape[0],), self.cfg.iters(),
                            jnp.int32) if iter_caps is None \
                else jnp.asarray(iter_caps, jnp.int32)
            ts = jnp.full((queries.shape[0],), self.angle_tau,
                          jnp.float32) if taus is None \
                else jnp.asarray(taus, jnp.float32)
            state = init_fn(params, store, neighbors, queries, entries,
                            caps, ts)
        else:
            def step_fn(params, store, neighbors, queries, qs_flat, s):
                s2 = self.step(params, store, neighbors, queries, qs_flat, s)
                return _freeze_done(s.done, s2, s)
            state = self.init_state(params, store, neighbors, queries,
                                    entries, iter_caps, taus)
        C = self.n_candidates(neighbors.shape[1])
        qs_flat = jnp.repeat(queries, C, axis=0)
        if max_steps is not None:
            limit = max_steps
        else:
            # per-lane caps above the uniform config cap must extend the
            # eager loop too, or debug would silently diverge from search()
            limit = self.cfg.iters() + 1
            if iter_caps is not None:
                limit = max(limit, int(jnp.max(jnp.asarray(iter_caps))) + 1)
        steps = 0
        while steps < limit and not bool(jnp.all(state.done)):
            state = step_fn(params, store, neighbors, queries, qs_flat,
                            state)
            steps += 1
            if on_step is not None:
                on_step(steps, state)
        return self._result(state)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _build(score_fn, meta, cfg: SearchConfig,
           options: EngineOptions) -> ExpansionEngine:
    """Assemble an engine. Measure→stage selection flows exclusively
    through the ``MeasureKernelBundle`` registry (``resolve_stages``) —
    this builder contains no measure-name or meta-tuple conditionals."""
    if options.adaptive not in ("off", "angle"):
        raise ValueError(f"EngineOptions.adaptive must be 'off' or 'angle', "
                         f"got {options.adaptive!r}")
    if options.adaptive == "angle":
        # the adaptive cutoff is an ANGLE (radians between the query
        # gradient and each neighbor offset) — it has no meaning for
        # projection keys or the no-grad sl2g mode
        if cfg.mode != "guitar" or cfg.rank_by != "angle":
            raise ValueError(
                "EngineOptions(adaptive='angle') requires SearchConfig("
                f"mode='guitar', rank_by='angle'); got mode={cfg.mode!r}, "
                f"rank_by={cfg.rank_by!r}")
    stages = resolve_stages(score_fn, meta, options)
    if cfg.mode == "guitar":
        grad, grad_fused = stages.grad, stages.grad_fused
        rank = make_guitar_rank_stage(cfg, options)
        rank_fused = make_guitar_rank_fused_stage(cfg, options) \
            if options.fused else None
    else:
        grad = grad_fused = None
        rank = select_all_rank_stage
        rank_fused = select_all_rank_fused_stage if options.fused else None
    # does any fused stage route to a Pallas kernel? The tile plan only
    # applies to ref-routed fused stages (Pallas kernels gather in-kernel)
    pallas_fused = options.fused and (
        use_pallas_impl(options.rank_impl)
        or use_pallas_impl(options.measure_impl)
        or use_pallas_impl(options.grad_impl))
    return ExpansionEngine(cfg=cfg, pop=default_pop_stage, rank=rank,
                           measure=stages.measure,
                           insert=default_insert_stage,
                           grad=grad, rank_fused=rank_fused,
                           measure_fused=stages.measure_fused,
                           corpus_dtype=options.corpus_dtype,
                           grad_fused=grad_fused,
                           tile=options.tile,
                           pallas_fused=pallas_fused,
                           adaptive=options.adaptive,
                           c_max=options.c_max,
                           angle_tau=options.angle_tau)


@functools.lru_cache(maxsize=128)
def _build_cached(score_fn, meta, cfg, options):
    return _build(score_fn, meta, cfg, options)


def build_engine_from_fn(score_fn, cfg: SearchConfig,
                         options: EngineOptions = EngineOptions(),
                         meta: Optional[Tuple] = None) -> ExpansionEngine:
    """Engine for a bare ``score_fn(params, x, q) -> scalar``. Pass the
    measure's ``meta`` tuple to resolve its kernel bundle (the sharded path
    does); without one the generic vmap/autodiff stages apply. Cached per
    (score_fn, meta, cfg, options) so repeated calls reuse the compiled
    search."""
    meta = tuple(meta) if meta is not None else None
    return _build_cached(score_fn, meta, cfg, options)


def build_engine(measure, cfg: SearchConfig,
                 options: EngineOptions = EngineOptions()) -> ExpansionEngine:
    """Engine for a ``Measure``. Stage selection resolves the measure's
    ``meta = (family, *args)`` against the ``MeasureKernelBundle`` registry
    (core/bundles.py) — e.g. ``('deepfm', fm_dim)`` routes the score AND
    gradient stages through the analytic DeepFM kernels — falling back to
    the generic vmap stages for unregistered families."""
    meta = getattr(measure, "meta", None)
    meta = tuple(meta) if meta is not None else None
    return _build_cached(measure.score_fn, meta, cfg, options)


def engine_search(measure, base, neighbors, queries, entries,
                  cfg: SearchConfig,
                  options: EngineOptions = EngineOptions()) -> SearchResult:
    """One-call convenience: build (cached) + run."""
    eng = build_engine(measure, cfg, options)
    return eng.search(measure.params, base, neighbors, queries, entries)
