"""Shared benchmark harness: builds the Twitch-/Amazon-stand-in systems
(train DeepFM on synthetic interactions, extract base/query vectors, build
the SL2G graph, compute exhaustive ground truth) and provides the
recall-vs-cost sweep used by every figure reproduction.

Scale note (documented in EXPERIMENTS.md): the container is offline and
single-core, so Table-1 scales (740k/3.8M items) are stood in for by
TWITCH_BENCH / AMAZON_BENCH (20k/40k items) from configs/guitar_deepfm.py.
All *relative* claims (GUITAR vs SL2G evaluation counts, alpha behaviour,
angle-vs-projection, BEGIN composition) are scale-free.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import pickle
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.guitar_deepfm import (AMAZON_BENCH, TWITCH_BENCH,
                                         GuitarExperiment, measure_config)
from repro.core import (Measure, SearchConfig, brute_force_topk,
                        deepfm_measure, deepfm_numpy_fns, mlp_measure,
                        recall, search_legacy, search_measure)
from repro.data import make_interactions
from repro.graph import GraphIndex, build_l2_graph
from repro.models import deepfm as deepfm_lib
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig

# Generated caches only — gitignored; build_system() regenerates on miss.
CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "reports", "bench_cache")


def quickstart_corpus(n: int = 5000, dim: int = 32,
                      seed: int = 0) -> np.ndarray:
    """The examples/quickstart.py corpus (gaussian items) — the shared small
    corpus for construction parity gates and micro-benchmarks."""
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, dim)).astype(np.float32)


@dataclasses.dataclass
class BenchSystem:
    name: str
    params: dict
    cfg: deepfm_lib.DeepFMConfig
    base: np.ndarray
    queries: np.ndarray
    graph: GraphIndex
    true_ids: Dict[int, np.ndarray]   # k -> (Q, k) ground truth
    measure_family: str = "deepfm"    # registry family the sweeps run on
    # NOTE: the Measure (jit closure) is rebuilt via rebuild_measure() —
    # closures don't pickle into the bench cache.


def _family_measure(family: str, params: dict,
                    cfg: deepfm_lib.DeepFMConfig) -> Measure:
    """The bench measure for a registry family over the system's vectors.
    deepfm uses the trained measure MLP; mlp is a fresh deterministic
    (PRNGKey(0)) 'heavier f' network over the same vectors — ground truth
    is recomputed per family, so relative sweep claims stay valid."""
    if family == "deepfm":
        return deepfm_measure(params, cfg)
    if family == "mlp":
        import jax
        return mlp_measure(jax.random.PRNGKey(0), cfg.vec_dim, cfg.vec_dim,
                           hidden=(64, 64))
    raise ValueError(f"unknown bench measure family {family!r}")


def _base_fingerprint(sys: "BenchSystem") -> str:
    """Identity of the trained system a derived-family cache was computed
    from — derived pickles store it and are rebuilt when the base system
    changes underneath them (cross-family sweeps must share one corpus)."""
    import hashlib
    h = hashlib.sha1()
    h.update(np.ascontiguousarray(sys.base).tobytes())
    h.update(np.ascontiguousarray(sys.queries).tobytes())
    h.update(np.ascontiguousarray(sys.graph.neighbors).tobytes())
    return h.hexdigest()


def build_system(exp: GuitarExperiment, train_steps: int = 60,
                 ks=(1, 10, 50, 100), seed: int = 0,
                 cache: bool = True,
                 measure_family: str = "deepfm") -> BenchSystem:
    os.makedirs(CACHE_DIR, exist_ok=True)
    if measure_family != "deepfm":
        # non-deepfm families reuse the trained vectors + graph of the
        # deepfm system (cached) and only relabel the ground truth under
        # their own measure; the derived pickle is keyed to the base
        # system's fingerprint so it can never outlive a retrain
        base_sys = build_system(exp, train_steps, ks, seed, cache)
        fp = _base_fingerprint(base_sys)
        cpath = os.path.join(CACHE_DIR,
                             f"{exp.name}-{measure_family}.pkl")
        if cache and os.path.exists(cpath):
            with open(cpath, "rb") as f:
                payload = pickle.load(f)
            if isinstance(payload, dict) and payload.get("base_fp") == fp:
                return payload["sys"]
        measure = _family_measure(measure_family, base_sys.params,
                                  base_sys.cfg)
        kmax = max(ks)
        ids, _ = brute_force_topk(measure, jnp.asarray(base_sys.base),
                                  jnp.asarray(base_sys.queries), kmax)
        ids = np.asarray(ids)
        sys = dataclasses.replace(
            base_sys, true_ids={k: ids[:, :k] for k in ks},
            measure_family=measure_family)
        if cache:
            with open(cpath, "wb") as f:
                pickle.dump({"sys": sys, "base_fp": fp}, f)
        return sys
    cpath = os.path.join(CACHE_DIR, f"{exp.name}.pkl")
    if cache and os.path.exists(cpath):
        with open(cpath, "rb") as f:
            return pickle.load(f)

    cfg = measure_config(n_users=exp.n_queries, n_items=exp.n_items)
    params, _ = deepfm_lib.init_model(jax.random.PRNGKey(seed), cfg)
    data = make_interactions(exp.n_queries, exp.n_items,
                             n_inter=20 * exp.n_items, seed=seed)
    params = dict(params)
    params["users"] = jnp.asarray(data["user_init"][:, :cfg.vec_dim])
    params["items"] = jnp.asarray(data["item_init"][:, :cfg.vec_dim])

    def loss_fn(p, b):
        return deepfm_lib.interaction_loss(p, b["u"], b["i"], b["y"], cfg)

    def batch_fn(step):
        r = np.random.default_rng(step)
        idx = r.integers(0, data["user_ids"].shape[0], 1024)
        return {"u": jnp.asarray(data["user_ids"][idx]),
                "i": jnp.asarray(data["item_ids"][idx]),
                "y": jnp.asarray(data["labels"][idx])}

    tr = Trainer(loss_fn, params, OptimizerConfig(lr=3e-3, total_steps=train_steps * 2),
                 TrainerConfig(total_steps=train_steps, ckpt_every=10**9))
    tr.run(batch_fn)
    params = {k: np.asarray(v) if not isinstance(v, dict) else
              jax.tree_util.tree_map(np.asarray, v)
              for k, v in tr.params.items()}

    base = np.asarray(params["items"], np.float32)
    queries = np.asarray(params["users"], np.float32)[: exp.n_test_queries]
    measure = deepfm_measure(params, cfg)
    graph = build_l2_graph(base, m=exp.m, k_construction=exp.k_construction,
                           seed=seed)
    kmax = max(ks)
    ids, _ = brute_force_topk(measure, jnp.asarray(base), jnp.asarray(queries),
                              kmax)
    ids = np.asarray(ids)
    true_ids = {k: ids[:, :k] for k in ks}
    sys = BenchSystem(exp.name, params, cfg, base, queries, graph, true_ids)
    if cache:
        with open(cpath, "wb") as f:
            pickle.dump(sys, f)
    return sys


def rebuild_measure(sys: BenchSystem) -> Measure:
    """Measure objects don't survive pickling of jitted closures cleanly —
    rebuild from params (+ the system's measure family; pre-family cache
    pickles lack the field and default to deepfm)."""
    family = getattr(sys, "measure_family", "deepfm")
    return _family_measure(family, sys.params, sys.cfg)


@dataclasses.dataclass
class SweepPoint:
    recall: float
    qps: float
    total_evals: float     # #NN + 2*#Grad per query (paper's 'Total')
    n_eval: float
    n_grad: float
    ef: int
    params: dict


def run_sweep(sys: BenchSystem, mode: str, k: int, efs=None,
              alpha: float = 1.01, budget: int = 8, rank_by: str = "angle",
              graph: Optional[GraphIndex] = None,
              time_queries: bool = True,
              searcher: str = "engine") -> List[SweepPoint]:
    """Sweep ef (the paper's k_search) -> (recall, QPS, Total) points.
    ``searcher``: 'engine' (staged batch-major pipeline) | 'legacy'."""
    if searcher not in ("engine", "legacy"):
        raise ValueError(f"unknown searcher {searcher!r}")
    graph = graph or sys.graph
    measure = rebuild_measure(sys)

    def run_search(base_j, nbrs_j, queries_j, entries, cfg):
        if searcher == "legacy":
            return search_legacy(measure.score_fn, measure.params, base_j,
                                 nbrs_j, queries_j, entries, cfg)
        return search_measure(measure, base_j, nbrs_j, queries_j, entries, cfg)

    efs = efs or [max(k, e) for e in (8, 16, 32, 64, 128, 256)]
    Q = sys.queries.shape[0]
    base_j = jnp.asarray(graph.base)
    nbrs_j = jnp.asarray(graph.neighbors)
    queries_j = jnp.asarray(sys.queries)
    entries = jnp.full((Q,), graph.entry, jnp.int32)
    out = []
    for ef in efs:
        cfg = SearchConfig(k=k, ef=ef, budget=budget, alpha=alpha, mode=mode,
                           rank_by=rank_by)
        res = run_search(base_j, nbrs_j, queries_j, entries, cfg)
        jax.block_until_ready(res.ids)
        if time_queries:
            t0 = time.perf_counter()
            res = run_search(base_j, nbrs_j, queries_j, entries, cfg)
            jax.block_until_ready(res.ids)
            dt = time.perf_counter() - t0
            qps = Q / dt
        else:
            qps = 0.0
        r = recall(res.ids, jnp.asarray(sys.true_ids[k]))
        total = float(res.n_eval.mean() + 2.0 * res.n_grad.mean())
        out.append(SweepPoint(r, qps, total, float(res.n_eval.mean()),
                              float(res.n_grad.mean()), ef,
                              {"alpha": alpha, "mode": mode, "rank_by": rank_by}))
    return out


def frontier(points: List[SweepPoint], by: str = "total_evals"
             ) -> List[SweepPoint]:
    """Pareto frontier: max recall per cost bucket (paper's bucketing)."""
    pts = sorted(points, key=lambda p: getattr(p, by))
    out, best_r = [], -1.0
    for p in pts:
        if p.recall > best_r:
            out.append(p)
            best_r = p.recall
    return out


def speedup_at_recall(pts_a: List[SweepPoint], pts_b: List[SweepPoint],
                      level: float, by: str = "total_evals") -> Optional[float]:
    """cost_b / cost_a at the first point reaching `level` recall
    (>1 means a is cheaper)."""
    def cost_at(pts):
        for p in sorted(pts, key=lambda p: getattr(p, by)):
            if p.recall >= level:
                return getattr(p, by)
        return None
    ca, cb = cost_at(pts_a), cost_at(pts_b)
    if ca is None or cb is None:
        return None
    return cb / ca


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.2f},{derived}"


_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def expansion_bytes_model(Q: int, B: int, C: int, D: int,
                          corpus_dtype: str = "float32",
                          fused: bool = False) -> int:
    """Corpus-side HBM bytes moved per expansion step (DESIGN.md §8).

    Pre-gathered path: the engine reads Q·B corpus rows, STAGES them as a
    fp32 (Q, B, D) block (one HBM write + one read-back by the rank kernel),
    then stages the selected (Q·C, D) fp32 candidate block for the measure
    kernel the same way. Index-fused path: each kernel reads its rows once,
    straight from the resident corpus, in residency width — no staged
    intermediates. int8 adds 4 bytes/row of scale traffic.

    Residency width is what the jnp-ref fused path reads. The Pallas fused
    kernels on TPU gather from the store's kernel layout instead
    (``CorpusStore.kernel_rows``): float32 rows padded to 128 lanes, so
    each row read there costs ``4 * ceil(D / 128) * 128`` bytes whatever
    the dtype — 512 B at D=40 or 64 (DESIGN.md §8).
    """
    s = _DTYPE_BYTES[corpus_dtype]
    scale = 4 if corpus_dtype == "int8" else 0
    if fused:
        rank_bytes = Q * B * (D * s + scale)
        measure_bytes = Q * C * (D * s + scale)
        return rank_bytes + measure_bytes
    # corpus read (residency width) + fp32 staging write+read, twice over
    gather = Q * B * (D * s + scale)
    stage_rank = 2 * Q * B * D * 4
    stage_measure = 2 * Q * C * D * 4
    return gather + stage_rank + stage_measure


def grad_stage_bytes_model(Q: int, D: int, corpus_dtype: str = "float32",
                           fused: bool = False) -> int:
    """Corpus-side HBM bytes the GRAD stage moves per expansion step
    (DESIGN.md §8, grad extension). Pre-gathered path: the engine gathers
    the (Q, D) frontier (residency-width corpus read), stages it as a fp32
    block (one HBM write), and the grad stage reads it back — 3 passes.
    Index-fused path (``grad_fused``): the kernel reads each frontier row
    once, straight from the resident corpus, in residency width, plus ONE
    fp32 write of the dequantized rows it hands the rank stage (charged
    honestly — that write replaces the engine's whole gather+stage+read
    cycle). int8 adds 4 bytes/row of scale traffic. As in
    ``expansion_bytes_model``, the fused read is at residency width; the
    TPU kernels read 512 B per row at D=40 from the lane-padded kernel
    layout, which leaves fused and pre-gathered at about the same bytes
    there (DESIGN.md §8)."""
    s = _DTYPE_BYTES[corpus_dtype]
    scale = 4 if corpus_dtype == "int8" else 0
    if fused:
        return Q * (D * s + scale) + Q * D * 4
    return Q * (D * s + scale) + 2 * Q * D * 4
