"""Chip smoke test: drive the GUITAR search path once on a TPU, at the
paper's widths, through the entry points a user calls, and check what
comes out.

    python chip_smoke.py                  # one chip: N=100k, D=40 DeepFM
    python chip_smoke.py --items 739991   # Twitch's Table-1 corpus size
    python chip_smoke.py --four-chips     # corpus sharded over four chips

One chip: a seeded corpus, the L2 graph at the paper's M=24 / k=100, the
DeepFM measure ``serve`` builds, then one-shot searches at batch 32 and 128
with the default engine, a few hundred requests through the continuous
runtime, and the fused path at float32 and bfloat16 residency. Recall@10
is taken against the exhaustive float32 reference, and ids and scores
against the same engine on its jnp reference stages. The lowered search
must hold the rank, score and grad kernels as ``tpu_custom_call``s.

``--four-chips`` runs only the corpus-sharded ``shard_map`` search over a
(1, 4) ("data", "model") mesh and compares it with the host-merged search
over the same shards.

Everything runs in this one process. With no TPU, or any failed check, it
exits non-zero and prints no result; otherwise the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

D = 40               # item/user width: FM 8 + deep 32 (configs/guitar_deepfm.py)
K = 10
# Agreement limits, fixed before the chip run. Recall@10 is a mean over
# queries of hits/10, so 0.02 is two hits in every hundred returned ids.
RECALL_TOL = 0.02
# bfloat16 residency rounds the corpus the measure reads, so its search may
# legitimately rank differently from the float32 reference.
BF16_RECALL_TOL = 0.05
# Scores are sigmoid outputs in (0, 1); a float32 evaluation of the same
# row agrees to a few ulps. 1e-4 admits reassociated sums, not bf16 passes.
SCORE_ATOL = 1e-4
# Share of returned ids the kernel path must share with the reference-stage
# path. Both walk the same graph, so the two lists differ only where
# float32 rounding flips a tie between candidates.
ID_OVERLAP_MIN = 0.95


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[smoke] check {name}: {'ok' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.mean([len(set(x) & set(y)) / len(y)
                          for x, y in zip(a.tolist(), b.tolist())]))


def kernel_names(text: str) -> set:
    import re
    return set(re.findall(r'kernel_name = "([a-z_]+)"', text))


def exact_scores(measure, base, queries, ids):
    """The measure at float32 (``highest``) on (query, returned id)."""
    import jax
    import jax.numpy as jnp
    rows = jnp.asarray(base)[jnp.maximum(jnp.asarray(ids), 0)]
    fn = jax.vmap(jax.vmap(lambda x, q: measure.score_fn(measure.params, x, q),
                           in_axes=(0, None)))
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(rows, jnp.asarray(queries)))


def one_chip(args, check: Checks) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import (EngineOptions, SearchConfig, brute_force_topk,
                            build_engine, make_family_measure, recall)
    from repro.graph import build_l2_graph
    from repro.serving import ContinuousRuntime, Request

    rng = np.random.default_rng(args.seed)
    base = rng.standard_normal((args.items, D), dtype=np.float32)
    n_q = max(args.requests, 128)
    queries = rng.standard_normal((n_q, D), dtype=np.float32)
    t0 = time.perf_counter()
    graph = build_l2_graph(base, m=24, k_construction=100, seed=args.seed)
    log(f"set-up: index build {time.perf_counter() - t0:.3f} s "
        f"(N={args.items}, D={D}, M=24, k_construction=100, "
        f"max degree {graph.max_degree})")
    measure = make_family_measure("deepfm", jax.random.PRNGKey(0), D)
    cfg = SearchConfig(k=K, ef=64, budget=8, alpha=1.01)
    base_j = jnp.asarray(base)
    nbrs_j = jnp.asarray(graph.neighbors)
    t0 = time.perf_counter()
    true_ids, _ = brute_force_topk(measure, base_j, jnp.asarray(queries), K)
    true_ids = np.asarray(true_ids)
    log(f"set-up: exhaustive float32 reference for {n_q} queries "
        f"{time.perf_counter() - t0:.3f} s")
    log(f"tolerances: recall {RECALL_TOL}, bf16 recall {BF16_RECALL_TOL}, "
        f"score atol {SCORE_ATOL}, id overlap >= {ID_OVERLAP_MIN}")

    def run(options, corpus, batch, expect, precision=None):
        eng = build_engine(measure, cfg, options)
        q = jnp.asarray(queries[:batch])
        e = jnp.full((batch,), graph.entry, jnp.int32)
        if expect:
            names = kernel_names(jax.jit(eng.search).lower(
                measure.params, corpus, nbrs_j, q, e).as_text())
            check(f"kernels[{'+'.join(expect)}]", set(expect) <= names,
                  f"tpu_custom_call kernels {sorted(names)}")
        t0 = time.perf_counter()
        with jax.default_matmul_precision(precision):
            res = eng.search(measure.params, corpus, nbrs_j, q, e)
            ids = np.asarray(res.ids)
        return ids, np.asarray(res.scores), time.perf_counter() - t0

    unfused = ("neighbor_rank", "deepfm_score", "deepfm_grad")
    for batch in (32, 128):
        ids, scores, cold = run(EngineOptions(), base_j, batch, unfused)
        log(f"set-up: one-shot batch {batch} first call (compile + run) "
            f"{cold:.3f} s")
        ref_ids, _, _ = run(EngineOptions(rank_impl="ref",
                                          measure_impl="vmap",
                                          grad_impl="vmap"),
                            base_j, batch, (), precision="highest")
        r_k = recall(ids, true_ids[:batch])
        r_r = recall(ref_ids, true_ids[:batch])
        log(f"one-shot batch {batch}: recall@10 kernels {r_k:.4f}, "
            f"reference stages {r_r:.4f}")
        check(f"recall[batch {batch}]", abs(r_k - r_r) <= RECALL_TOL,
              f"|{r_k:.4f} - {r_r:.4f}| <= {RECALL_TOL}")
        ov = overlap(ids, ref_ids)
        check(f"ids[batch {batch}]", ov >= ID_OVERLAP_MIN,
              f"overlap {ov:.4f}")
        err = float(np.max(np.abs(
            scores - exact_scores(measure, base, queries[:batch], ids))))
        check(f"scores[batch {batch}]", err <= SCORE_ATOL,
              f"max |kernel - float32| {err:.3g}")
        if batch == 32:
            unfused_ids = ids

    eng = build_engine(measure, cfg, EngineOptions())
    rt = ContinuousRuntime(eng, measure.params, base_j, nbrs_j, n_lanes=64,
                           query_dim=D, entry=graph.entry, steps_per_tick=8)
    t0 = time.perf_counter()
    rt.warmup(queries[0])
    log(f"set-up: continuous runtime warmup {time.perf_counter() - t0:.3f} s")
    done = rt.run_stream([Request(rid=i, query=queries[i])
                          for i in range(args.requests)], realtime=False)
    by_rid = {c.rid: c for c in done}
    statuses = sorted({c.status for c in done})
    check("continuous[all ok]",
          len(by_rid) == args.requests
          and all(c.status == "ok" for c in done),
          f"{len(done)} completions, statuses {statuses}")
    if len(by_rid) == args.requests:
        got = np.stack([by_rid[i].ids for i in range(args.requests)])
        r_c = recall(got, true_ids[:args.requests])
        log(f"continuous: {args.requests} requests on 64 lanes, "
            f"recall@10 {r_c:.4f}")

    fused = ("neighbor_rank_fused", "deepfm_score_fused", "deepfm_grad_fused")
    recall_f32 = None
    for dtype in ("float32", "bfloat16"):
        opts = EngineOptions(fused=True, corpus_dtype=dtype)
        store = build_engine(measure, cfg, opts).prepare_store(base_j)
        ids, scores, cold = run(opts, store, 32, fused)
        r_f = recall(ids, true_ids[:32])
        log(f"fused {dtype}: first call {cold:.3f} s, recall@10 {r_f:.4f}")
        if dtype == "float32":
            recall_f32 = r_f
            ov = overlap(ids, unfused_ids)
            check("fused[float32 ids]", ov >= ID_OVERLAP_MIN,
                  f"overlap with the unfused kernels {ov:.4f}")
            err = float(np.max(np.abs(
                scores - exact_scores(measure, base, queries[:32], ids))))
            check("fused[float32 scores]", err <= SCORE_ATOL,
                  f"max |kernel - float32| {err:.3g}")
        else:
            check("fused[bfloat16 recall]",
                  abs(r_f - recall_f32) <= BF16_RECALL_TOL,
                  f"|{r_f:.4f} - {recall_f32:.4f}| <= {BF16_RECALL_TOL}")


def four_chips(args, check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core import (SearchConfig, brute_force_topk,
                            make_family_measure, recall)
    from repro.core.sharded import (build_sharded_index, place_index,
                                    shard_stores, sharded_search_host,
                                    sharded_search_stores)

    devs = jax.devices()
    if not check("devices[4]", len(devs) >= 4, f"{len(devs)} devices"):
        return
    rng = np.random.default_rng(args.seed)
    base = rng.standard_normal((args.items, D), dtype=np.float32)
    queries = rng.standard_normal((32, D), dtype=np.float32)
    t0 = time.perf_counter()
    index = build_sharded_index(base, 4, m=24, k_construction=100,
                                seed=args.seed)
    log(f"set-up: 4-shard index build {time.perf_counter() - t0:.3f} s "
        f"({index.base.shape[1]} rows per shard)")
    measure = make_family_measure("deepfm", jax.random.PRNGKey(0), D)
    cfg = SearchConfig(k=K, ef=64, budget=8, alpha=1.01)
    mesh = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    spans = len(place_index(index, mesh)[0].sharding.device_set)
    check("sharded corpus spans 4 devices", spans == 4, f"{spans} devices")
    t0 = time.perf_counter()
    res_mesh = sharded_search_host(measure, index, queries, cfg, mesh)
    log(f"set-up: shard_map search first call {time.perf_counter() - t0:.3f} s")
    res_host = sharded_search_stores(measure, shard_stores(index), index,
                                     queries, cfg)
    true_ids = np.asarray(brute_force_topk(measure, jnp.asarray(base),
                                           jnp.asarray(queries), K)[0])
    r_mesh = recall(res_mesh.ids, true_ids)
    r_host = recall(res_host.ids, true_ids)
    same = np.array_equal(res_mesh.ids, res_host.ids)
    log(f"four chips: recall@10 shard_map {r_mesh:.4f}, host merge "
        f"{r_host:.4f}, ids equal {same}")
    check("four chips[ids equal]", same,
          f"{int(np.sum(res_mesh.ids != res_host.ids))} of "
          f"{res_mesh.ids.size} ids differ")
    check("four chips[recall equal]", r_mesh == r_host,
          f"{r_mesh:.4f} vs {r_host:.4f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--items", type=int, default=100_000,
                    help="corpus rows (Twitch's Table-1 size is 739991)")
    ap.add_argument("--requests", type=int, default=256,
                    help="queries for the continuous runtime and recall")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the corpus-sharded search on 4 chips")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[smoke] no TPU: JAX found {devs[0].platform} devices; "
              f"this smoke has no CPU fallback", file=sys.stderr)
        return 2
    from repro.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"device: {devs[0].platform} {devs[0].device_kind}, "
        f"{len(devs)} device(s); jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args, check)
    log(f"total {time.perf_counter() - t0:.3f} s")
    if check.failed:
        print(f"[smoke] FAILED: {', '.join(check.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
