"""Index-build launcher: construct a GUITAR/SL2G index once, persist it with
``repro.graph.io``, and reuse it from ``serve.py``, benchmarks, and tests —
construction and serving are separate jobs at scale.

    # single-partition index over a saved (N, D) .npy corpus
    PYTHONPATH=src python -m repro.launch.build_index \
        --base corpus.npy --m 24 --out runs/index

    # corpus-sharded index (4 partitions) over a synthetic corpus
    PYTHONPATH=src python -m repro.launch.build_index \
        --items 20000 --dim 32 --shards 4 --out runs/sharded-index

    # measure-aware (BEGIN) index under the registry-resolved measure —
    # the same deterministic measure serve.py builds for that family/dim
    PYTHONPATH=src python -m repro.launch.build_index \
        --items 10000 --dim 32 --graph begin --measure deepfm --out runs/bg
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import numpy as np

from repro.core.begin import build_begin_graph
from repro.core.measures import MEASURE_FAMILIES, make_family_measure
from repro.core.sharded import build_sharded_index
from repro.graph import build_l2_graph, save_index
from repro.utils import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None) -> str:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--base", type=str, default=None,
                    help="path to an (N, D) .npy corpus; synthetic if unset")
    ap.add_argument("--items", type=int, default=10000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--m", type=int, default=24)
    ap.add_argument("--k-construction", type=int, default=64)
    ap.add_argument("--shards", type=int, default=0,
                    help="0 = single partition, else corpus-sharded build")
    ap.add_argument("--impl", choices=["blocked", "ref"], default="blocked")
    ap.add_argument("--graph", choices=["l2", "begin"], default="l2",
                    help="l2 = SL2G construction; begin = measure-aware "
                         "bipartite-derived adjacency (spends offline "
                         "neural-measure evaluations, core/begin.py)")
    ap.add_argument("--measure", choices=sorted(MEASURE_FAMILIES),
                    default="deepfm",
                    help="measure family for --graph begin "
                         "(registry-resolved; built with the same "
                         "PRNGKey(0) as serve.py, so the served measure "
                         "matches the index)")
    ap.add_argument("--train-queries", type=int, default=256,
                    help="--graph begin: sampled training queries (the "
                         "offline f-evaluation budget is T x N)")
    ap.add_argument("--corpus-dtype",
                    choices=["float32", "bfloat16", "int8"],
                    default="float32",
                    help="stored corpus residency: bf16 halves / int8 "
                         "quarters the vector payload (per-row scales); "
                         "serve.py loads it straight into the index-fused "
                         "search path")
    ap.add_argument("--page-rows", type=int, default=4096,
                    help="rows per page in the saved (v3) payload layout — "
                         "the page granularity paged residency faults at "
                         "(recorded in meta; load_corpus_store defaults "
                         "to it)")
    ap.add_argument("--residency", choices=["whole", "paged"],
                    default="whole",
                    help="post-build verification residency: 'paged' "
                         "reloads the saved index through the paged store "
                         "and checks a sample gather against the whole-"
                         "resident payload (the layout on disk is the "
                         "same either way — residency is a LOAD-time "
                         "policy)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, required=True,
                    help="output index directory")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.base:
        base = np.load(args.base).astype(np.float32)
    else:
        rng = np.random.default_rng(args.seed)
        base = rng.normal(size=(args.items, args.dim)).astype(np.float32)

    t0 = time.perf_counter()
    if args.shards > 0:
        if args.graph == "begin":
            raise SystemExit("--graph begin is single-partition only "
                             "(partition-local entries would not survive "
                             "the measure-aware two-hop construction)")
        index = build_sharded_index(base, n_shards=args.shards, m=args.m,
                                    k_construction=args.k_construction,
                                    seed=args.seed, impl=args.impl)
        desc = (f"{args.shards} shards x {index.base.shape[1]} rows, "
                f"max degree {index.neighbors.shape[2]}")
    elif args.graph == "begin":
        import jax

        measure = make_family_measure(args.measure, jax.random.PRNGKey(0),
                                      base.shape[1])
        rng = np.random.default_rng(args.seed + 1)
        train_q = rng.normal(size=(args.train_queries,
                                   base.shape[1])).astype(np.float32)
        index = build_begin_graph(measure, base, train_q, m=args.m,
                                  seed=args.seed)
        desc = (f"{index.n} nodes (BEGIN/{args.measure}, "
                f"T={args.train_queries}), avg degree "
                f"{index.avg_degree:.1f}")
    else:
        index = build_l2_graph(base, m=args.m,
                               k_construction=args.k_construction,
                               seed=args.seed, impl=args.impl)
        desc = f"{index.n} nodes, avg degree {index.avg_degree:.1f}"
    dt = time.perf_counter() - t0
    # record construction provenance: serve.py warns when a measure-aware
    # (BEGIN) index is served under a different measure family
    extra = {"graph_kind": args.graph}
    if args.graph == "begin":
        extra["measure_family"] = args.measure
    meta_path = save_index(args.out, index, corpus_dtype=args.corpus_dtype,
                           extra_meta=extra, page_rows=args.page_rows)
    print(f"[build_index] {base.shape[0]} items dim={base.shape[1]}: {desc}, "
          f"built in {dt:.1f}s -> {args.out} "
          f"(corpus_dtype={args.corpus_dtype}, page_rows={args.page_rows})")
    if args.residency == "paged" and args.shards == 0:
        import jax.numpy as jnp

        from repro.core.corpus import ResidencyPolicy
        from repro.graph import load_corpus_store
        paged = load_corpus_store(args.out,
                                  residency=ResidencyPolicy("paged"))
        whole = load_corpus_store(args.out)
        probe = jnp.arange(min(256, index.n if args.shards == 0 else 1))
        if not np.array_equal(np.asarray(paged.take(probe)),
                              np.asarray(whole.take(probe))):
            raise SystemExit("[build_index] paged-residency verification "
                             "FAILED: paged gather != whole gather")
        st = paged.stats_snapshot()
        print(f"[build_index] paged verification ok: page_rows="
              f"{paged.cache.page_rows}, faults={st.faults}, "
              f"resident_bytes={st.resident_bytes}")
    return meta_path


if __name__ == "__main__":
    main()
