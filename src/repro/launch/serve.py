"""Serving launcher: stand up a GUITAR ranking service (measure + index) and
run queries against it. ``--measure`` selects the measure family
(registry-resolved kernel bundle — DeepFM by default so the demo exercises
the Pallas score+grad path; ``--list-measures`` prints the registry),
``--mode`` the pruning strategy, ``--searcher`` the execution path (staged
expansion engine vs the legacy lane-major searcher), ``--runtime`` the
serving discipline:

- ``oneshot``      closed-loop batch jobs: queries arrive in whole batches,
  each batch steps until every lane converges. Batches are bucket-padded to
  the ``serving/batching.py`` size ladder so jit executables are reused.
- ``continuous``   open-loop traffic (DESIGN.md §9): Poisson arrivals at
  ``--offered-qps`` feed an admission queue; the lane-recycling scheduler
  (``serving/runtime.py``) swaps queued queries into lanes as they free up,
  and per-request completions stream out with full SLA metrics
  (p50/p95/p99 latency, time-in-queue, lane occupancy, evals/query).

``--index`` serves a prebuilt index directory (``python -m
repro.launch.build_index``) instead of building in-process; ``--save-index``
persists an in-process build for reuse. ``--corpus-dtype`` / ``--fused``
select index-fused quantized residency (DESIGN.md §8).

    PYTHONPATH=src python -m repro.launch.serve --items 10000 --queries 128
    PYTHONPATH=src python -m repro.launch.serve --runtime continuous \
        --lanes 32 --offered-qps 200 --queries 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (MEASURE_FAMILIES, EngineOptions, SearchConfig,
                        brute_force_topk, build_engine, get_bundle,
                        list_families, make_corpus_store,
                        make_family_measure, mlp_measure, recall,  # noqa: F401  (re-export compat)
                        search_legacy, search_measure)
from repro.obs import (NULL_TRACER, Registry, Tracer, format_trace,
                       profile_trace)
from repro.graph import (GraphIndex, build_l2_graph, load_corpus_store,
                         load_index, load_index_meta, save_index)
from repro.serving import (BATCH_BUCKETS, ContinuousRuntime, Request,  # noqa: F401  (re-export compat)
                           bucket_pad, bucket_size, latency_summary,
                           load_policy, poisson_arrivals)
from repro.utils import enable_compile_cache


def serve_oneshot(args, graph, measure, cfg, options, corpus_arg, nbrs_j,
                  base_j, rng) -> None:
    """Closed-loop batch serving: whole bucket-padded batches, each stepped
    to full convergence (the pre-§9 path, still best for batch jobs)."""
    def run_batch(qj, entries):
        if args.searcher == "legacy":
            return search_legacy(measure.score_fn, measure.params, base_j,
                                 nbrs_j, qj, entries, cfg)
        return search_measure(measure, corpus_arg, nbrs_j, qj, entries, cfg,
                              options)

    lat_ms, evals, iters_all = [], [], []
    first_recall = None
    shapes_seen = set()
    cache_hits = 0
    n_batches = 0
    for s in range(0, args.queries, args.batch):
        n = min(args.batch, args.queries - s)   # ragged tail exercises
        q = rng.normal(size=(n, args.dim)).astype(np.float32)  # bucketing
        qj, entries, n = bucket_pad(q, graph.entry)
        n_batches += 1
        if qj.shape in shapes_seen:
            cache_hits += 1
        shapes_seen.add(qj.shape)
        t0 = time.perf_counter()
        res = run_batch(qj, entries)
        jax.block_until_ready(res.ids)
        dt = time.perf_counter() - t0
        lat_ms.append(dt * 1e3)
        evals.append(float(res.n_eval[:n].mean()))
        iters_all.extend(np.asarray(res.n_iters[:n]).tolist())
        if s == 0:
            nr = min(16, n)
            true_ids, _ = brute_force_topk(measure, base_j, qj[:nr], args.k)
            first_recall = recall(res.ids[:nr], true_ids)

    # batch 0 pays compilation; use the rest for steady-state numbers, but
    # guard the single-batch (--queries <= --batch) case: re-run the warm
    # batch so the report never divides by zero or quotes compile time.
    steady = lat_ms[1:]
    if not steady:
        q = rng.normal(size=(args.batch, args.dim)).astype(np.float32)
        qj, entries, _ = bucket_pad(q, graph.entry)
        t0 = time.perf_counter()
        res = run_batch(qj, entries)
        jax.block_until_ready(res.ids)
        steady = [(time.perf_counter() - t0) * 1e3]
        evals.append(float(res.n_eval.mean()))
    qps = args.batch * len(steady) / (sum(steady) / 1e3)
    lat = latency_summary(steady)
    iters = np.asarray(iters_all) if iters_all else np.asarray([0])
    if args.metrics_json:
        import json
        summ = {"runtime": "oneshot", "qps": qps, **lat,
                "evals_per_query": float(np.mean(evals)),
                "iters_mean": float(iters.mean()),
                "iters_max": float(iters.max()),
                "recall": (float(first_recall)
                           if first_recall is not None else None),
                "n_batches": n_batches}
        with open(args.metrics_json, "w") as f:
            json.dump(summ, f, indent=1, sort_keys=True)
        print(f"[serve] metrics json -> {args.metrics_json}")
    print(f"[serve] searcher={args.searcher} mode={args.mode} "
          f"measure={args.measure} "
          f"corpus_dtype={args.corpus_dtype} fused={options.fused} "
          f"recall@{args.k}={first_recall:.3f} steady-state {qps:.0f} QPS "
          f"(batch={args.batch})")
    print(f"[serve] latency/batch p50={lat['p50_ms']:.1f}ms "
          f"p95={lat['p95_ms']:.1f}ms "
          f"compile-cache hits={cache_hits}/{n_batches} "
          f"({len(shapes_seen)} bucket shapes) "
          f"effective-evals/query={np.mean(evals):.0f} "
          f"iters mean={iters.mean():.0f} max={iters.max()}")


def _parse_sla_mix(spec: str, policy) -> list:
    """'premium:0.2,standard:0.5,economy:0.3' -> tier-name list of 100
    slots (request i takes slot i % 100) — a deterministic traffic mix."""
    names = {c.name for c in policy.classes}
    slots = []
    for part in spec.split(","):
        name, _, frac = part.partition(":")
        name = name.strip()
        if name not in names:
            raise SystemExit(f"--sla-mix tier {name!r} not in policy "
                             f"(have {sorted(names)})")
        slots += [name] * max(1, round(float(frac or 1) * 100))
    return slots[:100] or [policy.classes[0].name]


def serve_continuous(args, graph, measure, cfg, options, corpus_arg, nbrs_j,
                     base_j, rng) -> None:
    """Open-loop continuous batching: Poisson arrivals at --offered-qps
    into the lane-recycling runtime; per-request SLA metrics out."""
    engine = build_engine(measure, cfg, options)
    sla_policy = None
    if args.sla != "off":
        sla_policy = load_policy(args.sla)
        print("[serve] SLA tiers (richest first; each tier overrides the "
              "request's iter_cap + angle_tau, corpus_dtype is advisory):")
        for line in sla_policy.table():
            print(f"[serve]   {line}")
        if options.adaptive == "off" \
                and any(c.angle_tau > 0 for c in sla_policy.classes):
            print("[serve] note: tiers carry angle_tau cutoffs but "
                  "--adaptive is off — taus are inert; pass "
                  "--adaptive angle to let tiers shrink |C|")
    fault_plan = None
    fault_hook = None
    if args.chaos:
        from repro.serving import FaultPlan
        fault_plan = FaultPlan.load(args.chaos)
        fault_hook = fault_plan.tick_hook("tick")
        print(f"[serve] chaos: replaying {args.chaos} "
              f"(seed={fault_plan.seed}, {len(fault_plan.events)} event(s))")
    tracer = (Tracer(sample=args.trace_sample)
              if args.trace_sample else NULL_TRACER)
    runtime = ContinuousRuntime(engine, measure.params, corpus_arg, nbrs_j,
                                n_lanes=args.lanes, query_dim=args.dim,
                                entry=graph.entry,
                                steps_per_tick=args.steps_per_tick,
                                max_queue=args.max_queue,
                                fault_hook=fault_hook, tracer=tracer,
                                sla_policy=sla_policy)
    if fault_plan is not None and getattr(runtime.store, "is_paged", False):
        # page-read faults only make sense against a pager
        runtime.store.set_read_hook(fault_plan.pager_hook("pager"))
    if tracer.enabled and getattr(runtime.store, "is_paged", False):
        runtime.store.set_tracer(tracer)
    queries = rng.normal(size=(args.queries, args.dim)).astype(np.float32)
    runtime.warmup(queries[0])  # compile reset + tick off the clock
    registry = None
    if args.metrics_out:
        registry = Registry()
        runtime.bind_registry(registry)     # after warmup: see docstring
        from repro.kernels import autotune
        autotune.bind_registry(registry)

    arrivals = poisson_arrivals(args.queries, args.offered_qps, seed=1)
    mix = (_parse_sla_mix(args.sla_mix, sla_policy)
           if sla_policy is not None and args.sla_mix else None)
    stream = [Request(rid=i, query=queries[i], t_arrive=float(arrivals[i]),
                      deadline=args.deadline,
                      sla=mix[i % len(mix)] if mix else None)
              for i in range(args.queries)]
    completions = runtime.run_stream(stream,
                                     health_every_s=args.health_every)

    def export_telemetry():
        import json
        if args.trace_out and tracer.enabled:
            n = tracer.export_jsonl(args.trace_out)
            print(f"[serve] traces -> {args.trace_out} ({n} spans, "
                  f"1/{args.trace_sample} sampling)")
            slow = max((c for c in completions
                        if tracer.sampled(c.rid) and c.status == "ok"),
                       key=lambda c: c.record.latency_ms, default=None)
            if slow is not None:
                print(f"[serve] slowest traced ok request:")
                print(format_trace(tracer, slow.rid,
                                   sites=("pager", runtime.site)))
        if registry is not None:
            with open(args.metrics_out, "w") as f:
                f.write(registry.render_text())
            print(f"[serve] metrics (prometheus text) -> "
                  f"{args.metrics_out}")
        if args.metrics_json:
            with open(args.metrics_json, "w") as f:
                json.dump(runtime.metrics.summary(), f, indent=1,
                          sort_keys=True)
            print(f"[serve] metrics json -> {args.metrics_json}")

    by_rid = {c.rid: c for c in completions}
    nr = min(16, args.queries)
    ok_rids = [i for i in range(nr) if by_rid[i].status == "ok"]
    if not ok_rids:
        # everything in the recall probe window was shed / failed / timed
        # out — report SLA metrics only instead of dividing by nothing.
        # Only a run that injected faults on purpose may end that way and
        # still succeed.
        print(f"[serve] runtime=continuous lanes={args.lanes} "
              f"offered={args.offered_qps:.0f} QPS — no ok completions in "
              f"the recall window (degraded run)")
        print(runtime.format_health())
        print(runtime.metrics.report())
        export_telemetry()
        if not args.chaos:
            raise SystemExit("[serve] no request in the recall window "
                             "completed ok, and no --chaos plan was given")
        return
    true_ids, _ = brute_force_topk(measure, base_j,
                                   jnp.asarray(queries[:nr]), args.k)
    got = jnp.asarray(np.stack([by_rid[i].ids for i in ok_rids]))
    r = recall(got, jnp.asarray(np.asarray(true_ids)[ok_rids]))
    print(f"[serve] runtime=continuous lanes={args.lanes} "
          f"steps_per_tick={args.steps_per_tick} "
          f"offered={args.offered_qps:.0f} QPS mode={args.mode} "
          f"measure={args.measure} "
          f"corpus_dtype={args.corpus_dtype} fused={options.fused} "
          f"recall@{args.k}={r:.3f}")
    print(runtime.format_health())
    print(runtime.metrics.report())
    export_telemetry()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=10000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--mode", choices=["guitar", "sl2g"], default="guitar")
    ap.add_argument("--measure", choices=sorted(MEASURE_FAMILIES),
                    default="deepfm",
                    help="measure family (registry-resolved kernel bundle); "
                         "deepfm default exercises the Pallas score+grad "
                         "path end to end")
    ap.add_argument("--list-measures", action="store_true",
                    help="print the measure-kernel bundle registry and exit")
    ap.add_argument("--searcher", choices=["engine", "legacy"],
                    default="engine")
    ap.add_argument("--runtime", choices=["oneshot", "continuous"],
                    default="oneshot",
                    help="batch-scoped vs lane-recycling serving (§9)")
    ap.add_argument("--lanes", type=int, default=32,
                    help="continuous runtime: engine lanes (slots)")
    ap.add_argument("--offered-qps", type=float, default=200.0,
                    help="continuous runtime: open-loop Poisson arrival rate")
    ap.add_argument("--steps-per-tick", type=int, default=8,
                    help="continuous runtime: engine steps per scheduler "
                         "round (latency quantum vs host overhead)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="continuous runtime: max seconds in queue before a "
                         "request is dropped as timed out")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="continuous runtime: bounded admission queue — "
                         "submits beyond this depth are load-shed "
                         "(status='shed') instead of queueing unboundedly "
                         "(DESIGN.md §12); with --sla, this depth DEGRADES "
                         "(floor-tier admission) and 2x this depth sheds")
    ap.add_argument("--sla", type=str, default="off",
                    metavar="off|default|POLICY.json",
                    help="continuous runtime: SLA-tiered serving "
                         "(DESIGN.md §14). Each tier overrides, per "
                         "request: iter_cap (the per-lane expansion budget "
                         "that --budget/engine cfg otherwise fixes) and "
                         "angle_tau (the adaptive cutoff — only active "
                         "under --adaptive angle, inert otherwise); a "
                         "tier's corpus_dtype is ADVISORY (residency is "
                         "fixed at startup by --corpus-dtype; a conflict "
                         "warns, never fails). Requests classify by "
                         "deadline (or --sla-mix); under queue pressure "
                         "tiers degrade before anything is shed. 'default' "
                         "= the stock premium/standard/economy ladder; a "
                         "JSON path loads a custom ladder (serving/sla.py)")
    ap.add_argument("--sla-mix", type=str, default=None,
                    metavar="TIER:FRAC,...",
                    help="with --sla: pin requests to explicit tiers in "
                         "this proportion (e.g. 'premium:0.2,standard:0.5,"
                         "economy:0.3') instead of deadline classification")
    ap.add_argument("--adaptive", choices=["off", "angle"], default="off",
                    help="angle-based adaptive candidate-set sizing "
                         "(paper's adaptive |C|): the rank stage keeps the "
                         "alpha*theta band + per-lane tau cutoff as a mask "
                         "over a static c-max block — fewer neural evals "
                         "where the angle spectrum says they buy nothing. "
                         "'off' is bit-identical to the non-adaptive engine")
    ap.add_argument("--c-max", type=int, default=0,
                    help="adaptive: static candidate block width (0 = "
                         "--budget); the per-lane mask selects a prefix")
    ap.add_argument("--angle-tau", type=float, default=0.0,
                    help="adaptive: absolute angle cutoff in radians "
                         "(<=0 disables; SLA tiers override per request)")
    ap.add_argument("--chaos", type=str, default=None, metavar="PLAN.json",
                    help="continuous runtime: replay a FaultPlan "
                         "(serving/faults.py) — tick faults at site 'tick', "
                         "page-read faults at site 'pager' when serving "
                         "paged residency")
    ap.add_argument("--health-every", type=float, default=None,
                    metavar="SECONDS",
                    help="continuous runtime: print a [health] line at this "
                         "period while the stream drains")
    ap.add_argument("--trace-sample", type=int, default=0, metavar="N",
                    help="continuous runtime: trace every Nth request "
                         "(rid %% N == 0) into per-request span trees "
                         "(obs/trace.py, DESIGN.md §13); 0 = tracing off")
    ap.add_argument("--trace-out", type=str, default=None,
                    metavar="TRACES.jsonl",
                    help="export the trace ring buffer as JSONL after the "
                         "stream drains (requires --trace-sample)")
    ap.add_argument("--metrics-out", type=str, default=None,
                    metavar="METRICS.prom",
                    help="continuous runtime: write the obs.Registry in "
                         "Prometheus text exposition format at exit")
    ap.add_argument("--metrics-json", type=str, default=None, metavar="PATH",
                    help="dump the final metrics summary() dict as JSON "
                         "(machine-readable twin of the [serve] report)")
    ap.add_argument("--profile-dir", type=str, default=None,
                    help="capture a jax profiler trace of the whole serve "
                         "run into this directory (TensorBoard/Perfetto), "
                         "with its clock anchor (repro_clock.json) that "
                         "maps --trace-out spans onto the trace's clock")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ef", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=1.01)
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--corpus-dtype",
                    choices=["float32", "bfloat16", "int8"],
                    default="float32",
                    help="corpus residency; non-fp32 implies the "
                         "index-fused search path")
    ap.add_argument("--fused", action="store_true",
                    help="index-fused rank/score stages at fp32 residency")
    ap.add_argument("--tile", type=str, default=None,
                    help="fused-path tiling override "
                         "('tile'|'rowwise'[:<bt>] — kernels/autotune.py "
                         "spec); default resolves the tuning cache / "
                         "shipped defaults per shape")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep the fused-step plan at this serving shape "
                         "before accepting traffic and persist the winner "
                         "to the tuning cache (skipped on a cache hit — "
                         "the second serve never pays the sweep)")
    ap.add_argument("--index", type=str, default=None,
                    help="serve a prebuilt index directory (graph/io.py)")
    ap.add_argument("--save-index", type=str, default=None,
                    help="persist the built index to this directory")
    ap.add_argument("--residency", choices=["whole", "paged"],
                    default="whole",
                    help="corpus residency policy: 'paged' serves --index "
                         "payloads straight off their mmap'd page files "
                         "through an LRU page cache (bounded resident "
                         "bytes) instead of loading the corpus whole")
    ap.add_argument("--page-rows", type=int, default=4096,
                    help="paged residency: rows per page (the index meta's "
                         "saved page_rows wins when this is left at the "
                         "default)")
    ap.add_argument("--cache-mb", type=int, default=64,
                    help="paged residency: LRU page-cache byte budget (MiB)")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()

    if args.list_measures:
        print("measure-kernel bundle registry "
              "(family: registered stage factories)")
        for fam in list_families():
            slots = get_bundle(fam).slots()
            have = [s for s, ok in slots.items() if ok]
            servable = " (serve constructor)" if fam in MEASURE_FAMILIES \
                else ""
            print(f"  {fam}: {', '.join(have)}{servable}")
        print("unregistered families fall back to the generic "
              "vmap/jax.grad stages")
        print("adaptive |C| (--adaptive angle) masks the score_fused "
              "stage: families with a fused scorer skip fully-masked "
              "tiles in-kernel; generic fallbacks mask densely")
        return

    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}; compile cache {cache_dir}")
    fused = args.fused or args.corpus_dtype != "float32"
    if args.searcher == "legacy" and fused:
        raise SystemExit("--searcher legacy has no index-fused/quantized "
                         "path; use the engine searcher")
    if args.runtime == "continuous" and args.searcher == "legacy":
        raise SystemExit("--runtime continuous is engine-only (lane "
                         "recycling needs the per-lane reset API)")

    rng = np.random.default_rng(0)
    store = None
    paged_policy = None
    if args.residency == "paged":
        from repro.core.corpus import ResidencyPolicy
        paged_policy = ResidencyPolicy("paged", args.page_rows,
                                       args.cache_mb << 20)
    if args.index:
        graph = load_index(args.index)
        if not isinstance(graph, GraphIndex):
            raise SystemExit(f"--index {args.index} is not a single-partition "
                             "graph index (serve a ShardedIndex via "
                             "core.sharded / launch.dryrun)")
        base = graph.base
        args.items, args.dim = base.shape
        index_meta = load_index_meta(args.index)
        saved_dtype = index_meta.get("corpus_dtype", "float32")
        if saved_dtype != args.corpus_dtype:
            # mirror the measure-mismatch warning below: never silently
            # serve a different residency than the operator asked for
            print(f"[serve] WARNING: index at {args.index} stores the "
                  f"corpus as {saved_dtype!r} but --corpus-dtype="
                  f"{args.corpus_dtype!r} was requested — re-quantizing "
                  f"the loaded payload to {args.corpus_dtype!r} "
                  f"({saved_dtype!r} round-trip error carries over; "
                  f"rebuild with --corpus-dtype {args.corpus_dtype} to "
                  f"serve exactly what was quantized at build time)")
            if paged_policy is not None:
                raise SystemExit(
                    "[serve] --residency paged cannot re-quantize (paging "
                    "serves the on-disk payload as-is); rebuild the index "
                    f"with --corpus-dtype {args.corpus_dtype} or serve "
                    f"--corpus-dtype {saved_dtype}")
        if paged_policy is not None:
            store = load_corpus_store(args.index, residency=paged_policy)
        elif fused and saved_dtype == args.corpus_dtype:
            # reuse the stored payload when it matches the requested
            # residency — no fp32 round-trip, no requantization
            store = load_corpus_store(args.index)
        print(f"[serve] index: loaded {args.index} ({graph.n} items, "
              f"degree {graph.avg_degree:.1f}, residency={args.residency})")
        # carried through --save-index below so provenance survives copies
        provenance = {k: index_meta[k]
                      for k in ("graph_kind", "measure_family")
                      if k in index_meta}
        built_under = index_meta.get("measure_family")
        if built_under is not None and built_under != args.measure:
            print(f"[serve] WARNING: index was built measure-aware under "
                  f"the {built_under!r} family but --measure="
                  f"{args.measure!r} is being served — the query-aware "
                  f"adjacency no longer matches the measure; recall will "
                  f"degrade (rebuild with --measure {args.measure} or "
                  f"serve --measure {built_under})")
    else:
        base = rng.normal(size=(args.items, args.dim)).astype(np.float32)
        t0 = time.time()
        graph = build_l2_graph(base, m=16, k_construction=48)
        provenance = {"graph_kind": "l2"}
        print(f"[serve] index: {args.items} items, "
              f"degree {graph.avg_degree:.1f}, "
              f"built in {time.time() - t0:.1f}s")
    if args.save_index:
        save_index(args.save_index, graph, corpus_dtype=args.corpus_dtype,
                   extra_meta=provenance)
        print(f"[serve] index saved -> {args.save_index} "
              f"(corpus_dtype={args.corpus_dtype})")
    # deterministic in the key: build_index constructs the SAME measure for
    # measure-aware (BEGIN) graph construction
    measure = make_family_measure(args.measure, jax.random.PRNGKey(0),
                                  args.dim)

    cfg = SearchConfig(k=args.k, ef=args.ef, mode=args.mode,
                       budget=args.budget, alpha=args.alpha)
    options = EngineOptions(fused=fused, corpus_dtype=args.corpus_dtype,
                            tile=args.tile, adaptive=args.adaptive,
                            c_max=args.c_max, angle_tau=args.angle_tau)
    if args.sla != "off":
        import sys
        if args.runtime != "continuous":
            raise SystemExit("--sla needs --runtime continuous (tiers are "
                             "admission policy on the lane scheduler)")
        policy = load_policy(args.sla)
        explicit_dtype = any(a.startswith("--corpus-dtype")
                             for a in sys.argv[1:])
        conflicting = [c for c in policy.classes
                       if c.corpus_dtype != args.corpus_dtype]
        if explicit_dtype and conflicting:
            # warn, never fail: residency is a store-level property fixed
            # here at startup — a tier's corpus_dtype is the fleet
            # recommendation, not a per-request switch
            names = ", ".join(f"{c.name}({c.corpus_dtype})"
                              for c in conflicting)
            print(f"[serve] WARNING: --corpus-dtype={args.corpus_dtype} "
                  f"conflicts with the residency recommended by tier(s) "
                  f"{names}; every tier serves {args.corpus_dtype} — "
                  f"tiers still apply their iter_cap/angle_tau knobs")

    base_j = jnp.asarray(base)
    nbrs_j = jnp.asarray(graph.neighbors)
    if store is None and paged_policy is not None:
        # synthetic corpus under a paged policy: quantize host-side and
        # page from host memory (file-backed pages need --index)
        store = make_corpus_store(base, args.corpus_dtype,
                                  residency=paged_policy)
    if store is None and fused:
        # quantize once, up front — every batch then searches the resident
        # (possibly bf16/int8) payload, in the engine's layout, without
        # per-call conversion
        store = make_corpus_store(base_j, args.corpus_dtype)
    if store is not None and not getattr(store, "is_paged", False):
        store = build_engine(measure, cfg, options).prepare_store(store)
    corpus_arg = store if store is not None else base_j
    if store is not None and getattr(store, "is_paged", False):
        print(f"[serve] corpus paged: dtype={store.dtype} page_rows="
              f"{store.cache.page_rows} cache_budget={args.cache_mb} MiB "
              f"(resident bytes bounded; LRU page faults on demand)")
    elif fused:
        mib = store.nbytes() / 2**20
        print(f"[serve] corpus resident: dtype={store.dtype} {mib:.1f} MiB "
              f"(fused gather-rank-score path)")

    if args.autotune and store is not None \
            and getattr(store, "is_paged", False):
        print("[serve] autotune: skipped (paged residency always runs the "
              "tile plan — one combined pager gather per step)")
    elif args.autotune and fused:
        # sweep the fused-step plan at the exact serving shape before any
        # traffic; a prior run at this shape is a cache hit (no sweep)
        from repro.kernels import autotune
        lanes = args.lanes if args.runtime == "continuous" else args.batch
        # own generator: the sweep must not advance the serving rng stream
        # (query workload — and recall — would change under --autotune)
        tune_rng = np.random.default_rng(12345)
        tune_q = jnp.asarray(tune_rng.normal(
            size=(lanes, args.dim)).astype(np.float32))
        tune_e = jnp.full((lanes,), graph.entry, jnp.int32)
        t0 = time.time()
        tuned = autotune.tune_engine_step(measure, corpus_arg, nbrs_j,
                                          tune_q, tune_e, cfg, options)
        print(f"[serve] autotune: engine_step plan={tuned.plan} "
              f"(Q={lanes}, B={nbrs_j.shape[1]}, D={args.dim}, "
              f"{args.corpus_dtype}) in {time.time() - t0:.1f}s "
              f"-> {autotune.cache_path()}")
    elif args.autotune:
        print("[serve] autotune: nothing to tune (the tile plan applies "
              "to the fused path; pass --fused or a non-fp32 "
              "--corpus-dtype)")

    with profile_trace(args.profile_dir):
        if args.runtime == "continuous":
            serve_continuous(args, graph, measure, cfg, options, corpus_arg,
                             nbrs_j, base_j, rng)
        else:
            serve_oneshot(args, graph, measure, cfg, options, corpus_arg,
                          nbrs_j, base_j, rng)
    if args.profile_dir:
        print(f"[serve] profiler trace -> {args.profile_dir}")


if __name__ == "__main__":
    main()
