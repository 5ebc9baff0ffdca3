"""Unified telemetry subsystem (DESIGN.md §13): per-request span tracing,
the metric registry + exposition, and the adapters that bind serving,
pager, mutation, and autotune state into them.

House invariant, extended to observability: tracing is read-only —
search results with tracing enabled are BIT-IDENTICAL to tracing off
(single-runtime, sharded, and paged-store continuous serving), and a
disabled tracer costs one attribute lookup on the hot path.

The acceptance bar from the issue: a traced degraded run (one shard
crashing + pager I/O errors) must produce a span tree whose union of
phase intervals attributes >=95% of each traced request's wall-clock.
"""
import json
import sys
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core import (EngineOptions, SearchConfig, build_engine,
                        mlp_measure)
from repro.core.corpus import ResidencyPolicy, make_corpus_store
from repro.core.sharded import build_sharded_index
from repro.graph import DurableIndex, build_l2_graph
from repro.kernels import autotune
from repro.obs import (NULL_TRACER, NullTracer, Registry, Tracer,
                       attribution, format_trace)
from repro.serving import (ContinuousRuntime, FaultEvent, FaultPlan,
                           ServingMetrics, ShardedContinuousRuntime)
from repro.serving.metrics import RequestRecord


@pytest.fixture(scope="module")
def system():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(600, 16)).astype(np.float32)
    queries = rng.normal(size=(24, 16)).astype(np.float32)
    graph = build_l2_graph(base, m=8, k_construction=24)
    measure = mlp_measure(jax.random.PRNGKey(1), 16, 16, hidden=(32,))
    cfg = SearchConfig(k=5, ef=24, mode="guitar", budget=6, alpha=1.1)
    engine = build_engine(measure, cfg,
                          EngineOptions(rank_impl="ref", measure_impl="vmap"))
    sharded = build_sharded_index(base, n_shards=2, m=8, k_construction=24)
    return dict(base=base, queries=queries, graph=graph, measure=measure,
                cfg=cfg, engine=engine, sharded=sharded)


def _run_single(s, tracer=NULL_TRACER, corpus=None, n=12):
    rt = ContinuousRuntime(s["engine"], s["measure"].params,
                           s["base"] if corpus is None else corpus,
                           s["graph"].neighbors, n_lanes=4, query_dim=16,
                           entry=s["graph"].entry, steps_per_tick=2,
                           tracer=tracer)
    for i in range(n):
        rt.submit(s["queries"][i], rid=i)
    while rt.queue or rt.in_flight:
        rt.step_once()
    return {c.rid: c for c in rt.pop_completions()}, rt


def _drive_sharded(rt, queries, per_round=2):
    i, out = 0, {}
    while i < len(queries) or rt.in_flight or rt.queued or rt._partial \
            or any(r.completions for r in rt.runtimes):
        for _ in range(per_round):
            if i < len(queries):
                rt.submit(queries[i], rid=i)
                i += 1
        for c in rt.step_once():
            out[c.rid] = c
    return out


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------

def test_ring_buffer_wraparound():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.emit(f"s{i}", 0.0, 1.0)
    spans = tr.spans()
    assert len(spans) == 4                      # bounded
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]  # oldest out
    assert tr.n_emitted == 10                   # lifetime counter survives


def test_drain_force_closes_open_spans():
    tr = Tracer()
    sid = tr.begin("tick", rid=3)
    tr.root_for(3, t0=0.0)
    done = tr.end(tr.begin("admit", rid=3))
    assert not done.open
    drained = tr.drain()
    assert {s.name for s in drained} == {"tick", "request"}
    assert all(s.open for s in drained)         # flagged, not silently lost
    assert tr.end(sid) is None                  # already force-closed
    # roots cleared: a new root_for starts a fresh request span
    assert tr.root_for(3) != drained[0].span_id
    tr.drain()


def test_sampling_is_pure_function_of_rid():
    tr = Tracer(sample=4)
    assert tr.sampled(0) and tr.sampled(8)
    assert not tr.sampled(1) and not tr.sampled(6)
    assert not tr.sampled(-1)                   # warmup sentinel
    assert not tr.sampled(None)
    with pytest.raises(ValueError):
        Tracer(sample=0)
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    assert NULL_TRACER.begin("x") == -1
    assert NULL_TRACER.emit("x", 0, 1) == -1
    assert NULL_TRACER.sampled(0) is False
    assert NULL_TRACER.drain() == [] and NULL_TRACER.spans() == []


def test_export_jsonl_round_trip(tmp_path):
    tr = Tracer()
    tr.emit("tick", 0.0, 0.002, rid=0, site="shard:1", i=3)
    tr.emit("page_fault", 0.001, 0.0015, site="pager", pid=7)
    path = str(tmp_path / "traces.jsonl")
    assert tr.export_jsonl(path) == 2
    recs = [json.loads(line) for line in open(path)]
    assert [r["name"] for r in recs] == ["tick", "page_fault"]
    assert recs[0]["rid"] == 0 and recs[0]["attrs"] == {"i": 3}
    assert recs[1]["site"] == "pager"


def test_attribution_and_format_trace_edge_cases():
    att = attribution([], rid=0)
    assert att == {"wall_ms": 0.0, "attributed_ms": 0.0, "coverage": 0.0,
                   "by_name": {}}
    assert format_trace([], rid=3) == "(no trace for rid=3)"
    # overlapping leaves count once in coverage, per-name sums stay raw
    tr = Tracer()
    tr.root_for(0, t0=0.0)
    tr.emit("tick", 0.0, 0.6, rid=0)
    tr.emit("tick", 0.4, 1.0, rid=0)
    tr.finish_request(0, t1=1.0)
    att = attribution(tr.spans(), 0)
    assert att["coverage"] == pytest.approx(1.0)
    assert att["by_name"]["tick"] == pytest.approx(1200.0)  # 0.6s + 0.6s
    txt = format_trace(tr, 0)
    assert txt.startswith("request rid=0") and "tick" in txt


# ---------------------------------------------------------------------------
# registry mechanics + exposition
# ---------------------------------------------------------------------------

def test_registry_label_cardinality_cap():
    reg = Registry(max_series_per_metric=2)
    c = reg.counter("repro_test_total", labelnames=("status",))
    c.labels(status="ok").inc()
    c.labels(status="shed").inc()
    with pytest.raises(ValueError, match="cardinality"):
        c.labels(status="a-third-value")
    c.labels(status="ok").inc()                 # existing series still fine
    with pytest.raises(ValueError):             # undeclared label name
        c.labels(shard="0")


def test_registry_name_and_kind_validation():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.counter("9starts_with_digit")
    with pytest.raises(ValueError):
        reg.counter("repro_x_total", labelnames=("bad-label",))
    reg.counter("repro_dup")
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("repro_dup")
    with pytest.raises(ValueError):
        reg.counter("repro_neg").inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("repro_g").observe(1.0)


def test_histogram_exposition_is_cumulative_and_monotone():
    reg = Registry()
    h = reg.histogram("repro_lat_ms", "t", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0, 5000.0):
        h.observe(v)
    text = reg.render_text()
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("repro_lat_ms_bucket")]
    assert counts == [2, 3, 4, 5]               # cumulative, +Inf == count
    assert counts == sorted(counts)
    assert "repro_lat_ms_count 5" in text
    j = reg.render_json()
    assert j["repro_lat_ms"]["series"][0]["count"] == 5


def test_registry_collect_callbacks_feed_gauges():
    reg = Registry()
    g = reg.gauge("repro_depth")
    state = {"depth": 0}
    reg.register_collect(lambda: g.set(state["depth"]))
    state["depth"] = 7
    assert "repro_depth 7" in reg.render_text()
    state["depth"] = 3
    assert json.loads(reg.render_json_str())[
        "repro_depth"]["series"][0]["value"] == 3.0


# ---------------------------------------------------------------------------
# satellites: ServingMetrics surface + adapters
# ---------------------------------------------------------------------------

def test_summary_surfaces_queue_depth_last():
    m = ServingMetrics(4)
    m.observe_queue_depth(5)
    m.observe_queue_depth(2)
    s = m.summary()
    assert s["queue_depth_last"] == 2.0 and s["queue_depth_max"] == 5.0


def test_report_is_clean_with_zero_completions():
    m = ServingMetrics(4)
    m.observe(RequestRecord(0, 0.0, 0.0, 0.0, shed=True))
    m.observe(RequestRecord(1, 0.0, 0.0, 0.1, timed_out=True))
    m.observe_queue_depth(3)
    line = m.report()
    assert "nan" not in line.lower()
    assert "completed=0" in line and "shed=1" in line
    assert "timed_out=1" in line


def test_serving_metrics_bind_registry():
    m = ServingMetrics(2)
    reg = m.bind_registry(Registry())
    m.observe(RequestRecord(0, 0.0, 0.001, 0.004, n_eval=30, n_iters=6))
    m.observe(RequestRecord(1, 0.0, 0.0, 0.0, shed=True))
    m.observe_queue_depth(4)
    m.observe_occupancy(busy=1, n_lanes=2)
    text = reg.render_text()
    assert 'repro_serving_requests_total{status="ok"} 1' in text
    assert 'repro_serving_requests_total{status="shed"} 1' in text
    assert "repro_serving_latency_ms_count 1" in text
    assert "repro_engine_evals_total 30" in text
    assert "repro_serving_queue_depth 4" in text
    assert "repro_serving_occupancy 0.5" in text
    # snapshot API unaffected by the registry view
    assert m.summary()["n_completed"] == 1.0


def test_autotune_bind_registry():
    reg = Registry()
    autotune.bind_registry(reg)
    before = dict(autotune.CACHE_STATS)
    autotune.CACHE_STATS["lookup_hits"] = before["lookup_hits"] + 2
    try:
        text = reg.render_text()
        want = autotune.CACHE_STATS["lookup_hits"]
        assert f"repro_autotune_lookup_hits_total {want}" in text
    finally:
        autotune.CACHE_STATS.update(before)


# ---------------------------------------------------------------------------
# pager + mutation span emission
# ---------------------------------------------------------------------------

def _paged(base, **policy_kw):
    policy = ResidencyPolicy("paged", page_rows=64, cache_bytes=1 << 20,
                             retry_backoff_s=0.0, **policy_kw)
    return make_corpus_store(base, "float32", residency=policy)


def test_pager_emits_fault_and_retry_spans(system):
    store = _paged(system["base"])
    tr = Tracer()
    store.set_tracer(tr)
    plan = FaultPlan([FaultEvent("page_io_error", site="pager", start=1,
                                 count=2)])
    store.set_read_hook(plan.pager_hook())
    store.take(np.array([[0, 70, 130], [599, 3, 64]]))
    faults = tr.spans(rid=None, site="pager")
    assert any(s.name == "page_fault" and not s.attrs.get("failed")
               for s in faults)
    # retries absorbed the injected errors; the span still records them
    assert sum(s.attrs.get("io_errors", 0) for s in faults) == 2
    assert not any(s.attrs.get("failed") for s in faults)


def test_pager_fallback_emits_span(system):
    store = _paged(system["base"])
    tr = Tracer()
    store.set_tracer(tr)
    plan = FaultPlan([FaultEvent("page_io_error", site="pager", start=0,
                                 count=10 ** 6)])
    store.set_read_hook(plan.pager_hook())
    store.take(np.arange(0, 600, 7))
    fb = [s for s in tr.spans(site="pager") if s.name == "fallback"]
    assert len(fb) == 1 and fb[0].attrs["rows"] == 600
    # the exhausted page fault before the fallback is flagged failed
    assert any(s.name == "page_fault" and s.attrs.get("failed")
               for s in tr.spans(site="pager"))


def test_pager_bind_registry(system):
    store = _paged(system["base"])
    reg = Registry()
    store.bind_registry(reg, shard="3")
    store.take(np.arange(0, 600, 11))
    text = reg.render_text()
    st = store.stats_snapshot()
    assert f'repro_pager_faults_total{{shard="3"}} {st.faults}' in text
    assert f'repro_pager_resident_bytes{{shard="3"}}' in text


def test_durable_index_emits_commit_spans(tmp_path):
    rng = np.random.default_rng(5)
    base = rng.normal(size=(80, 8)).astype(np.float32)
    graph = build_l2_graph(base, m=4, k_construction=12)
    d = DurableIndex.create(str(tmp_path), graph)
    tr = Tracer()
    d.tracer = tr
    d.insert(rng.normal(size=(4, 8)).astype(np.float32), k_candidates=16)
    d.delete([3, 17])
    d.checkpoint()
    spans = tr.spans(rid=None, site="mutate")
    names = Counter(s.name for s in spans)
    assert names["commit"] == 2 and names["journal"] == 2
    assert names["checkpoint"] == 1
    ops = {s.attrs.get("op") for s in spans if s.name == "commit"}
    assert ops == {"insert", "delete"}
    for s in spans:                             # journal nests under commit
        if s.name == "journal":
            assert s.t1 <= max(x.t1 for x in spans if x.name == "commit")


# ---------------------------------------------------------------------------
# runtime integration: bit-identity, sampling, coverage
# ---------------------------------------------------------------------------

def test_single_runtime_bit_identical_traced_vs_untraced(system):
    ref, _ = _run_single(system)
    tr = Tracer(sample=1)
    got, _ = _run_single(system, tracer=tr)
    assert set(got) == set(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid].ids, ref[rid].ids)
        np.testing.assert_array_equal(got[rid].scores, ref[rid].scores)
        assert got[rid].status == ref[rid].status
    # every request produced a closed root + queue span; the phases are
    # the runtime's round spans
    for rid in ref:
        names = {s.name for s in tr.spans(rid=rid)}
        assert {"request", "queue"} <= names
    rounds = {s.name for s in tr.spans(rid=None, site="runtime")}
    assert rounds == {"round", "admit", "dispatch", "fetch", "resolve"}


def test_paged_continuous_bit_identical_traced(system):
    ref, _ = _run_single(system)
    tr = Tracer(sample=1)
    store = _paged(system["base"])
    store.set_tracer(tr)
    got, _ = _run_single(system, tracer=tr, corpus=store)
    for rid in ref:
        np.testing.assert_array_equal(got[rid].ids, ref[rid].ids)
        np.testing.assert_array_equal(got[rid].scores, ref[rid].scores)
    assert any(s.name == "page_fault" for s in tr.spans(site="pager"))


def test_sharded_bit_identical_traced_vs_untraced(system):
    s = system
    qs = s["queries"]

    def make(tracer):
        return ShardedContinuousRuntime(
            s["engine"], s["measure"].params, s["sharded"], n_lanes=4,
            query_dim=16, steps_per_tick=2, tracer=tracer)

    ref = _drive_sharded(make(NULL_TRACER), qs)
    tr = Tracer(sample=1)
    got = _drive_sharded(make(tr), qs)
    assert set(got) == set(ref)
    for rid in ref:
        np.testing.assert_array_equal(got[rid].ids, ref[rid].ids)
        np.testing.assert_array_equal(got[rid].scores, ref[rid].scores)
    # fan-out spans carry the shard site; the merge layer owns the root
    sites = {sp.site for sp in tr.spans(rid=0)}
    assert {"shard:0", "shard:1"} <= sites
    assert any(sp.name == "merge" for sp in tr.spans(rid=0))


def test_sampled_out_requests_emit_zero_spans(system):
    tr = Tracer(sample=2)
    _run_single(system, tracer=tr)
    for rid in range(12):
        spans = tr.spans(rid=rid)
        if rid % 2 == 0:
            assert spans, f"rid {rid} sampled but traceless"
        else:
            assert spans == [], f"rid {rid} sampled out but has spans"


def test_healthy_run_attribution_covers_wall_clock(system):
    tr = Tracer(sample=1)
    _run_single(system, tracer=tr)
    att = attribution(tr.spans(), 0, sites=("runtime",))
    assert att["wall_ms"] > 0
    assert att["coverage"] >= 0.95
    assert {"queue", "round", "dispatch", "fetch"} <= set(att["by_name"])


def test_runtime_bind_registry_exposes_serving_series(system):
    tr = Tracer(sample=1)
    rt = ContinuousRuntime(system["engine"], system["measure"].params,
                           system["base"], system["graph"].neighbors,
                           n_lanes=4, query_dim=16,
                           entry=system["graph"].entry, steps_per_tick=2,
                           tracer=tr)
    reg = Registry()
    rt.bind_registry(reg)
    for i in range(8):
        rt.submit(system["queries"][i], rid=i)
    while rt.queue or rt.in_flight:
        rt.step_once()
    text = reg.render_text()
    assert 'repro_serving_requests_total{status="ok"} 8' in text
    assert "repro_serving_latency_ms_count 8" in text
    rt.close()                                  # drains open spans
    assert all(not sp.open or sp.name == "request"
               for sp in tr.spans())


# ---------------------------------------------------------------------------
# the acceptance bar: traced degraded run attributes the wall-clock
# ---------------------------------------------------------------------------

SHARD_SITES = ("pager", "shard:0", "shard:1")


def test_degraded_run_trace_attributes_latency(system):
    """Chaos plan (one shard's ticks crash until its breaker opens) plus
    transient pager I/O errors on the other shard's paged store: the
    traced span tree must still attribute >=95% of every traced answered
    request's end-to-end latency across queue/phase/merge (+ pager)
    spans — the issue's acceptance criterion."""
    s = system
    qs = np.random.default_rng(3).normal(size=(32, 16)).astype(np.float32)
    plan = FaultPlan([FaultEvent("shard_crash", site="shard:1/tick",
                                 start=3, count=3)], seed=0)
    tr = Tracer(sample=2, capacity=8192)
    rt = ShardedContinuousRuntime(
        s["engine"], s["measure"].params, s["sharded"], n_lanes=4,
        query_dim=16, steps_per_tick=2, k_failures=2, cooldown_rounds=3,
        fault_plan=plan, tracer=tr)
    # shard 0 serves from a paged store with a lossy (but transient,
    # retry-absorbed) read path, so pager spans weave into the traces
    paged = _paged(np.asarray(s["sharded"].base[0]))
    paged.set_tracer(tr)
    pager_plan = FaultPlan([FaultEvent("page_io_error", site="pager",
                                       start=0, count=60, rate=0.4)], seed=1)
    paged.set_read_hook(pager_plan.pager_hook())
    rt.runtimes[0].store = paged

    got = _drive_sharded(rt, qs)
    assert set(got) == set(range(32))           # every rid resolved
    statuses = Counter(c.status for c in got.values())
    assert statuses["partial"] > 0              # the crash really degraded

    spans = tr.spans()
    assert any(sp.name == "page_fault" for sp in spans)   # pager visible
    checked = 0
    for rid, c in got.items():
        if rid % 2 or c.status not in ("ok", "partial"):
            continue
        att = attribution(spans, rid, sites=SHARD_SITES)
        assert att["wall_ms"] > 0
        assert att["coverage"] >= 0.95, \
            f"rid {rid} ({c.status}): coverage {att['coverage']:.3f}"
        checked += 1
    assert checked >= 8
    # a degraded request's flame renders with its merge + phase spans
    rid = next(r for r, c in got.items()
               if r % 2 == 0 and c.status == "partial")
    txt = format_trace(tr, rid, sites=SHARD_SITES)
    assert txt.startswith(f"request rid={rid}")
    assert "merge" in txt and "round @shard:" in txt


def test_profile_trace_raises_when_profiler_cannot_start(monkeypatch,
                                                         tmp_path):
    """A trace that was asked for and cannot start fails the run; no
    directory stays a no-op."""
    from repro.obs import profile_trace

    def broken(*args, **kwargs):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with profile_trace(str(tmp_path)):
            pass
    with profile_trace(None):
        pass


# ---------------------------------------------------------------------------
# the runtime's round spans
# ---------------------------------------------------------------------------

PHASES = ("admit", "dispatch", "fetch", "resolve")


def _rounds(tr, site="runtime"):
    """[(round span, its phase spans as emitted)] at one site."""
    spans = tr.spans(rid=None, site=site)
    kids = {}
    for sp in spans:
        if sp.name in PHASES:
            kids.setdefault(sp.parent_id, []).append(sp)
    return [(sp, kids.get(sp.span_id, [])) for sp in spans
            if sp.name == "round"]


@pytest.mark.parametrize("lanes", [1, 4, 16])
def test_round_spans_one_set_per_round_whatever_the_lanes(system, lanes):
    tr = Tracer(sample=1)
    rt = ContinuousRuntime(system["engine"], system["measure"].params,
                           system["base"], system["graph"].neighbors,
                           n_lanes=lanes, query_dim=16,
                           entry=system["graph"].entry, steps_per_tick=2,
                           tracer=tr)
    for i in range(16):
        rt.submit(system["queries"][i], rid=i)
    n_rounds = 0
    while rt.queue or rt.in_flight:
        rt.step_once()
        n_rounds += 1
    rounds = _rounds(tr)
    assert len(rounds) == n_rounds
    for rnd, phases in rounds:
        assert [p.name for p in phases] == list(PHASES)
    # site spans are the rounds' and nothing else; requests keep only
    # their root and queue spans
    assert len(tr.spans(rid=None)) == 5 * n_rounds
    assert {s.name for s in tr.spans() if s.rid is not None} == {
        "request", "queue"}


def test_round_spans_tile_the_round(system):
    tr = Tracer(sample=1)
    _run_single(system, tracer=tr)
    rounds = _rounds(tr)
    assert rounds
    for rnd, (adm, disp, fetch, res) in rounds:
        edges = [(rnd.t0, adm.t0), (adm.t1, disp.t0), (disp.t1, fetch.t0),
                 (fetch.t1, res.t0), (res.t1, rnd.t1)]
        for a, b in edges:
            assert abs(a - b) <= 1e-6
        assert all(p.t1 >= p.t0 for p in (adm, disp, fetch, res))
        assert rnd.attrs["lanes"] >= 1


def test_round_spans_fit_the_ring_at_64_lanes(system):
    """200 rounds of 64 busy lanes, every request traced: the default
    ring holds every span (per-lane phase copies would need ~38,000)."""
    tr = Tracer(sample=1)
    rt = ContinuousRuntime(system["engine"], system["measure"].params,
                           system["base"], system["graph"].neighbors,
                           n_lanes=64, query_dim=16,
                           entry=system["graph"].entry, steps_per_tick=1,
                           tracer=tr)
    qs = np.random.default_rng(7).normal(size=(2000, 16)).astype(np.float32)
    for i, q in enumerate(qs):
        rt.submit(q, rid=i)
    for _ in range(200):
        rt.step_once()
    assert rt.queue                         # every round ran 64 busy lanes
    rounds = _rounds(tr)
    assert len(rounds) == 200
    assert all(r.attrs["lanes"] == 64 for r, _ in rounds)
    assert tr.n_emitted == len(tr.spans()) < tr.capacity


def test_clock_anchor_maps_spans_into_their_annotation(tmp_path):
    """profile_trace writes the session's anchor beside a CPU trace; a
    span emitted inside a TraceAnnotation maps, through the anchor, to
    inside that annotation."""
    import glob
    import time

    from jax.profiler import ProfileData

    from repro.obs.profile import ANCHOR_FILE, profile_trace, trace_clock
    tr = Tracer()
    with profile_trace(str(tmp_path)):
        time.sleep(0.01)
        with jax.profiler.TraceAnnotation("test/outer"):
            time.sleep(0.002)
            t0 = time.perf_counter()
            time.sleep(0.005)
            tr.emit("inner", t0, time.perf_counter())
            time.sleep(0.002)
    with open(tmp_path / ANCHOR_FILE) as f:
        anchor = json.load(f)["perf_counter"]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    outer = [ev for plane in profile.planes for line in plane.lines
             for ev in line.events if ev.name == "test/outer"]
    assert len(outer) == 1
    a0, a1 = outer[0].start_ns, outer[0].start_ns + outer[0].duration_ns
    to_ns = trace_clock(profile, anchor)
    span, = tr.spans()
    assert a0 < to_ns(span.t0) < to_ns(span.t1) < a1
    # 2 ms of sleep on each side: the mapping is off by far less
    assert to_ns(span.t0) - a0 == pytest.approx(2e6, abs=1e6)
