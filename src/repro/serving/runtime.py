"""Continuous-batching serving runtime (DESIGN.md §9).

The batch-major engine steps a whole (Q, ...) state until ``jnp.all(done)``
— fine for closed-loop batch jobs, but under open-loop traffic the batch
finishes at the pace of its slowest lane while finished lanes burn frozen
steps. This runtime changes the engine's lifecycle from batch-scoped to
lane-scoped: the Q lanes are *slots*. An admission queue holds arriving
requests (arrival-time + deadline tagged); each scheduler round is

    admit    swap queued queries into free lanes via the engine's
             ``reset_lanes`` (lane-masked re-init: entry seed, pool,
             visited slice, counters — same shapes, no recompile)
    tick     ``steps_per_tick`` engine steps under one jitted fori_loop
             (finished lanes stay frozen by ``_freeze_done`` until
             harvested, exactly as in the one-shot while_loop)
    harvest  lanes whose query converged stream out per-request
             ``Completion``s and become free slots

Per-request results are bit-identical to one-shot ``engine.search`` on the
same query (the stages are lane-row-independent; tests pin ids AND scores).
``ShardedContinuousRuntime`` runs one runtime per corpus partition and
merges per-request top-k with the same ``merge_topk`` as the one-shot
sharded path.

The runtime is **bundle-agnostic**: it drives only the engine's lane
lifecycle (reset/step/idle), so any measure family resolved through the
``MeasureKernelBundle`` registry — kernel-backed score and fused analytic
grad stages included — serves through it unmodified (tests pin the
lane-recycling parity for both the deepfm and mlp bundles).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import ExpansionEngine, _freeze_done
from repro.obs.profile import annotate
from repro.obs.trace import NULL_TRACER
from repro.serving.health import ShardHealthTracker
from repro.serving.metrics import RequestRecord, ServingMetrics
from repro.serving.sla import SLAPolicy, resolve_tier


@dataclasses.dataclass
class Request:
    """One query for the admission queue. ``t_arrive`` is seconds relative
    to the start of the stream (``run_stream``) or an absolute ``now_fn``
    timestamp (direct ``submit``); ``deadline`` is seconds of queueing the
    request tolerates before it is dropped as timed out; ``budget_iters``
    caps this request's expansions (SLA tier / anytime search — None means
    the engine config's uniform cap); ``sla`` names an explicit tier when
    the runtime has an ``SLAPolicy`` (None = classify by deadline);
    ``angle_tau`` overrides the adaptive angle cutoff for this request
    (adaptive engines only — None = the tier's / engine's value);
    ``degraded`` records that pressure admitted it below its resolved
    tier (set by the runtime, not the caller)."""
    rid: int
    query: np.ndarray
    t_arrive: float = 0.0
    entry: Optional[int] = None
    deadline: Optional[float] = None
    budget_iters: Optional[int] = None
    sla: Optional[str] = None
    angle_tau: Optional[float] = None
    degraded: bool = False


@dataclasses.dataclass
class Completion:
    rid: int
    ids: np.ndarray        # (k,) int32
    scores: np.ndarray     # (k,) float32
    n_eval: int
    n_grad: int
    n_iters: int
    lane: int
    record: RequestRecord
    epoch: int = 0         # index version the request was admitted under
    # degradation ladder outcome (DESIGN.md §12): "ok" = full answer;
    # "partial" = merged over surviving shards only; "timeout" = deadline
    # drop; "shed" = load-shed at admission; "failed" = every fault domain
    # holding it failed. Anything except "ok" carries ids -1 / scores -inf
    # or a flagged subset — never a silently wrong full answer.
    status: str = "ok"
    partial: bool = False


def poisson_arrivals(n: int, qps: float, seed: int = 0) -> np.ndarray:
    """Open-loop Poisson arrival offsets (seconds): cumsum of Exp(1/qps)."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / qps, size=n))


class ContinuousRuntime:
    """Lane-recycling scheduler over one ``ExpansionEngine``.

    Shapes are fixed at construction (n_lanes × corpus) so every jitted
    callable — the lane-masked reset and the multi-step tick — compiles
    exactly once and is reused for the life of the runtime.
    """

    def __init__(self, engine: ExpansionEngine, params, corpus, neighbors,
                 n_lanes: int, query_dim: int, entry: int = 0,
                 steps_per_tick: int = 4,
                 now_fn: Callable[[], float] = time.perf_counter,
                 max_queue: Optional[int] = None,
                 fault_hook: Optional[Callable[[], float]] = None,
                 shared_fns: Optional[tuple] = None,
                 tracer=NULL_TRACER, trace_site: str = "",
                 trace_owner: bool = True,
                 sla_policy: Optional[SLAPolicy] = None):
        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        if steps_per_tick < 1:
            raise ValueError(
                f"steps_per_tick must be >= 1, got {steps_per_tick}")
        self.engine = engine
        self.params = params
        self.store = engine.prepare_store(corpus)
        self.neighbors = jnp.asarray(neighbors)
        self.n_lanes = n_lanes
        self.default_entry = entry
        self.steps_per_tick = steps_per_tick
        self._now = now_fn
        # bounded admission: beyond max_queue queued requests, submits are
        # load-shed (immediate status="shed" completion) instead of growing
        # the queue without bound; None = unbounded (previous behavior).
        # With an SLA policy the ladder degrades BEFORE it sheds: at
        # max_queue a tiered request is admitted at the policy floor
        # (smaller cap / tighter tau — cheaper, drains the queue faster)
        # and only past 2x max_queue is it shed outright.
        self.max_queue = max_queue
        self.sla_policy = sla_policy
        # EMA of observed service time (admit -> done), the deadline-aware
        # admission estimate: a request whose remaining deadline is under
        # the EMA is degraded one tier at admit instead of being left to
        # time out
        self._ema_service_s = 0.0
        # chaos surface (serving/faults.py): consulted once per busy tick;
        # returns extra reported tick-seconds or raises InjectedFault
        self.fault_hook = fault_hook
        self.tick_penalty_s = 0.0
        self._closing = False
        # telemetry (DESIGN.md §13): spans go to the injected tracer; the
        # NullTracer default keeps the disabled hot path at one attribute
        # lookup per guard. ``trace_site`` labels this runtime's spans
        # (the sharded runtime passes "shard:<s>"); ``trace_owner=False``
        # means something above us (the sharded merge layer) owns the
        # request root span's lifecycle — we only emit phase spans.
        self.tracer = tracer
        self.trace_site = trace_site
        self._trace_owner = trace_owner
        self._queue_spans: Dict[int, int] = {}
        self._n_ticks = 0
        self._t_fetched: Optional[float] = None

        self.epoch = 0
        self._pending_index: Optional[tuple] = None
        self._lane_epoch: List[int] = [0] * n_lanes
        self.queue: collections.deque[Request] = collections.deque()
        self._lane_req: List[Optional[Request]] = [None] * n_lanes
        self._admit_time: List[float] = [0.0] * n_lanes
        self._queries_np = np.zeros((n_lanes, query_dim), np.float32)
        self._entries_np = np.full((n_lanes,), entry, np.int32)
        self._caps_np = np.full((n_lanes,), engine.cfg.iters(), np.int32)
        self._taus_np = np.full((n_lanes,), engine.angle_tau, np.float32)
        self._queries_j = jnp.asarray(self._queries_np)
        self._state = engine.idle_state(n_lanes, self.store.n)
        self.completions: List[Completion] = []
        self.metrics = ServingMetrics(n_lanes)
        self._rid_gen = itertools.count()

        if shared_fns is not None:
            # same engine + same shapes => same traced program; sharing the
            # jitted callables (ShardedContinuousRuntime does, across its
            # per-shard runtimes) avoids S identical compiles — jax.jit
            # caches per closure identity, not per computation
            self._reset_fn, self._tick_fn = shared_fns
            return

        eng = engine
        spt = steps_per_tick

        def reset(params, store, queries, entries, state, mask, caps, taus):
            return eng.reset_lanes(params, store, queries, entries, state,
                                   mask, caps, taus)

        def tick(params, store, neighbors, queries, state):
            qs_flat = eng.repeat_queries(queries, neighbors.shape[1])

            def body(_, s):
                s2 = eng.step(params, store, neighbors, queries, qs_flat, s)
                return _freeze_done(s.done, s2, s)

            with jax.named_scope("repro_loop"):
                return jax.lax.fori_loop(0, spt, body, state)

        self._reset_fn = jax.jit(reset)
        self._tick_fn = jax.jit(tick)

    # -- queue side ---------------------------------------------------------

    @property
    def in_flight(self) -> int:
        return sum(r is not None for r in self._lane_req)

    def submit(self, query: np.ndarray, rid: Optional[int] = None,
               entry: Optional[int] = None, deadline: Optional[float] = None,
               t_arrive: Optional[float] = None,
               budget_iters: Optional[int] = None,
               sla: Optional[str] = None,
               angle_tau: Optional[float] = None) -> int:
        rid = rid if rid is not None else next(self._rid_gen)
        t = t_arrive if t_arrive is not None else self._now()
        tr = self.tracer
        if tr.enabled and tr.sampled(rid):
            # idempotent: under the sharded fan-out the merge layer has
            # already created this rid's root — we just parent to it
            root = tr.root_for(rid, t0=t)
            self._queue_spans[rid] = tr.begin(
                "queue", t0=t, rid=rid, site=self.trace_site, parent=root)
        tier = resolve_tier(self.sla_policy, sla, deadline)
        degraded = False
        pressured = (self.max_queue is not None
                     and len(self.queue) >= self.max_queue)
        if self._closing or (pressured and (
                tier is None
                or len(self.queue) >= 2 * self.max_queue)):
            self._resolve_sentinel(rid, t, "shed",
                                   sla=tier.name if tier else "")
            return rid
        eff = tier
        if pressured:
            # degrade-before-shed: admit at the policy floor instead of
            # dropping; the record keeps the ORIGINAL tier name so
            # per-tier degrade counts mean "tier-X traffic that was
            # degraded", with ``degraded`` carrying the outcome
            eff = self.sla_policy.floor()
            degraded = eff.name != tier.name
        if eff is not None:
            if budget_iters is None:
                budget_iters = eff.iter_cap
            if angle_tau is None:
                angle_tau = eff.angle_tau
        self.queue.append(Request(rid, np.asarray(query, np.float32), t,
                                  entry, deadline, budget_iters,
                                  sla=tier.name if tier else sla,
                                  angle_tau=angle_tau, degraded=degraded))
        return rid

    def _resolve_sentinel(self, rid: int, t_arrive: float,
                          status: str, sla: str = "") -> Completion:
        """Resolve a request WITHOUT searching (shed / failed): the rid
        completes exactly once with ids -1 / scores -inf, flagged by
        ``status`` — downstream consumers never hang on it."""
        now = self._now()
        rec = RequestRecord(rid, t_arrive, now, now,
                            shed=(status == "shed"),
                            failed=(status == "failed"), sla=sla)
        k = self.engine.cfg.k
        c = Completion(rid, np.full((k,), -1, np.int32),
                       np.full((k,), -np.inf, np.float32), 0, 0, 0, -1,
                       rec, self.epoch, status=status)
        self.metrics.observe(rec)
        self.completions.append(c)
        tr = self.tracer
        if tr.enabled:
            qs = self._queue_spans.pop(rid, None)
            if qs is not None:
                tr.end(qs, t1=now, status=status)
            if self._trace_owner and tr.sampled(rid):
                tr.finish_request(rid, t1=now, status=status)
        return c

    def complete_failed(self, rid: int,
                        t_arrive: Optional[float] = None) -> Completion:
        """Resolve one rid as failed without queueing it (the sharded
        runtime synthesizes parts for breaker-open shards this way)."""
        t = t_arrive if t_arrive is not None else self._now()
        return self._resolve_sentinel(rid, t, "failed")

    def shed_queue(self) -> List[Completion]:
        """Shed every queued request (graceful drain — nothing admitted)."""
        out = []
        while self.queue:
            req = self.queue.popleft()
            out.append(self._resolve_sentinel(req.rid, req.t_arrive, "shed",
                                              sla=req.sla or ""))
        return out

    def fail_all(self) -> List[Completion]:
        """Resolve EVERYTHING this runtime holds as failed — in-flight
        lanes and queued requests alike — and reset the engine state to
        idle. Called when this runtime's fault domain is declared dead
        (circuit breaker opens); a later re-admission starts clean."""
        out = []
        for lane in range(self.n_lanes):
            req = self._lane_req[lane]
            if req is not None:
                self._lane_req[lane] = None
                out.append(self._resolve_sentinel(req.rid, req.t_arrive,
                                                  "failed"))
        while self.queue:
            req = self.queue.popleft()
            out.append(self._resolve_sentinel(req.rid, req.t_arrive,
                                              "failed"))
        self._state = self.engine.idle_state(self.n_lanes, self.store.n)
        return out

    # -- index-version epochs (streaming mutation) --------------------------

    def install_index(self, corpus, neighbors, entry: Optional[int] = None
                      ) -> int:
        """Stage a new index version (mutated / compacted corpus store +
        neighbor lists + optional new entry point). The swap is deferred:
        in-flight lanes FINISH against the epoch they were admitted under
        (their pools, visited bitmaps, and neighbor ids are all old-index
        coordinates), admissions hold while the swap is pending, and once
        the runtime drains the staged index swaps in atomically — queued
        and future requests then search the new epoch. Returns the epoch
        number the staged index will serve as; each ``Completion.epoch``
        records the version its request actually ran against."""
        self._pending_index = (corpus, neighbors, entry)
        return self.epoch + 1

    def _maybe_swap_index(self) -> bool:
        if self._pending_index is None or self.in_flight:
            return False
        corpus, neighbors, entry = self._pending_index
        self._pending_index = None
        self.store = self.engine.prepare_store(corpus)
        self.neighbors = jnp.asarray(neighbors)
        if entry is not None:
            self.default_entry = int(entry)
        self._entries_np[:] = self.default_entry
        # shapes may change (inserts grow N, compaction shrinks it); the
        # jitted reset/tick retrace on the new shapes automatically
        self._state = self.engine.idle_state(self.n_lanes, self.store.n)
        self.epoch += 1
        return True

    # -- scheduler round ----------------------------------------------------

    def _admit(self, now: float) -> Tuple[List[Completion], np.ndarray]:
        """Move queued requests into free lanes (host bookkeeping only);
        returns the deadline drops and the mask of lanes to reset."""
        dropped: List[Completion] = []
        mask = np.zeros((self.n_lanes,), bool)
        if self._pending_index is not None:
            return dropped, mask    # admissions hold until the staged epoch
        free = [l for l in range(self.n_lanes) if self._lane_req[l] is None]
        if not free or not self.queue:
            return dropped, mask
        tr = self.tracer
        while free and self.queue:
            req = self.queue.popleft()
            if req.deadline is not None and now - req.t_arrive > req.deadline:
                # dropped, but still completed: downstream consumers (the
                # sharded merge, the stream driver) must see every rid
                # resolve exactly once
                k = self.engine.cfg.k
                rec = RequestRecord(req.rid, req.t_arrive, now, now,
                                    timed_out=True, sla=req.sla or "",
                                    degraded=req.degraded)
                self.metrics.observe(rec)
                c = Completion(req.rid, np.full((k,), -1, np.int32),
                               np.full((k,), -np.inf, np.float32),
                               0, 0, 0, -1, rec, self.epoch,
                               status="timeout")
                self.completions.append(c)
                dropped.append(c)
                if tr.enabled:
                    qs = self._queue_spans.pop(req.rid, None)
                    if qs is not None:
                        tr.end(qs, t1=now, status="timeout")
                    if self._trace_owner and tr.sampled(req.rid):
                        tr.finish_request(req.rid, t1=now, status="timeout")
                continue
            cap, tau = req.budget_iters, req.angle_tau
            if (self.sla_policy is not None and req.sla
                    and req.deadline is not None
                    and self._ema_service_s > 0.0
                    and req.deadline - (now - req.t_arrive)
                    < self._ema_service_s):
                # deadline-aware degrade: the remaining budget is under
                # the typical service time at this tier — drop one rung
                # (cheaper knobs finish sooner) rather than admitting
                # work that will blow its deadline anyway
                down = self.sla_policy.degrade(self.sla_policy.get(req.sla))
                if down is not None:
                    cap = (down.iter_cap if down.iter_cap is not None
                           else cap)
                    tau = down.angle_tau
                    req.degraded = True
            lane = free.pop(0)
            mask[lane] = True
            if tr.enabled:
                qs = self._queue_spans.pop(req.rid, None)
                if qs is not None:
                    tr.end(qs, t1=now, lane=lane)
            self._lane_req[lane] = req
            self._lane_epoch[lane] = self.epoch
            self._admit_time[lane] = now
            self._queries_np[lane] = req.query
            self._entries_np[lane] = (req.entry if req.entry is not None
                                      else self.default_entry)
            self._caps_np[lane] = (cap if cap is not None
                                   else self.engine.cfg.iters())
            self._taus_np[lane] = (tau if tau is not None
                                   else self.engine.angle_tau)
        return dropped, mask

    def _dispatch(self, mask: np.ndarray) -> None:
        """Send the round's device work: the lane-masked reset of the
        newly admitted lanes, then the tick. Both calls are async."""
        if mask.any():
            self._queries_j = jnp.asarray(self._queries_np)
            with annotate("repro/reset"):
                self._state = self._reset_fn(
                    self.params, self.store, self._queries_j,
                    jnp.asarray(self._entries_np), self._state,
                    jnp.asarray(mask), jnp.asarray(self._caps_np),
                    jnp.asarray(self._taus_np))
        self._tick()

    def _tick(self) -> None:
        self.tick_penalty_s = 0.0
        busy = self.in_flight
        if not busy:
            return
        if self.fault_hook is not None:
            # may raise InjectedFault (crash) or report extra seconds
            # (stall/slow tick) — the sharded runtime adds the penalty to
            # the measured tick time before its deadline check
            self.tick_penalty_s = float(self.fault_hook() or 0.0)
        with annotate("repro/tick"):
            self._state = self._tick_fn(self.params, self.store,
                                        self.neighbors, self._queries_j,
                                        self._state)
        self._n_ticks += 1
        self.metrics.observe_occupancy(busy, self.n_lanes,
                                       self.steps_per_tick)

    def _harvest(self, now: float) -> List[Completion]:
        occupied = [l for l in range(self.n_lanes)
                    if self._lane_req[l] is not None]
        if not occupied:
            return []
        # one fused transfer per round: done + results + counters together
        # (the sync on this fetch is what absorbs the tick's compute; a
        # separate done-probe would just pay the round-trip twice)
        k = self.engine.cfg.k
        done, ids, scores, n_eval, n_grad, n_iters = jax.device_get(
            (self._state.done, self._state.pool_ids[:, :k],
             self._state.pool_scores[:, :k], self._state.n_eval,
             self._state.n_grad, self._state.n_iters))
        if self.tracer.enabled:
            # the traced round's fetch | resolve edge, stamped here so the
            # round still goes through this one method
            self._t_fetched = self._now()
        ready = [l for l in occupied if done[l]]
        if not ready:
            return []
        out = []
        for lane in ready:
            req = self._lane_req[lane]
            service = now - self._admit_time[lane]
            self._ema_service_s = (service if self._ema_service_s == 0.0
                                   else 0.9 * self._ema_service_s
                                   + 0.1 * service)
            rec = RequestRecord(req.rid, req.t_arrive,
                                self._admit_time[lane], now,
                                int(n_eval[lane]), int(n_grad[lane]),
                                int(n_iters[lane]), sla=req.sla or "",
                                degraded=req.degraded)
            c = Completion(req.rid, ids[lane].copy(), scores[lane].copy(),
                           int(n_eval[lane]), int(n_grad[lane]),
                           int(n_iters[lane]), lane, rec,
                           self._lane_epoch[lane])
            self.metrics.observe(rec)
            self.completions.append(c)
            self._lane_req[lane] = None
            out.append(c)
        return out

    def step_once(self) -> List[Completion]:
        """One admit → tick → harvest round; returns every request that
        resolved this round — harvested results AND deadline drops. A
        staged index (``install_index``) swaps in at the top of the round
        once the previous epoch's lanes have all harvested."""
        self._maybe_swap_index()
        self.metrics.observe_queue_depth(len(self.queue))
        if not self.tracer.enabled:
            dropped, mask = self._admit(self._now())
            self._dispatch(mask)
            return dropped + self._harvest(self._now())
        # traced round: five shared timestamps tile it into admit (host
        # bookkeeping), dispatch (the async reset + tick calls), fetch (the
        # one device_get, which waits out the dispatched device work) and
        # resolve (the per-lane Python after it)
        t0 = self._now()
        dropped, mask = self._admit(t0)
        t1 = self._now()
        self._dispatch(mask)
        t2 = self._now()
        self._t_fetched = None
        harvested = self._harvest(t2)
        t4 = self._now()
        t3 = t2 if self._t_fetched is None else self._t_fetched
        self._emit_round_spans((t0, t1, t2, t3, t4), int(mask.sum()),
                               dropped, harvested)
        return dropped + harvested

    @property
    def site(self) -> str:
        """The site of this runtime's round spans: its ``trace_site``, or
        ``"runtime"`` for a standalone runtime. ``attribution`` and
        ``format_trace`` weave them into a request through ``sites``."""
        return self.trace_site or "runtime"

    def _emit_round_spans(self, ts: tuple, admitted: int,
                          dropped: List[Completion],
                          harvested: List[Completion]) -> None:
        """One ``round`` span with its four phases, at the runtime's
        site (no rid): the same set whatever the number of lanes. A round
        with no lane busy and nothing admitted or dropped emits nothing."""
        tr = self.tracer
        busy = self.in_flight + len(harvested)
        if busy or dropped:
            site = self.site
            rnd = tr.emit("round", ts[0], ts[4], site=site, i=self._n_ticks,
                          steps=self.steps_per_tick, lanes=busy,
                          admitted=admitted, resolved=len(harvested))
            for name, a, b in zip(("admit", "dispatch", "fetch", "resolve"),
                                  ts, ts[1:]):
                tr.emit(name, a, b, site=site, parent=rnd)
        if self._trace_owner:
            for c in harvested:
                if c.lane >= 0 and tr.sampled(c.rid):
                    tr.finish_request(c.rid, t1=ts[4], status=c.status)

    def close(self) -> List[Completion]:
        """Graceful drain: stop admitting (late submits are shed), shed the
        queue, finish the in-flight lanes. Returns everything that resolved
        during the drain (also visible via ``pop_completions``)."""
        self._closing = True
        out = self.shed_queue()
        while self.in_flight:
            out += self.step_once()
        if self._trace_owner and self.tracer.enabled:
            # anything still open (a span whose request never resolved)
            # surfaces flagged open=True rather than vanishing
            self.tracer.drain()
        return out

    def pop_completions(self) -> List[Completion]:
        out, self.completions = self.completions, []
        return out

    # -- observability ------------------------------------------------------

    def bind_registry(self, registry):
        """Register this runtime's metric families (serving + pager) into
        an ``obs.Registry``. Call AFTER ``warmup()`` — warmup replaces
        ``self.metrics`` with a fresh object."""
        self.metrics.bind_registry(registry)
        if getattr(self.store, "is_paged", False):
            self.store.bind_registry(registry, shard=self.trace_site or "0")
        return registry

    def health_snapshot(self) -> dict:
        recs = self.metrics.records
        snap = {"queue": len(self.queue), "in_flight": self.in_flight,
                "completed": sum(not (r.timed_out or r.shed or r.failed)
                                 for r in recs),
                "timed_out": sum(r.timed_out for r in recs),
                "shed": sum(r.shed for r in recs),
                "failed": sum(r.failed for r in recs)}
        if getattr(self.store, "is_paged", False):
            st = self.store.stats_snapshot()
            snap["pager"] = {"hit_rate": round(st.hit_rate, 3),
                             "retries": st.retries,
                             "io_errors": st.io_errors,
                             "mode": st.fallback or "paged"}
        return snap

    def format_health(self) -> str:
        s = self.health_snapshot()
        line = (f"[health] queue={s['queue']} in_flight={s['in_flight']} "
                f"completed={s['completed']} timed_out={s['timed_out']} "
                f"shed={s['shed']} failed={s['failed']}")
        if "pager" in s:
            p = s["pager"]
            line += (f" pager(mode={p['mode']} hit_rate={p['hit_rate']} "
                     f"retries={p['retries']} io_errors={p['io_errors']})")
        return line

    def warmup(self, query: np.ndarray) -> None:
        """Compile the jitted reset + tick off the clock: run one sentinel
        request to completion, then discard its completion and metrics.
        Both serve paths call this before timing anything."""
        self.run_stream([Request(rid=-1, query=np.asarray(query))],
                        realtime=False)
        self.pop_completions()
        self.metrics = ServingMetrics(self.n_lanes)

    # -- open-loop driver ---------------------------------------------------

    def run_stream(self, requests: Sequence[Request],
                   realtime: bool = True,
                   health_every_s: Optional[float] = None
                   ) -> List[Completion]:
        """Drive a pre-scheduled stream to completion. ``t_arrive`` offsets
        are seconds from the start of the run; arrivals are open-loop —
        independent of completions. ``realtime=False`` collapses the
        schedule — every request is due immediately and is stamped as
        arriving at submission (honoring future offsets in the records
        would make latency/queue times negative); arrival ORDER still
        follows the offsets, which is all the deterministic tests need.
        ``health_every_s`` prints a periodic ``format_health`` line."""
        pending = collections.deque(
            sorted(requests, key=lambda r: r.t_arrive))
        t0 = self._now()
        t_health = t0
        while pending or self.queue or self.in_flight:
            if health_every_s is not None \
                    and self._now() - t_health >= health_every_s:
                t_health = self._now()
                print(self.format_health())
            now = self._now() - t0
            while pending and (not realtime or pending[0].t_arrive <= now):
                r = pending.popleft()
                self.submit(r.query, rid=r.rid, entry=r.entry,
                            deadline=r.deadline,
                            t_arrive=(t0 + r.t_arrive) if realtime
                            else self._now(),
                            budget_iters=r.budget_iters, sla=r.sla,
                            angle_tau=r.angle_tau)
            if realtime and not self.queue and not self.in_flight and pending:
                dt = pending[0].t_arrive - (self._now() - t0)
                if dt > 0:
                    time.sleep(min(dt, 0.005))
                continue
            self.step_once()
        return self.pop_completions()


class ShardedContinuousRuntime:
    """Continuous batching over a partitioned corpus: one lane-recycling
    runtime per shard, a request fans out to every shard, and the harvest
    side merges per-shard top-k with the SAME ``merge_topk`` as the
    one-shot sharded path (bit-identical merged results). Counters follow
    the sharded accounting: ``n_eval``/``n_grad`` sum over shards (total
    work), ``n_iters`` is the max (shards step in parallel — the critical
    path).

    Each shard is a **fault domain** (DESIGN.md §12): a per-shard
    ``ShardHealthTracker`` (circuit breaker + straggler monitor) takes a
    strike whenever a shard's tick raises or blows ``tick_deadline_s``;
    ``k_failures`` consecutive strikes open the breaker — the shard's
    in-flight work resolves as failed parts, it receives no traffic for
    ``cooldown_rounds`` rounds, then probes half-open and one clean busy
    tick re-admits it. Merges proceed over the surviving shards with the
    completion flagged ``partial=True``; only if EVERY shard failed does
    the rid resolve as ``failed`` (ids -1). ``fault_plan`` installs a
    chaos schedule's tick hooks (site ``shard:<s>/tick``) for tests and
    ``benchmarks/chaos.py``."""

    def __init__(self, engine: ExpansionEngine, params, index, n_lanes: int,
                 query_dim: int, steps_per_tick: int = 4,
                 now_fn: Callable[[], float] = time.perf_counter,
                 max_queue: Optional[int] = None,
                 tick_deadline_s: Optional[float] = None,
                 k_failures: int = 3, cooldown_rounds: int = 8,
                 fault_plan=None, tracer=NULL_TRACER,
                 sla_policy: Optional[SLAPolicy] = None):
        self.engine = engine
        self.index = index
        self.max_queue = max_queue
        # tier resolution happens HERE, once per rid: shards receive the
        # resolved concrete knobs (cap/tau), never the policy — per-shard
        # classification could disagree (admit clocks differ) and a rid
        # must run the same tier on every partition
        self.sla_policy = sla_policy
        self._sla_info: Dict[int, tuple] = {}
        self.tick_deadline_s = tick_deadline_s
        self._closing = False
        self.tracer = tracer
        # merge-window open time per sampled rid: stamped when the FIRST
        # shard part lands, so the "merge" span covers the straggler wait
        # (slowest-shard gap) as well as the merge pass itself
        self._merge_open: Dict[int, float] = {}
        self.health = ShardHealthTracker(index.n_shards,
                                         k_failures=k_failures,
                                         cooldown_rounds=cooldown_rounds)
        self.runtimes: List[ContinuousRuntime] = []
        for s in range(index.n_shards):
            # partitions are equal-shape by construction, so every shard
            # runtime reuses the first one's jitted reset/tick — one
            # compile, not n_shards identical ones
            shared = (None if not self.runtimes else
                      (self.runtimes[0]._reset_fn, self.runtimes[0]._tick_fn))
            hook = (fault_plan.tick_hook(f"shard:{s}/tick")
                    if fault_plan is not None else None)
            self.runtimes.append(ContinuousRuntime(
                engine, params, index.base[s], index.neighbors[s], n_lanes,
                query_dim, entry=int(index.entries[s]),
                steps_per_tick=steps_per_tick, now_fn=now_fn,
                fault_hook=hook, shared_fns=shared,
                tracer=tracer, trace_site=f"shard:{s}", trace_owner=False))
        self.metrics = ServingMetrics(n_lanes * index.n_shards)
        self.completions: List[Completion] = []
        self._partial: Dict[int, List[Completion]] = {}
        self._rid_gen = itertools.count()
        self._merge = jax.jit(_merge_one, static_argnames=("k",))
        self._indices: Dict[int, object] = {0: index}

    def install_index(self, index) -> int:
        """Stage a new ``ShardedIndex`` version on every shard runtime.
        Each shard swaps when ITS lanes drain (per-shard epochs advance in
        lockstep — one install bumps every shard by one), and the merge
        remaps each partial's local ids through the global_ids of the
        epoch that shard actually searched, so harvests straddling the
        swap stay correct. Returns the staged epoch number."""
        if index.n_shards != len(self.runtimes):
            raise ValueError(
                f"staged index has {index.n_shards} shards, runtime has "
                f"{len(self.runtimes)}")
        epoch = max(self._indices) + 1
        self._indices[epoch] = index
        self.index = index
        for s, rt in enumerate(self.runtimes):
            rt.install_index(index.base[s], index.neighbors[s],
                             int(index.entries[s]))
        return epoch

    @property
    def in_flight(self) -> int:
        return max(rt.in_flight for rt in self.runtimes)

    @property
    def queued(self) -> int:
        return max(len(rt.queue) for rt in self.runtimes)

    def submit(self, query: np.ndarray, rid: Optional[int] = None,
               deadline: Optional[float] = None,
               t_arrive: Optional[float] = None,
               budget_iters: Optional[int] = None,
               sla: Optional[str] = None,
               angle_tau: Optional[float] = None) -> int:
        """No per-request ``entry`` here (unlike the single-partition
        runtime): entry ids are partition-LOCAL rows, so one global value
        cannot mean anything across shards — each shard searches from its
        own entry point."""
        rid = rid if rid is not None else next(self._rid_gen)
        now_fn = self.runtimes[0]._now
        t = t_arrive if t_arrive is not None else now_fn()
        tr = self.tracer
        traced = tr.enabled and tr.sampled(rid)
        if traced:
            # the merge layer owns the root's lifecycle; per-shard
            # sub-runtimes parent their phase spans to it
            tr.root_for(rid, t0=t)
        tier = resolve_tier(self.sla_policy, sla, deadline)
        degraded = False
        pressured = (self.max_queue is not None
                     and self.queued >= self.max_queue)
        if self._closing or (pressured and (
                tier is None or self.queued >= 2 * self.max_queue)):
            # shed at the TOP level: per-shard sheds would desync rid
            # resolution across the fan-out
            now = now_fn()
            rec = RequestRecord(rid, t, now, now, shed=True,
                                sla=tier.name if tier else "")
            k = self.engine.cfg.k
            self.metrics.observe(rec)
            self.completions.append(Completion(
                rid, np.full((k,), -1, np.int32),
                np.full((k,), -np.inf, np.float32), 0, 0, 0, -1, rec,
                max(self._indices), status="shed"))
            if traced:
                tr.emit("queue", t, now, rid=rid,
                        parent=tr.root_for(rid), status="shed")
                tr.finish_request(rid, t1=now, status="shed")
            return rid
        eff = tier
        if pressured:
            # degrade-before-shed (same ladder as the single runtime)
            eff = self.sla_policy.floor()
            degraded = eff.name != tier.name
        if eff is not None:
            if budget_iters is None:
                budget_iters = eff.iter_cap
            if angle_tau is None:
                angle_tau = eff.angle_tau
            self._sla_info[rid] = (tier.name, degraded)
        for s, rt in enumerate(self.runtimes):
            if self.health.serving(s):
                rt.submit(query, rid=rid, deadline=deadline, t_arrive=t,
                          budget_iters=budget_iters,
                          sla=tier.name if tier else None,
                          angle_tau=angle_tau)
            else:
                # breaker open: synthesize this shard's part as failed up
                # front so the rid's merge window is never missing a slot
                rt.complete_failed(rid, t)
        return rid

    def _shard_failed(self, s: int, reason: str) -> bool:
        opened = self.health.record_failure(s, reason)
        if opened:
            # out of rotation: everything the shard holds resolves as
            # failed parts, so no merge window waits on a dead shard.
            # (A strike SHORT of opening leaves its work in place — the
            # next round retries it, and transient faults recover free.)
            self.runtimes[s].fail_all()
        return opened

    def step_once(self) -> List[Completion]:
        self.health.on_round()
        now_fn = self.runtimes[0]._now
        times = {}
        for s, rt in enumerate(self.runtimes):
            if not self.health.serving(s):
                continue
            probe = rt.in_flight > 0 or bool(rt.queue)
            t0 = now_fn()
            try:
                rt.step_once()
            except Exception as err:  # noqa: BLE001 — injected faults,
                # CorpusUnavailableError, pager callbacks dying inside XLA:
                # ANY tick death is a strike against this fault domain
                self._shard_failed(s, repr(err))
                continue
            dt = (now_fn() - t0) + rt.tick_penalty_s
            if self.tick_deadline_s is not None and dt > self.tick_deadline_s:
                self._shard_failed(
                    s, f"tick {dt:.3f}s > deadline {self.tick_deadline_s}s")
                continue
            times[s] = min(dt, 1e6)     # stalls report inf; keep medians sane
            self.health.record_success(s, probed=probe)
        self.health.record_tick_times(times)
        # merged occupancy mirrors the per-shard tick observations (the
        # sub-runtimes own the raw samples; without this the sharded
        # report would always read occupancy 0)
        self.metrics.sync_occupancy(
            sum(rt.metrics._busy_steps for rt in self.runtimes),
            sum(rt.metrics._lane_steps for rt in self.runtimes))
        self.metrics.observe_queue_depth(self.queued)
        return self._merge_ready()

    def _merge_ready(self) -> List[Completion]:
        S = len(self.runtimes)
        tr = self.tracer
        now_fn = self.runtimes[0]._now
        for s, rt in enumerate(self.runtimes):
            for c in rt.pop_completions():
                if tr.enabled and c.rid not in self._merge_open \
                        and tr.sampled(c.rid):
                    self._merge_open[c.rid] = now_fn()
                self._partial.setdefault(c.rid, [None] * S)[s] = c
        out = []
        k = self.engine.cfg.k
        for rid in [r for r, ps in self._partial.items()
                    if all(p is not None for p in ps)]:
            parts = self._partial.pop(rid)
            live = [(s, p) for s, p in enumerate(parts)
                    if p.status not in ("failed", "shed")]
            n_failed = sum(p.status == "failed" for p in parts)
            shed = any(p.status == "shed" for p in parts)
            none_ids = np.full((k,), -1, np.int32)
            none_scores = np.full((k,), -np.inf, np.float32)
            if shed:
                # drain-time shed on the serving shards => the rid is shed
                # at the merged level too
                status, ids, scores = "shed", none_ids, none_scores
            elif not live:
                # EVERY shard in the window failed — the empty-harvest
                # path: resolve completed-with-all-ids-(-1) (the deadline
                # contract) instead of raising or waiting forever
                status, ids, scores = "failed", none_ids, none_scores
            elif any(p.record.timed_out for _, p in live):
                # per-shard queues can disagree about a deadline (admit
                # times differ per shard); a merged answer missing a whole
                # partition's candidates is NOT a valid top-k, so the
                # single-runtime contract holds end to end: timed out =>
                # ids all -1
                status, ids, scores = "timeout", none_ids, none_scores
            else:
                # merge over the shards that actually answered; a missing
                # (failed) shard makes the answer partial — flagged, never
                # silently passed off as a full top-k
                gl = [np.where(p.ids >= 0,
                               self._indices[p.epoch]
                               .global_ids[s][np.maximum(p.ids, 0)],
                               -1) for s, p in live]
                m_ids, m_scores = self._merge(
                    jnp.asarray(np.stack(gl))[None],
                    jnp.asarray(np.stack([p.scores for _, p in live]))[None],
                    k=k)
                ids, scores = np.asarray(m_ids)[0], np.asarray(m_scores)[0]
                status = "partial" if n_failed else "ok"
            live_p = [p for _, p in live]
            src = live_p if live_p else parts
            sla_name, degraded = self._sla_info.pop(rid, ("", False))
            # a per-shard deadline degrade counts at the merged level too
            degraded = degraded or any(p.record.degraded for p in parts)
            rec = RequestRecord(
                rid, min(p.record.t_arrive for p in parts),
                max(p.record.t_admit for p in src),
                max(p.record.t_done for p in src),
                sum(p.n_eval for p in live_p),
                sum(p.n_grad for p in live_p),
                max((p.n_iters for p in live_p), default=0),
                timed_out=(status == "timeout"), shed=(status == "shed"),
                failed=(status == "failed"),
                partial=(status == "partial"),
                sla=sla_name, degraded=degraded)
            c = Completion(rid, ids, scores,
                           rec.n_eval, rec.n_grad, rec.n_iters, -1, rec,
                           max(p.epoch for p in parts), status=status,
                           partial=(status == "partial"))
            self.metrics.observe(rec)
            self.completions.append(c)
            out.append(c)
            if tr.enabled and tr.sampled(rid):
                now = now_fn()
                tr.emit("merge", self._merge_open.pop(rid, now), now,
                        rid=rid, parent=tr.root_for(rid), status=status,
                        shards=len(live))
                tr.finish_request(rid, t1=now, status=status)
        return out

    def pop_completions(self) -> List[Completion]:
        out, self.completions = self.completions, []
        return out

    def close(self) -> List[Completion]:
        """Graceful drain at the merged level: admits nothing new, sheds
        queued requests (their merge windows resolve as shed), then rounds
        continue until every in-flight rid has merged."""
        self._closing = True
        out = []
        for rt in self.runtimes:
            rt.shed_queue()
        # un-popped per-shard parts (e.g. synthesized failures) count as
        # unresolved work: every rid must merge before the drain ends
        while self.in_flight or self._partial \
                or any(rt.completions for rt in self.runtimes):
            out += self.step_once()
        if self.tracer.enabled:
            self.tracer.drain()
        return out

    # -- observability ------------------------------------------------------

    def bind_registry(self, registry):
        """Register merged serving metrics, per-shard health, and any
        paged shard stores into an ``obs.Registry``."""
        self.metrics.bind_registry(registry)
        self.health.bind_registry(registry)
        for s, rt in enumerate(self.runtimes):
            if getattr(rt.store, "is_paged", False):
                rt.store.bind_registry(registry, shard=str(s))
        return registry

    def health_snapshot(self) -> dict:
        recs = self.metrics.records
        return {"shards": self.health.states(),
                "breaker_opens": self.health.n_opened,
                "queue": self.queued, "in_flight": self.in_flight,
                "completed": sum(not (r.timed_out or r.shed or r.failed)
                                 for r in recs),
                "partial": sum(r.partial for r in recs),
                "timed_out": sum(r.timed_out for r in recs),
                "shed": sum(r.shed for r in recs),
                "failed": sum(r.failed for r in recs)}

    def format_health(self) -> str:
        s = self.health_snapshot()
        return (f"[health] shards=[{','.join(s['shards'])}] "
                f"opens={s['breaker_opens']} queue={s['queue']} "
                f"in_flight={s['in_flight']} completed={s['completed']} "
                f"partial={s['partial']} timed_out={s['timed_out']} "
                f"shed={s['shed']} failed={s['failed']}")

    def run_stream(self, requests: Sequence[Request],
                   realtime: bool = True,
                   health_every_s: Optional[float] = None
                   ) -> List[Completion]:
        now_fn = self.runtimes[0]._now
        pending = collections.deque(
            sorted(requests, key=lambda r: r.t_arrive))
        t0 = now_fn()
        t_health = t0
        while pending or self.queued or self.in_flight or self._partial \
                or any(rt.completions for rt in self.runtimes):
            if health_every_s is not None \
                    and now_fn() - t_health >= health_every_s:
                t_health = now_fn()
                print(self.format_health())
            now = now_fn() - t0
            while pending and (not realtime or pending[0].t_arrive <= now):
                r = pending.popleft()
                if r.entry is not None:
                    raise ValueError(
                        "Request.entry is partition-local and cannot be "
                        "honored by the sharded runtime; leave it None")
                self.submit(r.query, rid=r.rid, deadline=r.deadline,
                            t_arrive=(t0 + r.t_arrive) if realtime
                            else now_fn(),
                            budget_iters=r.budget_iters, sla=r.sla,
                            angle_tau=r.angle_tau)
            if realtime and not self.queued and not self.in_flight \
                    and not self._partial and pending:
                dt = pending[0].t_arrive - (now_fn() - t0)
                if dt > 0:
                    time.sleep(min(dt, 0.005))
                continue
            self.step_once()
        return self.pop_completions()


def _merge_one(all_ids, all_scores, k: int):
    from repro.core.sharded import merge_topk
    return merge_topk(all_ids, all_scores, k)
