"""Open loop of independent users into the continuous runtime: Poisson
arrivals at a fixed rate, each request stamped with its due time and
submitted when it falls due, whether or not earlier ones have finished.
The driver calls ``ContinuousRuntime.submit`` and ``step_once`` itself and
records how late each submit ran. After the window every request due in
it is waited for, up to ``drain_s`` past the close; one that never
completes is missing.

Traffic parameters: ``rate_qps``, ``gap_seed``, ``lanes``,
``steps_per_tick``, ``drain_s``.
"""
from __future__ import annotations

import math
import time

import numpy as np

import data
from harness import log


def p95(values) -> float:
    """Nearest-rank 95th percentile (inf counts as a value)."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)])


def run(env) -> dict:
    from repro.serving import ContinuousRuntime
    tr = env.traffic
    rate = float(tr["rate_qps"])
    rt = ContinuousRuntime(env.engine, env.params, env.store, env.neighbors,
                           n_lanes=int(tr["lanes"]),
                           query_dim=env.query_dim, entry=env.entry,
                           steps_per_tick=int(tr["steps_per_tick"]))
    env.runtime = rt
    rng = env.rng(2)
    due = data.poisson_offsets(rate, env.seconds, int(tr["gap_seed"]), rng)
    users = rng.integers(0, len(env.users), len(due))
    n = len(due)
    # the runtime's own warm-up admits into an idle state only; a second
    # burst of more requests than lanes also compiles the admit onto a
    # ticked state and the harvest's reads of it, as the window does
    warm = env.users[env.rng(0).integers(0, len(env.users), rt.n_lanes + 8)]
    rt.warmup(warm[0])
    for q in warm:
        rt.submit(q)
    while rt.queue or rt.in_flight:
        rt.step_once()
    rt.pop_completions()
    rt.metrics = type(rt.metrics)(rt.n_lanes)
    submitted = np.full(n, np.nan)
    done = {}
    depth = []
    i = 0
    traced = None
    t_stop = math.inf
    stalls = []         # (s into the window, ms) of loop turns over 50 ms
    t0 = env.open_window()
    if env.trace:
        env.start_trace()
    t_prev = t0
    while True:
        now = time.perf_counter()
        if now - t_prev > 0.05:
            stalls.append((t_prev - t0, (now - t_prev) * 1e3))
        t_prev = now
        el = now - t0
        if env.trace and traced is None and el >= min(env.seconds,
                                                      env.trace_seconds):
            t_stop = time.perf_counter()
            env.stop_trace()
            traced = {}
        while i < n and due[i] <= el:
            submitted[i] = time.perf_counter() - t0
            rt.submit(env.users[users[i]], rid=i, t_arrive=t0 + due[i])
            i += 1
        if i >= n and el >= env.seconds:
            break
        if rt.queue or rt.in_flight:
            with env.span("bench/round"):
                for c in rt.step_once():
                    done[c.rid] = c
            depth.append(len(rt.queue))
        elif i < n:
            time.sleep(min(2e-4, max(0.0, due[i] - el)))
    if env.trace and traced is None:
        t_stop = time.perf_counter()
        env.stop_trace()
        traced = {}
    queue_at_close = len(rt.queue)
    t_close = time.perf_counter()
    worst = sorted(stalls, key=lambda s: -s[1])[:5]
    log(f"driver loop turns over 50 ms: {len(stalls)}; longest (s into "
        f"window, ms): {[(round(a, 3), round(b, 1)) for a, b in worst]}; "
        f"queue at close {queue_at_close}, deepest {max(depth or [0])}")
    while len(done) < n and time.perf_counter() - t_close < float(
            tr["drain_s"]):
        for c in rt.step_once():
            done[c.rid] = c
        if not (rt.queue or rt.in_flight):
            break
    env.last_answer()
    ok = sorted(r for r, c in done.items() if c.status == "ok")
    lat = np.full(n, np.inf)
    for r in ok:
        lat[r] = (done[r].record.t_done - (t0 + due[r])) * 1e3
    cs = [done[r] for r in ok]
    k = env.k
    completed = {
        "user": users[ok],
        "ids": np.stack([c.ids for c in cs]) if cs else np.zeros((0, k),
                                                                 np.int32),
        "scores": np.stack([c.scores for c in cs]) if cs else np.zeros(
            (0, k), np.float32),
        "n_eval": np.asarray([c.n_eval for c in cs], np.int64),
        "n_grad": np.asarray([c.n_grad for c in cs], np.int64),
        "n_iters": np.asarray([c.n_iters for c in cs], np.int64)}
    # the per-layer waits of a traced run count only what happened before
    # the profiler stopped: stopping it stalls the host for seconds
    late = (submitted - due) * 1e3
    out = {"completed": completed, "attempted": n, "missing": n - len(ok),
           "e2e": {"p95_ms": p95(lat)},
           "queue_ms": np.asarray([c.record.queue_ms for c in cs
                                   if c.record.t_admit < t_stop]),
           "late_ms": late[submitted + t0 < t_stop],
           "queue_at_close": queue_at_close,
           "queue_max": max(depth) if depth else 0,
           "latency_ms": lat}
    if traced is not None:
        out["traced"] = traced
    return out
