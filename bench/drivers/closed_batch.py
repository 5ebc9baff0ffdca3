"""Closed loop of whole batches: back-to-back ``ExpansionEngine.search``
calls, each on ``batch`` users drawn uniformly from the deployment's
users, with one host fetch of the results per batch (the way ``serve
--runtime oneshot`` runs them). The next batch is sent when the previous
one has come back.

With ``--trace 1``, once the window's last answer is in, the stage map
of the compiled search at the batch's shapes (``repro.obs.profile.
stage_map``, (module, instruction) -> engine stage) goes into
``out["stages"]`` for the per-stage readers (``bench/stages.py``).

Traffic parameters: ``batch``.
"""
from __future__ import annotations

import time

import numpy as np

from harness import log


def run(env) -> dict:
    import jax
    import jax.numpy as jnp
    B = int(env.traffic["batch"])
    n_users = len(env.users)
    rng = env.rng(1)
    entries = jnp.full((B,), env.entry, jnp.int32)

    def batch(users):
        q = jnp.asarray(env.users[users])
        res = env.engine.search(env.params, env.store, env.neighbors, q,
                                entries)
        return jax.device_get(res)

    batch(env.rng(0).integers(0, n_users, B))            # compiles
    rows = []
    traced = None
    t0 = env.open_window()
    if env.trace:
        env.start_trace()
        t_trace = time.perf_counter()
    while True:
        now = time.perf_counter()
        if env.trace and traced is None and now - t0 >= min(
                env.seconds, env.trace_seconds):
            env.stop_trace()
            traced = {"batches": len(rows), "host_s": now - t_trace}
        if now - t0 >= env.seconds:
            break
        users = rng.integers(0, n_users, B)
        with env.span("bench/batch"):
            res = batch(users)
        rows.append((users, res))
    t1 = time.perf_counter()
    env.last_answer()
    if env.trace and traced is None:
        env.stop_trace()
        traced = {"batches": len(rows), "host_s": t1 - t_trace}

    done = {"user": np.concatenate([r[0] for r in rows]),
            "ids": np.concatenate([r[1].ids for r in rows]),
            "scores": np.concatenate([r[1].scores for r in rows]),
            "n_eval": np.concatenate([r[1].n_eval for r in rows]),
            "n_grad": np.concatenate([r[1].n_grad for r in rows]),
            "n_iters": np.concatenate([r[1].n_iters for r in rows])}
    out = {"completed": done, "attempted": len(done["user"]), "missing": 0,
           "e2e": {"qps": len(done["user"]) / (t1 - t0)},
           "batches": [{"n_iters": r[1].n_iters, "n_eval": r[1].n_eval,
                        "n_grad": r[1].n_grad} for r in rows]}
    if traced is not None:
        tb = out["batches"][:traced["batches"]]
        traced.update(
            steps=int(sum(int(b["n_iters"].max()) for b in tb)),
            lane_steps=int(sum(int(b["n_iters"].sum()) for b in tb)),
            n_eval=int(sum(int(b["n_eval"].sum()) for b in tb)),
            n_grad=int(sum(int(b["n_grad"].sum()) for b in tb)))
        out["traced"] = traced
        t_map = time.perf_counter()
        out["stages"] = stage_map(env, B, entries)
        log(f"stage map of the compiled search: {len(out['stages'])} "
            f"instructions in {time.perf_counter() - t_map:.3f} s, after "
            f"the window")
    return out


def stage_map(env, B: int, entries) -> dict:
    """(module, instruction) -> engine stage of the program a batch runs,
    read back through JAX's compile caches (the warm-up compiled it)."""
    import jax.numpy as jnp
    from repro.obs.profile import stage_map as hlo_stage_map
    q = jnp.asarray(env.users[:B])
    text = env.engine.compiled_text(env.params, env.store, env.neighbors, q,
                                    entries)
    return {k: v.stage for k, v in hlo_stage_map(text).items()}
