"""Readings that set the limits of ``correct``, on the chip, for one cell
at its own size, in one process (one set-up):

- the program's readings: for each of --seeds, a short window of the
  cell's own traffic, then the comparison of bench/harness.py;
- the control's readings, on the first --control-seeds of them: the plain
  reference computed in bfloat16 (one MXU pass, the precision below the
  configuration's float32) put in the program's place, its scores in
  every completed answer, through the same comparison;
- each fault of FAULTS planted under the timed path, on the first
  --control-seeds seeds, through the same comparison;
- with --ef, the program's readings at other pool sizes (the cap on
  expansions 4 x ef, the program's default), to see how recall moves.

    python3 bench/control.py --workload deepfm-twitch.batch \\
        --seeds 11,12,13 --control-seeds 3 --seconds 3 --ef 32,128

Prints one JSON line per reading and a summary (the largest program
reading, the smallest control and fault readings of each number). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def bf16_answers(env, out: dict, ref) -> dict:
    """The control in the program's place: the driver's outcome with the
    score of every completed answer recomputed by the plain reference in
    bfloat16."""
    done = dict(out["completed"])
    done["scores"] = ref.pair_scores(env.weights, done["ids"],
                                     env.users[done["user"]],
                                     precision="bfloat16")
    return dict(out, completed=done)


def _rank_reversed(engine):
    rank = engine.rank

    def stage(x, grad, nvecs, valid, *rest):
        return rank(x, -grad, nvecs, valid, *rest)
    return dataclasses.replace(engine, rank=stage)


def _grad_zeroed(engine):
    import jax.numpy as jnp
    grad = engine.grad

    def stage(params, x, q):
        value, g = grad(params, x, q)
        return value, jnp.zeros_like(g)
    return dataclasses.replace(engine, grad=stage)


# faults of the search's own stages, planted on the engine that set-up
# built: the rank stage handed the reversed gradient (it keeps the
# neighbours that point away from it), and the grad stage returning zeros
FAULTS = {"rank_reversed": _rank_reversed, "grad_zeroed": _grad_zeroed}


def reading(env, ref, out, **tags) -> dict:
    import harness
    cmp = harness.compare(env, out, ref)
    row = {**tags, "seed": env.seed,
           "completed": len(out["completed"]["n_iters"]),
           "recall": cmp["recall"],
           "evals_per_query": float(out["completed"]["n_eval"].mean()),
           **{k: c["value"] for k, c in cmp["checks"].items()}}
    print(json.dumps(row), flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the program's readings")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--ef", default="",
                    help="comma-separated other pool sizes to read recall at")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    if harness.start_jax("control") is None:
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.find_cell(args.workload, ROOT)
    env = harness.Env(cell, seeds[0], args.seconds, False, T_START)
    env.setup()
    ref = harness.Reference(cell, env.index_path)
    rows = []
    for i, seed in enumerate(seeds):
        if seed != env.seed:
            env.set_seed(seed)
        out = cell.driver.run(env)
        rows.append(reading(env, ref, out, kind="program"))
        if i < args.control_seeds:
            rows.append(reading(env, ref, bf16_answers(env, out, ref),
                                kind="control"))
    for fault, plant in FAULTS.items():
        for seed in seeds[:args.control_seeds]:
            env.set_seed(seed)
            env.engine = plant(env.engine)
            rows.append(reading(env, ref, cell.driver.run(env), kind=fault))
    search = dict(cell.config["search"])
    for ef in [int(e) for e in args.ef.split(",") if e]:
        cell.config["search"] = dict(search, ef=ef, max_iters=4 * ef)
        env.set_seed(seeds[0])
        rows.append(reading(env, ref, cell.driver.run(env), kind="program",
                            ef=ef))
    cell.config["search"] = search
    summary = {"workload": args.workload, "seeds": len(seeds)}
    for kind in ["program", "control", *FAULTS]:
        got = [r for r in rows if r["kind"] == kind and "ef" not in r]
        pick = max if kind == "program" else min
        for num in ("score_gap", "search_miss", "bad_rows", "missing"):
            if got:
                summary[f"{kind}.{num}"] = pick(r[num] for r in got)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
