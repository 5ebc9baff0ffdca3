"""Knee sweep of an open-loop cell: one set-up, then a window at each of
a few fixed offered rates, printing per rate the requests offered and
completed, the queue depth at the window's close and its maximum, and
the latency percentiles. The knee is the highest rate whose queue stays
bounded through the window; the cell's traffic file then takes 0.8 x the
knee as its ``rate_qps``.

    python3 bench/knee.py --workload deepfm-twitch.serve-poisson \\
        --rates 400,600,800,1000 --seconds 10
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain-s", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    if harness.start_jax("knee") is None:
        return 2
    cell = harness.find_cell(args.workload, ROOT)
    env = harness.Env(cell, args.seed, args.seconds, False, T_START)
    env.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        env.traffic = dict(cell.traffic, rate_qps=rate, drain_s=args.drain_s)
        out = cell.driver.run(env)
        lat = out["latency_ms"]
        fin = lat[np.isfinite(lat)]
        print(json.dumps({
            "rate_qps": rate, "offered": int(out["attempted"]),
            "completed": int(len(fin)),
            "queue_at_close": int(out["queue_at_close"]),
            "queue_max": int(out["queue_max"]),
            "p50_ms": float(np.percentile(fin, 50)) if len(fin) else None,
            "p95_ms": out["e2e"]["p95_ms"],
            "gen_late_p95_ms": float(np.percentile(
                out["late_ms"][np.isfinite(out["late_ms"])], 95))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
