"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run: set-up (the deployment's index, built once per
checkout under bench/.cache and loaded after that; the measure's weights;
compiles, from JAX's persistent cache after the first run), warm-up of the
cell's own shapes, a measured window of --seconds, then the comparison
with the plain reference. The last line of stdout is one JSON object
(correct, attempted, failed, metrics, device, [breakdown], checks); the
numbers compared, each beside its limit, are also the last lines of
stderr. With --trace 1 the first seconds of the window run under the JAX
profiler and the metrics are the cell's per-layer metrics.

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def finite(x):
    """The result with every non-finite float as null (strict JSON)."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import harness
    if harness.start_jax() is None:
        return 2
    result, lines = harness.run_cell(args.workload, args.seed, args.seconds,
                                     bool(args.trace), T_START, root=ROOT)
    for line in lines:
        print(f"[bench] {line}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
