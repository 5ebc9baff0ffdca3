"""Benchmark orchestrator — one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (spec format) and writes a
machine-readable ``BENCH_<suite>.json`` per suite at the repo root so the
perf trajectory (QPS, recall, p50/p95, kernel throughput, gate status) is
tracked across PRs — CI uploads them as workflow artifacts. Every json
carries a ``provenance`` stamp (jax version, backend/device kind, git
sha, shared run timestamp) so numbers are comparable across machines.
``--compare OLD.json`` re-runs that suite and prints per-row speedup
factors, flagging rows that regressed >10%. Default runs the quick
profile (single dataset, reduced ef grid) so `python -m benchmarks.run`
finishes on the single-core container; --full sweeps everything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _parse_derived(derived: str) -> dict:
    """'k=v;k=v' pairs -> dict with floats where they parse (units like
    'ms'/'s' stripped); non k=v fragments are kept under 'notes'."""
    out, notes = {}, []
    for frag in derived.split(";"):
        if "=" not in frag:
            if frag:
                notes.append(frag)
            continue
        k, v = frag.split("=", 1)
        raw = v
        for unit in ("ms", "us", "s"):
            if v.endswith(unit) and v[: -len(unit)].replace(
                    ".", "").replace("-", "").replace("e", "").isdigit():
                v = v[: -len(unit)]
                break
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = raw
    if notes:
        out["notes"] = ";".join(notes)
    return out


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    try:
        us_f = float(us)
    except ValueError:
        us_f = None
    return {"name": name, "us_per_call": us_f,
            "derived": _parse_derived(derived), "raw": row}


def provenance(timestamp: float) -> dict:
    """Machine identity stamped into every BENCH json so the perf
    trajectory is comparable across machines and commits. ``timestamp`` is
    passed in (one stamp per run.py invocation, shared by all suites)."""
    import subprocess

    import jax

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT, text=True,
            capture_output=True, timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    dev = jax.devices()[0]
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "git_sha": sha,
        "unix_time": int(timestamp),
    }


def write_suite_json(suite: str, rows, ok: bool, quick: bool,
                     root: str = REPO_ROOT,
                     timestamp: float | None = None,
                     extra_provenance: dict | None = None) -> str:
    path = os.path.join(root, f"BENCH_{suite}.json")
    timestamp = time.time() if timestamp is None else timestamp
    prov = provenance(timestamp)
    if extra_provenance:
        prov.update(extra_provenance)
    payload = {
        "suite": suite,
        "ok": ok,
        "quick": quick,
        "unix_time": int(timestamp),
        "provenance": prov,
        "rows": [_parse_row(r) for r in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return path


def _is_tuning_row(name: str) -> bool:
    """One-time tuning sweeps (e.g. the ``autotune/engine_step`` row, a
    multi-second plan search that runs once and is cached) measure sweep
    cost, not serving performance — their run-to-run jitter is all compile
    scheduling. They are reported informationally, never as regressions."""
    return "autotune" in name


def compare_payloads(old: dict, new: dict, threshold: float = 0.9):
    """Per-row regression diff: rows matched by name, speedup =
    old_us / new_us (> 1 means the new run is faster). Returns (lines,
    regressed_names); rows slower by more than ``1 - threshold`` are
    flagged. Rows present in only one payload (a suite gained or lost a
    row between commits) are reported as added/removed, never treated as
    regressions. Gate-style rows without a latency (us=0) are skipped, and
    one-time tuning-sweep rows (``_is_tuning_row``) are excluded from
    regression matching — printed as informational only."""
    old_by_name = {r["name"]: r for r in old.get("rows", [])}
    lines, regressed = [], []
    for r in new.get("rows", []):
        o = old_by_name.get(r["name"])
        if o is None:
            lines.append(f"compare/{r['name']}: row added in new run")
            continue
        new_us, old_us = r.get("us_per_call"), o.get("us_per_call")
        if not old_us or not new_us:
            continue
        speedup = old_us / new_us
        if _is_tuning_row(r["name"]):
            lines.append(f"compare/{r['name']}: {old_us:.1f}us -> "
                         f"{new_us:.1f}us  (tuning sweep, informational "
                         "— excluded from regression gating)")
            continue
        flag = ""
        if speedup < threshold:
            flag = "  <-- REGRESSED"
            regressed.append(r["name"])
        lines.append(f"compare/{r['name']}: {old_us:.1f}us -> {new_us:.1f}us"
                     f"  speedup={speedup:.2f}x{flag}")
    only_old = sorted(set(old_by_name) - {r["name"]
                                          for r in new.get("rows", [])})
    for name in only_old:
        lines.append(f"compare/{name}: row removed in new run")
    return lines, regressed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_<suite>.json files")
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,fig5,table2,fig6,fig7,roofline,"
                         "kernels,graphbuild,serving,residency,chaos,"
                         "adaptive")
    ap.add_argument("--compare", default=None, metavar="OLD.json",
                    help="regression-diff mode: after the run, diff each "
                         "suite's rows against this prior BENCH json "
                         "(matched by suite name), print per-row speedup "
                         "factors, and flag rows that regressed >10%%")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the whole run "
                         "into this directory (view with TensorBoard or "
                         "Perfetto)")
    args = ap.parse_args()
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None
    old_payload = None
    if args.compare:
        with open(args.compare) as f:
            old_payload = json.load(f)
        if only is None and old_payload.get("suite"):
            only = {old_payload["suite"]}
    run_stamp = time.time()

    from benchmarks import (adaptive, chaos, fig4_recall_qps, fig5_alpha,
                            fig6_projection, fig7_begin, graph_build,
                            kernels_micro, residency, roofline, serving_load,
                            table2_breakdown)
    from repro.obs import profile_trace
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    # Suites whose rows were produced with telemetry attached stamp that
    # into their BENCH provenance, so trajectory diffs never compare a
    # traced p50 against an untraced one unknowingly.
    extra_prov = {"serving": {"tracing": True, "trace_sample": 16}}

    jobs = [
        ("fig4", lambda: fig4_recall_qps.run(
            datasets=("twitch",) if quick else ("twitch", "amazon"),
            ks=(1, 10) if quick else (1, 10, 50, 100), quick=quick,
            # multi-measure frontier: the registry-resolved mlp bundle
            # sweeps alongside deepfm (quick keeps the reduced ef grid)
            measures=("deepfm", "mlp"))),
        ("fig5", lambda: fig5_alpha.run(quick=quick)
         + fig5_alpha.run(quick=quick, measure="mlp")),
        ("table2", lambda: table2_breakdown.run(quick=quick)),
        ("fig6", lambda: fig6_projection.run(quick=quick)),
        ("fig7", lambda: fig7_begin.run(quick=quick)),
        ("kernels", lambda: kernels_micro.run(quick=quick)),
        ("graphbuild", lambda: graph_build.run(quick=quick)),
        ("serving", lambda: serving_load.run(quick=quick)),
        ("adaptive", lambda: adaptive.run(quick=quick)),
        ("residency", lambda: residency.run(quick=quick)),
        ("chaos", lambda: chaos.run(quick=quick)),
        ("roofline", lambda: roofline.run(mesh="single") + roofline.run(mesh="multi")),
    ]
    print("name,us_per_call,derived")
    failures = 0
    regressions = []
    with profile_trace(args.profile_dir):
        for name, fn in jobs:
            if only and name not in only:
                continue
            ok = True
            try:
                rows = list(fn())
                for row in rows:
                    print(row, flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                ok = False
                rows = [f"{name},0.00,ERROR={e!r}"]
                print(rows[0], flush=True)
                traceback.print_exc(file=sys.stderr)
            if not args.no_json:
                write_suite_json(name, rows, ok, quick, timestamp=run_stamp,
                                 extra_provenance=extra_prov.get(name))
            if old_payload is not None and old_payload.get("suite") == name:
                new_payload = {"rows": [_parse_row(r) for r in rows]}
                lines, regressed = compare_payloads(old_payload, new_payload)
                print(f"--- compare vs {args.compare} (suite={name}) ---",
                      flush=True)
                for line in lines:
                    print(line, flush=True)
                regressions += regressed
    if args.profile_dir:
        print(f"profiler trace -> {args.profile_dir}", flush=True)
    if regressions:
        print(f"REGRESSED ({len(regressions)}): {', '.join(regressions)}",
              flush=True)
    # non-zero exit only for genuine failures: a suite that crashed, or a
    # matched row >10% slower. Added/removed rows are informational.
    if failures or regressions:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
