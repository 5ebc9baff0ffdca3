"""Device time per engine stage, for the ``<stage>_us.batch`` and
``unscoped_share.batch`` readers.

A traced batch run reduces its trace to device seconds per (module,
instruction) (``trace_reduce``'s ``instr_s``) and, once the window has
closed, maps the compiled search's instructions to the engine's stage
scopes (``out["stages"]``: (module, instruction) -> stage, from
``repro.obs.profile.stage_map``). Summed through the map, the first is
the window's device time per stage; an op of another program, or one the
map leaves unscoped, is ``unscoped``.

``unscoped_share.batch`` times no layer: it guards the coverage of the
seven stage readers. Where it reads over ``COVERAGE_PCT``, the stage
times leave out that much of the busy time, and the run's log says so.
"""
from __future__ import annotations

import collections

from harness import log

UNSCOPED = "unscoped"
# unscoped share (%) above which the stage readers cover too little
COVERAGE_PCT = 1.0


def stage_seconds(instr_s: dict, stage_of: dict) -> dict:
    """{stage: device seconds} from {(module, instruction): seconds} and
    {(module, instruction): stage}."""
    out = collections.Counter()
    for key, sec in instr_s.items():
        out[stage_of.get(key, UNSCOPED)] += sec
    return dict(out)


def _joined(ctx):
    stage_of = ctx.out.get("stages")
    if ctx.trace is None or not stage_of or "instr_s" not in ctx.trace:
        return None
    return stage_seconds(ctx.trace["instr_s"], stage_of)


def stage_us(ctx, stage: str):
    """Device µs of ``stage`` per engine step of the traced window (the
    steps of ``step_us.batch``); None where the run was not traced or
    the compiled search has no such stage."""
    joined = _joined(ctx)
    traced = ctx.out.get("traced")
    if (joined is None or not traced or not traced.get("steps")
            or stage not in ctx.out["stages"].values()):
        return None
    return 1e6 * joined.get(stage, 0.0) / traced["steps"]


def unscoped_share(ctx):
    """Share (%) of the window's busy device time that no stage holds;
    logged where it is over ``COVERAGE_PCT``."""
    joined = _joined(ctx)
    if joined is None or ctx.trace["busy_s"] <= 0:
        return None
    share = 100.0 * joined.get(UNSCOPED, 0.0) / ctx.trace["busy_s"]
    if share > COVERAGE_PCT:
        log(f"stage readers: {share:.3f}% of the busy device time is in "
            f"no engine stage (over {COVERAGE_PCT}%): the stage map does "
            f"not cover the program that ran")
    return share
