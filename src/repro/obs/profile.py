"""Opt-in JAX profiler hooks, and the two joins that tie the program's own
labels to a device trace.

Both hooks are free when unused:

- :func:`profile_trace` wraps ``jax.profiler.trace`` for a whole run
  (``--profile-dir`` on serve.py / benchmarks.run); a ``None`` dir is a
  no-op context. It stamps the session's clock anchor (below) and writes
  it beside the trace.
- :func:`annotate` names host-side stage boundaries with
  ``jax.profiler.TraceAnnotation`` so device timelines line up with
  the serving runtime's phases (``repro/tick``, ``repro/reset``,
  ``repro/search``). Inside jitted code we use ``jax.named_scope``
  instead (trace-time metadata, zero runtime cost) — see
  core/engine.py.

The joins:

- :func:`stage_map` reads compiled HLO text (``ExpansionEngine.
  compiled_text``) and places each instruction in the engine stage whose
  ``repro_<stage>`` scope its ``op_name`` carries. A device trace names
  ops by instruction, so device time per stage is the trace's time per
  (module, instruction) summed through this map.
- :func:`clock_anchor` / :func:`trace_clock` map host ``perf_counter``
  seconds (the clock of ``obs.Tracer`` spans) onto a profiler session's
  clock, through one ``repro/clock`` annotation entered at a kept
  ``perf_counter`` value.

A trace that was asked for and cannot start raises: a run that was meant
to be measured must not pass for one that was. :func:`annotate` still
degrades to a null context, since it only names spans.
"""
from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import time
from typing import Callable, Dict, NamedTuple, Optional, Tuple

ANCHOR = "repro/clock"
ANCHOR_FILE = "repro_clock.json"
UNSCOPED = "unscoped"


@contextlib.contextmanager
def profile_trace(profile_dir: Optional[str]):
    """Capture a jax profiler trace into ``profile_dir`` (viewable with
    TensorBoard / Perfetto), with the session's clock anchor written to
    ``<profile_dir>/repro_clock.json`` so spans exported by ``--trace-out``
    join the trace offline (:func:`trace_clock`). ``None`` disables; a
    profiler that cannot start raises."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        t = clock_anchor()
        with open(os.path.join(profile_dir, ANCHOR_FILE), "w") as f:
            json.dump({"annotation": ANCHOR, "perf_counter": t}, f)
        yield


def annotate(name: str):
    """Named host-span for the device timeline; null context if the
    profiler annotation API is unavailable."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001
        return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# host clock -> profiler clock
# ---------------------------------------------------------------------------

def clock_anchor() -> float:
    """Enter and leave one ``repro/clock`` annotation in the running
    profiler session; returns the ``perf_counter`` value at which it was
    entered (the middle of two reads around the entry). Call once per
    session."""
    import jax
    ann = jax.profiler.TraceAnnotation(ANCHOR)
    t0 = time.perf_counter()
    with ann:
        t1 = time.perf_counter()
    return 0.5 * (t0 + t1)


def trace_clock(profile, anchor_s: float) -> Callable[[float], float]:
    """``t -> ns`` from host ``perf_counter`` seconds to the clock of
    ``profile`` (a ``jax.profiler.ProfileData``), given the value
    :func:`clock_anchor` returned in that session."""
    starts = [ev.start_ns for plane in profile.planes
              for line in plane.lines for ev in line.events
              if ev.name == ANCHOR]
    if len(starts) != 1:
        raise ValueError(f"expected one '{ANCHOR}' annotation in the "
                         f"profile, found {len(starts)}")
    t0 = float(starts[0])
    return lambda t: t0 + (t - anchor_s) * 1e9


# ---------------------------------------------------------------------------
# compiled HLO -> engine stage per instruction
# ---------------------------------------------------------------------------

class Stage(NamedTuple):
    stage: str      # a stage name, or UNSCOPED
    mixed: bool     # a fusion whose body holds ops of another stage too


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: str
    calls: Tuple[str, ...]
    root: bool


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|condition|body|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")


def _opcode(rhs: str) -> str:
    """``f32[8]{0} fusion(...)`` or ``(f32[], s32[]) while(...)`` ->
    the opcode."""
    rest = ""
    if rhs.startswith("("):                 # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rhs[i + 1:]
                break
    elif " " in rhs:
        rest = rhs.split(None, 1)[1]
    return rest.strip().split("(", 1)[0]


def _parse(hlo_text: str):
    module, comps, callers = "", collections.OrderedDict(), {}
    cur = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        if line and not line[0].isspace() and line.rstrip().endswith("{") \
                and "->" in line:
            head = line.split(None, 1)
            if head[0] == "ENTRY":
                head = head[1].split(None, 1)
            cur = head[0].lstrip("%")
            comps[cur] = []
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line) if cur is not None else None
        if not m:
            continue
        rhs = m.group(3)
        op = _OP_NAME.search(rhs)
        calls = _CALLS.findall(rhs)
        for lst in _CALL_LISTS.findall(rhs):
            calls += [c.strip().lstrip("%") for c in lst.split(",")
                      if c.strip()]
        ins = _Instr(m.group(2), _opcode(rhs), op.group(1) if op else "",
                     tuple(calls), bool(m.group(1)))
        comps[cur].append(ins)
        for c in ins.calls:
            callers.setdefault(c, ins)
    return module, comps, callers


def stage_map(hlo_text: str, prefix: str = "repro_"
              ) -> Dict[Tuple[str, str], Stage]:
    """``{(module, instruction): Stage}`` for every instruction of one
    compiled HLO module.

    - An instruction's stage is the innermost ``<prefix><stage>`` scope in
      its ``op_name``.
    - A fusion with no scope of its own takes the stage of its fused
      computation's root; if the root has none, the stage found most in
      that computation. A fusion whose body holds more than one stage keeps
      its own (or its root's) stage and is flagged ``mixed``.
    - Any other instruction with no scope (parameters, tuples, copies the
      compiler inserted) takes the stage of the instruction that calls its
      computation: the loop's, inside a ``while`` body or condition.
    - An instruction with no scope anywhere is ``unscoped``.
    """
    module, comps, callers = _parse(hlo_text)

    def own(ins: _Instr) -> Optional[str]:
        found = None
        for part in ins.op_name.split("/"):
            if part.startswith(prefix) and len(part) > len(prefix):
                found = part[len(prefix):]
        return found

    body_memo: Dict[str, collections.Counter] = {}

    def body_stages(comp: str) -> collections.Counter:
        """Stages of a computation's ops, and of what they call."""
        if comp not in body_memo:
            cnt = body_memo[comp] = collections.Counter()
            for ins in comps.get(comp, ()):
                s = own(ins)
                if s is not None:
                    cnt[s] += 1
                for c in ins.calls:
                    cnt.update(body_stages(c))
        return body_memo[comp]

    def fused_stage(ins: _Instr) -> Optional[str]:
        """A fusion's stage from its body: the root's, else the most
        common."""
        for c in ins.calls:
            root = next((r for r in comps.get(c, ()) if r.root), None)
            if root is not None and own(root) is not None:
                return own(root)
            cnt = body_stages(c)
            if cnt:
                return cnt.most_common(1)[0][0]
        return None

    comp_of = {ins.name: comp for comp, instrs in comps.items()
               for ins in instrs}
    memo: Dict[str, Stage] = {}

    def resolve(ins: _Instr) -> Stage:
        if ins.name in memo:
            return memo[ins.name]
        memo[ins.name] = Stage(UNSCOPED, False)     # cycle guard
        s = own(ins)
        fusion = ins.opcode == "fusion"
        if s is None and fusion:
            s = fused_stage(ins)
        if s is None and comp_of[ins.name] in callers:
            s = resolve(callers[comp_of[ins.name]]).stage
        s = s or UNSCOPED
        mixed = False
        if fusion:
            inner = set()
            for c in ins.calls:
                inner |= set(body_stages(c))
            mixed = bool(inner - {s})
        memo[ins.name] = Stage(s, mixed)
        return memo[ins.name]

    return {(module, ins.name): resolve(ins)
            for instrs in comps.values() for ins in instrs}
