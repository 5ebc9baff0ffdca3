"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read:

- the traced window: the host annotation ``bench/window`` the harness
  opens around the traced work;
- device busy intervals: the union of the ops on each device's
  ``XLA Ops`` line, clipped to the window;
- device time per op, keyed by the HLO instruction's name without its
  numeric suffix (a Pallas kernel's ``name``, e.g. ``neighbor_rank``);
- device time per (module, instruction), the module read from the
  device's ``XLA Modules`` line, which a stage map of the compiled
  program (``obs.profile.stage_map``) prices by engine stage; a
  container (a loop) is credited its own time, its span less the ops
  inside it, so these times add up to the busy time;
- the idle gaps between busy intervals, each labelled by the innermost
  host event on the window's thread that covers its middle.

Times in the trace are nanoseconds on one clock for host and device.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW = "bench/window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that contain other ops of the same line
CONTAINERS = ("while", "conditional", "call")
# gaps shorter than this are summed under one label
SMALL_GAP_NS = 10_000

_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)*(?:\s|=|$)")
_INSTR = re.compile(r"^%?([^\s=]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return paths[0]


def op_base(name: str) -> str:
    """'%neighbor_rank.6 = f32[...] custom-call(...)' -> 'neighbor_rank'."""
    m = _NAME.match(name.strip())
    return m.group(1) if m else name.split()[0]


def op_instr(name: str) -> str:
    """'%fusion.117 = f32[...] fusion(...)' -> 'fusion.117'."""
    return _INSTR.match(name.strip()).group(1)


def op_label(name: str) -> str:
    """A short readable op name: instruction, result type, operands,
    without layouts, at most 120 characters."""
    text = name.lstrip("%")
    prev = None
    while prev != text:
        prev, text = text, _LAYOUT.sub("", text)
    text = re.sub(r",\s*(calls|kind|metadata|backend_config)=.*$", "", text)
    return text[:120]


def union(intervals):
    """Merge (start, end) pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def _module_at(mods, starts, t):
    """The module whose execution on the device covers time ``t``, or
    ''; ``mods`` is a sorted list of (start, end, module)."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else ""


def _own_times(spans):
    """Each (start, end, is_container) span's own time: its length less
    the spans nested directly inside it, where only a container holds
    others. A container whose children overlap one another gets a
    negative own time, left as it is (``reduce_profile`` reports the
    sum as ``overlap_s``)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1], not spans[i][2]))
    own = [e - s for s, e, _ in spans]
    stack = []
    for i in order:
        s, e, cont = spans[i]
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][1]:
            own[stack[-1]] -= e - s
        if cont:
            stack.append(i)
    return own


def _label_gaps(gaps, host):
    """Label each (start, end) gap by the shortest host event covering
    its middle; ``host`` is a list of (start, end, name) sorted by
    start."""
    starts = [h[0] for h in host]
    labels = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        best = None
        i = bisect.bisect_right(starts, mid) - 1
        for j in range(i, max(-1, i - 400), -1):
            hs, he, hn = host[j]
            if he >= mid and (best is None or he - hs < best[1] - best[0]):
                best = (hs, he, hn)
        labels.append(best[2] if best else "none")
    return labels


def reduce_trace(path: str, devices: int = 1) -> dict:
    """The reduction of one trace file; see ``reduce_profile``."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), devices)


def reduce_profile(pd, devices: int = 1) -> dict:
    """The reduction of one ``jax.profiler.ProfileData``; see the module
    docstring.

    Returns {'window_s', 'busy_s' (mean over the devices), 'op_s' (op
    base name -> device seconds, summed over devices, containers left
    out), 'instr_s' ((module, instruction) -> device seconds, the mean
    over the devices, a container's own time only), 'overlap_s' (the
    mean over the devices of the time by which a container's children
    overlap one another, 0 where they tile it), 'device_ops' (top 10
    [label, seconds]), 'idle_gaps' (top 10 [host label, seconds]),
    'n_device_events'}."""
    window = None
    host = []
    dev_lines = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            lines = {ln.name: ln for ln in plane.lines}
            if idx < devices and OPS_LINE in lines:
                mods = sorted(
                    (s, s + d, n.split("(")[0])
                    for n, s, d in _events(lines[MODULES_LINE])
                ) if MODULES_LINE in lines else []
                dev_lines.append((lines[OPS_LINE], mods))
            continue
        for line in plane.lines:
            evs = list(_events(line))
            marks = [e for e in evs if e[0] == WINDOW]
            if marks:
                window = (marks[0][1], marks[-1][1] + marks[-1][2])
                host = sorted((s, s + d, n) for n, s, d in evs
                              if n != WINDOW)
    if window is None:
        raise ValueError(f"no '{WINDOW}' annotation in the trace")
    w0, w1 = window
    op_s = collections.Counter()
    instr_s = collections.Counter()
    overlap = 0.0
    label_s = collections.Counter()
    busy_total = 0.0
    gaps_all = []
    n_events = 0
    for line, mods in dev_lines:
        starts = [m[0] for m in mods]
        spans, names = [], []
        for name, s, d in _events(line):
            e = s + d
            if e <= w0 or s >= w1:
                continue
            n_events += 1
            spans.append((max(s, w0), min(e, w1),
                          op_base(name) in CONTAINERS))
            names.append(name)
        for (cs, ce, cont), own, name in zip(spans, _own_times(spans), names):
            key = (_module_at(mods, starts, cs), op_instr(name))
            if cont:
                instr_s[key] += own * 1e-9
                overlap -= min(own, 0.0) * 1e-9
                continue
            op_s[op_base(name)] += (ce - cs) * 1e-9
            instr_s[key] += (ce - cs) * 1e-9
            label_s[op_label(name)] += (ce - cs) * 1e-9
        busy = union([(cs, ce) for cs, ce, _ in spans])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    n_dev = max(1, len(dev_lines))
    big = [g for g in gaps_all if g[1] - g[0] >= SMALL_GAP_NS]
    idle = collections.Counter()
    for (s, e), lab in zip(big, _label_gaps(big, host)):
        idle[lab] += (e - s) * 1e-9
    small = sum(e - s for s, e in gaps_all if e - s < SMALL_GAP_NS) * 1e-9
    if small:
        idle[f"gaps under {SMALL_GAP_NS // 1000} us"] += small
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "op_s": dict(op_s),
        "instr_s": {k: v / n_dev for k, v in instr_s.items()},
        "overlap_s": overlap / n_dev,
        "device_ops": [[k, v] for k, v in label_s.most_common(10)],
        "idle_gaps": [[k, v / n_dev] for k, v in idle.most_common(10)],
        "n_device_events": n_events,
    }
