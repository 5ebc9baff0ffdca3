"""Work a kernel needs for the search path, counted from the engine's
own counters and the configuration's widths, never from a kernel's block
shapes. So padding, fusion or skipping done lanes in a later change can
lower a kernel's time but never raise the work it is credited with, and
no share of a roofline can pass 100%.

Roles (the configuration's ``kernels`` map names the kernel of each):

- ``rank``: one call per active lane-step, over the B neighbour rows of
  the popped node: reads B item rows, the node's row and its gradient,
  writes B ranking scores. Per row: the offset (D), its dot with the
  gradient (2D) and its norm (2D).
- ``score``: one (item, user) pair per effective evaluation (``n_eval``):
  reads the item row and the user row, writes one score; the forward
  FLOPs of the measure.
- ``grad``: one pair per gradient (``n_grad``): reads both rows, writes
  the value and the gradient row; a forward pass and a backward pass to
  the input, 2 x the forward FLOPs (no weight gradients).

Rows are counted at their logical width, 4 bytes a float32.
"""
from __future__ import annotations

F32 = 4


def forward_flops(ref, m: dict) -> int:
    """FLOPs of one measure evaluation on one (item, user) pair."""
    return int(ref.forward_flops(m))


def role_work(role: str, ref, m: dict, counts: dict, degree: int) -> tuple:
    """(flops, bytes) that ``role``'s kernel needs for the counted work.

    counts: {'lane_steps': active lane-steps (sum of n_iters),
             'n_eval': effective evaluations, 'n_grad': gradients};
    degree: B, the width of a neighbour list."""
    d, dq = ref.item_dim(m), ref.query_dim(m)
    f = forward_flops(ref, m)
    if role == "rank":
        n = counts["lane_steps"]
        flops = n * (degree * 5 * d + 2 * d)
        nbytes = n * (degree * d * F32 + 2 * d * F32 + degree * F32)
    elif role == "score":
        n = counts["n_eval"]
        flops = n * f
        nbytes = n * ((d + dq) * F32 + F32)
    elif role == "grad":
        n = counts["n_grad"]
        flops = n * 2 * f
        nbytes = n * ((d + dq) * F32 + F32 + d * F32)
    else:
        raise ValueError(f"unknown kernel role {role!r}")
    return int(flops), int(nbytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound) — the larger of FLOPs over the bf16 peak and
    bytes over HBM bandwidth, and which of the two it is."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def measure_flops(ref, m: dict, n_eval: int, n_grad: int) -> int:
    """Measure FLOPs of the search: forward passes plus gradients."""
    f = forward_flops(ref, m)
    return int(n_eval * f + n_grad * 2 * f)


def roofline_share(ctx, kernel: str):
    """Percent of its roofline that ``kernel`` reached over the traced
    window: the least time for the counted work over the kernel's summed
    device time. None where the cell runs no such kernel or nothing was
    traced."""
    roles = {v: k for k, v in ctx.config["kernels"].items()}
    traced = ctx.out.get("traced")
    if (kernel not in roles or ctx.trace is None or not traced
            or ctx.peak is None):
        return None
    t = ctx.trace["op_s"].get(kernel, 0.0)
    if t <= 0.0:
        return None
    flops, nbytes = role_work(roles[kernel], ctx.ref, ctx.config["measure"],
                              traced, ctx.degree)
    least, _ = least_time_s(flops, nbytes, ctx.peak)
    return 100.0 * least / t
