"""Measure FLOPs done in the traced window over the window's seconds
times the chip's bf16 peak (%): n_eval forward passes and n_grad
gradients (2 x forward) from the engine's counters, the forward FLOPs
from the configuration's widths."""
from work import measure_flops


def read(ctx):
    traced = ctx.out.get("traced")
    if (ctx.trace is None or ctx.peak is None or not traced
            or "n_eval" not in traced):
        return None
    flops = measure_flops(ctx.ref, ctx.config["measure"], traced["n_eval"],
                          traced["n_grad"])
    return 100.0 * flops / (ctx.trace["window_s"]
                            * ctx.peak["bf16_flops_per_s"])
