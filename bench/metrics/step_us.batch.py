"""Microseconds of the traced window's wall time per engine step: a
batch runs as many steps as its longest search (the largest n_iters)."""


def read(ctx):
    traced = ctx.out.get("traced")
    if not traced or not traced.get("steps"):
        return None
    return 1e6 * traced["host_s"] / traced["steps"]
