"""Mean effective measure evaluations (the engine's n_eval counter) of
the queries completed in the window."""
import numpy as np


def read(ctx):
    n_eval = ctx.out["completed"]["n_eval"]
    if not len(n_eval):
        return None
    return float(np.mean(n_eval))
