"""95th percentile (nearest rank) of a completed request's wait in the
runtime's queue, admit time minus due time, from the runtime's
RequestRecord."""
import math

import numpy as np


def read(ctx):
    q = np.sort(np.asarray(ctx.out.get("queue_ms", []), np.float64))
    if not len(q):
        return None
    return float(q[max(0, math.ceil(0.95 * len(q)) - 1)])
