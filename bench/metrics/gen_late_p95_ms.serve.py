"""95th percentile (nearest rank) of how late the load generator
submitted a request: submit time minus due time, on the host clock."""
import math

import numpy as np


def read(ctx):
    late = np.asarray(ctx.out.get("late_ms", []), np.float64)
    late = np.sort(late[np.isfinite(late)])
    if not len(late):
        return None
    return float(late[max(0, math.ceil(0.95 * len(late)) - 1)])
