"""Share of its roofline that the ``deepfm_score`` kernel reached (%): the
least time for the work the engine's counters credit it with, over the
kernel's device time in the trace (bench/work.py)."""
from work import roofline_share


def read(ctx):
    return roofline_share(ctx, "deepfm_score")
