"""Share of the traced window's busy device time (%) that no engine stage
scope holds: ops of other programs, and ops the stage map leaves
unscoped (bench/stages.py). It times no layer: it guards the coverage of
the seven ``<stage>_us.batch`` readers, near 0 where the map covers the
search, and a run whose share is over 1% says so in its log."""
from stages import unscoped_share


def read(ctx):
    return unscoped_share(ctx)
