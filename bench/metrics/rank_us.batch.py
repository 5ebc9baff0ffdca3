"""Device microseconds of the engine's ``rank`` stage (its ``repro_rank``
scope) per engine step of the traced window (bench/stages.py)."""
from stages import stage_us


def read(ctx):
    return stage_us(ctx, "rank")
