"""Device microseconds of the engine's ``measure`` stage (its ``repro_measure``
scope) per engine step of the traced window (bench/stages.py)."""
from stages import stage_us


def read(ctx):
    return stage_us(ctx, "measure")
