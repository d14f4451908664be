"""Device-idle time inside range.compact, in ms per range.step: the eager
beam-result extraction, the phase-2 trigger's sync, lane selection and
padding, and the gather of the survivors' beam states."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_step(ctx, "range.compact")
