"""Device-idle time inside range.respond, in ms per range.step: the fetch
of the results, Response assembly and the counter updates."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_step(ctx, "range.respond")
