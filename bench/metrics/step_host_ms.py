"""Device-idle time inside the server's range.step spans, in ms per step:
the host path of a lockstep step seen from inside the program."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_step(ctx, "range.step")
