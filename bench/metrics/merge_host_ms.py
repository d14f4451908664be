"""Device-idle time inside range.merge, in ms per range.step: the one
fetch of phase 1's and phase 2's results, the host merge and the upload."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_step(ctx, "range.merge")
