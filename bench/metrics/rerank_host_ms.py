"""Device-idle time inside range.rerank, in ms per range.step: the host
side of the int8 guard-band rerank (fetch, upper bounds, pair compaction,
upload) around its exact-distance program."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_step(ctx, "range.rerank")
