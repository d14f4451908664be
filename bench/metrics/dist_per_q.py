"""Distance computations per request served in the traced window, from the
server's n_dist counter (as each range.respond span carries it) over the
requests of the window's range.step spans."""
from bench import spans


def read(ctx):
    red = spans.reading(ctx)
    if red is None or not red["counters"]["served"]:
        return None
    return red["counters"]["n_dist"] / red["counters"]["served"]
