"""Greedy phase-2 lane utilization over the traced window: expansion rounds
of the request-carrying phase-2 lanes over dispatched lanes (pow2 padding
included) x the slowest lane's rounds, from the server's p2_lane_rounds and
p2_slot_rounds counters as each range.respond span carries them."""
from bench import spans


def read(ctx):
    red = spans.reading(ctx)
    if red is None or not red["counters"]["p2_slot_rounds"]:
        return None
    c = red["counters"]
    return c["p2_lane_rounds"] / c["p2_slot_rounds"]
