"""Device time of the programs that layers.json puts in `phase2`, in ms per
request answered in the traced window."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("layer_s", {}).get("phase2") if t else None
    if not s or not ctx["answered_in_window"]:
        return None
    return 1e3 * s / ctx["answered_in_window"]
