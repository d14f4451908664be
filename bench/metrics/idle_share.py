"""1 - (union of device-busy intervals) / (traced window)."""


def read(ctx):
    t = ctx["trace"]
    return t.get("idle_share") if t else None
