"""Size-weighted average precision of the answers that came in the window
against the reference: a whole number of passes over the pool."""


def read(ctx):
    return ctx["ap"]
