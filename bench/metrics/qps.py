"""Requests answered in the window over the window's seconds."""


def read(ctx):
    return ctx["answered_in_window"] / ctx["window_s"]
