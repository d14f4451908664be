"""Rows indexed per second by RangeSearchEngine.build (host clock, ending
when the graph is on the device)."""


def read(ctx):
    return ctx["rows"] / ctx["build_s"]
