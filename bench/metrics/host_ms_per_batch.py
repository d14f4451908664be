"""Time inside the harness's bench.step spans when the device was idle, in
ms per step: the server's host path (batching, host compaction, result
assembly) and the round trips it waits on."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["span_count"].get("bench.step"):
        return None
    return 1e3 * t["span_idle_s"]["bench.step"] / t["span_count"]["bench.step"]
