"""The program's own spans and counters in a profiler trace.

The server marks each lockstep step with ``jax.profiler.TraceAnnotation``
host spans, on the profiler's clock: ``range.step`` and, inside it and in
order, ``range.batch``, ``range.phase1``, ``range.compact``,
``range.phase2``, ``range.merge``, ``range.rerank`` and ``range.respond``.
``range.respond`` carries the step's work counts as arguments (``n_dist``,
``n_visited``, ``p2_lane_rounds``, ``p2_slot_rounds``), ``range.step`` its
batch size ``n``. The jitted programs name their device ops with
``jax.named_scope`` (``range.phase1``, ``range.phase2``, ``range.rerank``),
which the TPU trace keeps in each op's ``tf_op`` stat.

``load`` reads what ``bench.trace.load`` reads plus those spans;
``reduce`` puts every idle piece of the ``bench.window`` span down to the
innermost span that holds it, and sums the counters and the device time per
scope (``scope_s``, which no metric reads). The metric readers take their
numbers from ``reading(ctx)``; a trace of a program without these spans
gives ``None`` there.

    python3 -m bench.spans [trace dir]

prints the reduction of the newest trace under the directory (default: the
harness's).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

from . import trace

PREFIX = "range."
STEP = "range.step"
CHILDREN = ("range.batch", "range.phase1", "range.compact", "range.phase2",
            "range.merge", "range.rerank", "range.respond")
COUNTERS = ("n_dist", "n_visited", "p2_lane_rounds", "p2_slot_rounds")
_SCOPE = re.compile(r"(?:^|/)(range\.[A-Za-z0-9_]+)")


def _newest(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(log_dir: str) -> dict:
    """``bench.trace.load``'s events, with the program's host spans added
    to ``host`` as ``[start, duration, name, args]``, and for each chip
    ``scoped``: ``[start, duration, scope]`` for every device op under a
    ``range.*`` named scope. Times in ns."""
    from jax.profiler import ProfileData
    ev = trace.load(log_dir)
    path = _newest(log_dir)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ev["host"].extend(
                    [e.start_ns, e.duration_ns, e.name, dict(e.stats)]
                    for e in line.events if e.name.startswith(PREFIX))
    scoped = _scoped_ops(path)
    for chip in ev["chips"]:
        chip["scoped"] = scoped.get(chip["plane"], [])
    return ev


def _xspace_class():
    """A protobuf class for the part of the profiler's ``XSpace`` that
    holds the device ops' stats (``tsl/profiler/protobuf/xplane.proto``);
    ``ProfileData`` does not expose the stats of an op's metadata."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(name, fields):
        m = fd.message_type.add(name=name)
        for fname, num, typ, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=(F.LABEL_REPEATED if tname
                                   else F.LABEL_OPTIONAL))
            if tname:
                f.type_name = ".bench_xplane." + tname
        return m

    msg("XStat", [("metadata_id", 1, F.TYPE_INT64, None),
                  ("str_value", 5, F.TYPE_STRING, None)])
    msg("XEvent", [("metadata_id", 1, F.TYPE_INT64, None),
                   ("offset_ps", 2, F.TYPE_INT64, None),
                   ("duration_ps", 3, F.TYPE_INT64, None)])
    msg("XLine", [("name", 2, F.TYPE_STRING, None),
                  ("timestamp_ns", 3, F.TYPE_INT64, None),
                  ("events", 4, F.TYPE_MESSAGE, "XEvent")])
    msg("XEventMetadata", [("id", 1, F.TYPE_INT64, None),
                           ("stats", 5, F.TYPE_MESSAGE, "XStat")])
    msg("XStatMetadata", [("id", 1, F.TYPE_INT64, None),
                          ("name", 2, F.TYPE_STRING, None)])
    plane = msg("XPlane", [("name", 2, F.TYPE_STRING, None),
                           ("lines", 3, F.TYPE_MESSAGE, "XLine")])
    for fname, num, value in (("event_metadata", 4, "XEventMetadata"),
                              ("stat_metadata", 5, "XStatMetadata")):
        entry = plane.nested_type.add(
            name="".join(w.title() for w in fname.split("_")) + "Entry")
        entry.options.map_entry = True
        entry.field.add(name="key", number=1, type=F.TYPE_INT64,
                        label=F.LABEL_OPTIONAL)
        entry.field.add(name="value", number=2, type=F.TYPE_MESSAGE,
                        label=F.LABEL_OPTIONAL,
                        type_name=".bench_xplane." + value)
        plane.field.add(name=fname, number=num, type=F.TYPE_MESSAGE,
                        label=F.LABEL_REPEATED,
                        type_name=".bench_xplane.XPlane." + entry.name)
    msg("XSpace", [("planes", 1, F.TYPE_MESSAGE, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def scope_of(tf_op: str) -> str | None:
    """The innermost ``range.*`` component of an op's scope path."""
    found = _SCOPE.findall(tf_op)
    return found[-1] if found else None


def _scoped_ops(path: str) -> dict:
    """``{device plane: [[start ns, duration ns, scope], ...]}`` for the
    ``XLA Ops`` whose ``tf_op`` path holds a ``range.*`` scope."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        tf_op = [k for k, v in plane.stat_metadata.items()
                 if v.name == "tf_op"]
        scope = {}
        for k, meta in plane.event_metadata.items():
            path_ = next((s.str_value for s in meta.stats
                          if s.metadata_id in tf_op), "")
            scope[k] = scope_of(path_)
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                sc = scope.get(e.metadata_id)
                if sc:
                    ops.append([line.timestamp_ns + e.offset_ps / 1e3,
                                e.duration_ps / 1e3, sc])
        out[plane.name] = ops
    return out


def _segments(spans, lo: float, hi: float):
    """``[lo, hi]`` cut at every span edge: the cut points and, for each
    piece between two of them, the index of the innermost span that holds
    it (the shortest: program spans nest) or ``None``."""
    cuts = sorted({lo, hi} | {x for s, d, *_ in spans
                              for x in (s, s + d) if lo < x < hi})
    owner = []
    for a, b in zip(cuts, cuts[1:]):
        holders = [k for k, (s, d, *_) in enumerate(spans)
                   if s <= a and s + d >= b]
        owner.append(min(holders, key=lambda k: spans[k][1], default=None))
    return cuts, owner


def _split(g0: float, g1: float, spans, cuts, owner) -> list:
    """The idle gap ``[g0, g1]`` as ``[label, seconds]`` pieces, each
    labelled by the innermost span it lies in; neighbouring pieces of one
    span are one piece."""
    out, last = [], -1
    i = max(bisect.bisect_right(cuts, g0) - 1, 0)
    while i < len(owner) and cuts[i] < g1:
        a, b = max(cuts[i], g0), min(cuts[i + 1], g1)
        if b > a:
            k = owner[i]
            if out and k == last:
                out[-1][1] += (b - a) / 1e9
            else:
                out.append([spans[k][2] if k is not None else "no span",
                            (b - a) / 1e9])
            last = k
        i += 1
    return out


def _covered(busy, starts, s: float, e: float) -> float:
    """``trace.covered`` over the sorted merged intervals ``busy`` whose
    starts are ``starts``."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    j = bisect.bisect_left(starts, e)
    return trace.covered(busy[i:j], s, e)


def reduce(ev: dict, top: int = 10) -> dict:
    """Over the ``bench.window`` span: device-idle seconds in each span
    (``span_idle_s``, children included) and how many there are; the
    longest idle pieces labelled by the innermost span; the counters summed
    over the window's steps; device seconds per named scope (``scope_s``).
    Seconds, averaged over the chips."""
    win = [h for h in ev["host"] if h[2] == "bench.window"]
    if not win or not ev["chips"]:
        return {}
    lo, hi = win[0][0], win[0][0] + win[0][1]
    spans = sorted((h for h in ev["host"]
                    if h[2] != "bench.window" and h[0] < hi
                    and h[0] + h[1] > lo), key=lambda h: (h[0], -h[1]))
    cuts, owner = _segments(spans, lo, hi)
    n = len(ev["chips"])
    gaps, span_idle, scope_s = [], {}, {}
    for chip in ev["chips"]:
        busy = trace.union([(s, d) for s, d, *_ in chip["ops"]
                            or chip["modules"]], lo, hi)
        starts = [b[0] for b in busy]
        edges = [lo] + [x for b in busy for x in b] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            gaps += [[label, t / n]
                     for label, t in _split(g0, g1, spans, cuts, owner)]
        for s, d, name, *_ in spans:
            s0, s1 = max(s, lo), min(s + d, hi)
            idle = (s1 - s0 - _covered(busy, starts, s0, s1)) / 1e9 / n
            span_idle[name] = span_idle.get(name, 0.0) + idle
        by_scope: dict[str, list] = {}
        for s, d, sc in chip.get("scoped", ()):
            by_scope.setdefault(sc, []).append((s, d))
        for sc, iv in by_scope.items():
            t = sum(e - s for s, e in trace.union(iv, lo, hi)) / 1e9 / n
            scope_s[sc] = scope_s.get(sc, 0.0) + t
    span_count: dict[str, int] = {}
    counters = dict.fromkeys(COUNTERS + ("served",), 0)
    for _, _, name, *args in spans:
        span_count[name] = span_count.get(name, 0) + 1
        a = args[0] if args else {}
        if name == STEP:
            counters["served"] += int(a.get("n", 0))
        elif name == "range.respond":
            for k in COUNTERS:
                counters[k] += int(a.get(k, 0))
    return {
        "window_s": (hi - lo) / 1e9,
        "span_idle_s": span_idle,
        "span_count": span_count,
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
        "counters": counters,
        "scope_s": scope_s,
    }


def reading(ctx) -> dict | None:
    """The reduction of the run's trace, made once per run and kept in
    ``ctx["trace"]["program"]``; ``None`` without a trace or where the
    program wrote no ``range.step`` span."""
    t = ctx["trace"]
    if not t:
        return None
    if "program" not in t:
        from .harness import TRACE_DIR
        t["program"] = reduce(load(TRACE_DIR))
    red = t["program"]
    return red if red and red["span_count"].get(STEP) else None


def idle_ms_per_step(ctx, name: str) -> float | None:
    """Device-idle ms inside the span ``name`` per ``range.step``."""
    red = reading(ctx)
    if red is None:
        return None
    return (1e3 * red["span_idle_s"].get(name, 0.0)
            / red["span_count"][STEP])


def main(argv=None) -> int:
    from .harness import TRACE_DIR
    args = sys.argv[1:] if argv is None else argv
    print(json.dumps(reduce(load(args[0] if args else TRACE_DIR))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
