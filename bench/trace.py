"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` of a ``--trace 1`` run into plain lists:
for each chip, the device's programs (``XLA Modules``) and operations
(``XLA Ops``), and the host spans that the harness puts around its own calls
(``bench.window``, ``bench.step``, ``bench.submit``).
``reduce`` works on those lists alone, so it is checked on a small recorded
trace (``tests/bench/trace_sample.json``).
"""
from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "bench."


def load(log_dir: str) -> dict:
    """Events of the newest trace under ``log_dir``, times in ns."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [[e.start_ns, e.duration_ns, e.name]
                            for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [[e.start_ns, e.duration_ns, e.name]
                           for e in line.events]
            chips.append({"plane": plane.name, "modules": mods, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"chips": chips, "host": host}


def layers() -> dict:
    with open(os.path.join(HERE, "layers.json")) as f:
        return json.load(f)


def union(intervals, lo: float, hi: float) -> list[list[float]]:
    """Merged ``[start, end]`` intervals of ``[start, duration]`` pairs,
    clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, d in sorted((s, d) for s, d in intervals):
        e = min(s + d, hi)
        s = max(s, lo)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(busy: list[list[float]], s: float, e: float) -> float:
    """Length of ``[s, e]`` that the merged intervals ``busy`` cover."""
    return sum(max(0.0, min(e, b1) - max(s, b0)) for b0, b1 in busy)


def layer_of(name: str, table: dict) -> str:
    for layer, patterns in table.items():
        if any(re.search(p, name) for p in patterns):
            return layer
    return "other"


def _split(g0: float, g1: float, spans) -> list:
    """The idle gap ``[g0, g1]`` cut at the host spans' edges: ``[label,
    seconds]`` pieces, labelled by the span they lie in."""
    out, t = [], g0
    for s, d, name in spans:
        s0, s1 = max(s, t), min(s + d, g1)
        if s1 <= s0:
            continue
        if s0 > t:
            out.append(["no span", (s0 - t) / 1e9])
        out.append([name, (s1 - s0) / 1e9])
        t = s1
    if g1 > t:
        out.append(["no span", (g1 - t) / 1e9])
    return out


def reduce(ev: dict, table: dict, top: int = 10) -> dict:
    """Busy and idle time, device time per layer and per program, and the
    idle gaps labelled by the host span they fall in, over the
    ``bench.window`` span. Times are averaged over the chips; seconds."""
    win = [h for h in ev["host"] if h[2] == "bench.window"]
    if not win or not ev["chips"]:
        return {}
    lo, hi = win[0][0], win[0][0] + win[0][1]
    spans = sorted((h for h in ev["host"]
                    if h[2] != "bench.window" and h[0] < hi
                    and h[0] + h[1] > lo), key=lambda h: h[0])
    n = len(ev["chips"])
    busy_s = 0.0
    per_layer: dict[str, float] = {}
    per_prog: dict[str, float] = {}
    gaps, span_idle = [], {}
    for chip in ev["chips"]:
        ops = chip["ops"] or chip["modules"]
        busy = union([(s, d) for s, d, _ in ops], lo, hi)
        busy_s += sum(e - s for s, e in busy) / 1e9
        for s, d, name in chip["modules"]:
            t = max(0.0, min(s + d, hi) - max(s, lo)) / 1e9
            per_prog[name] = per_prog.get(name, 0.0) + t / n
            lay = layer_of(name, table)
            per_layer[lay] = per_layer.get(lay, 0.0) + t / n
        edges = [lo] + [x for b in busy for x in b] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            gaps += [[label, t / n] for label, t in _split(g0, g1, spans)]
        for s, d, name in spans:
            s0, s1 = max(s, lo), min(s + d, hi)
            self_s = (s1 - s0 - covered(busy, s0, s1)) / 1e9 / n
            span_idle[name] = span_idle.get(name, 0.0) + self_s
    window_s = (hi - lo) / 1e9
    busy_s /= n
    span_count: dict[str, int] = {}
    for _, _, name in spans:
        span_count[name] = span_count.get(name, 0) + 1
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "layer_s": per_layer,
        "span_idle_s": span_idle,
        "span_count": span_count,
        "device_ops": sorted(([k, v] for k, v in per_prog.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
    }
