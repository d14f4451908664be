"""The reduction from a profiler trace to per-layer numbers, on hand-made
events and on a small trace recorded on a TPU v5e chip
(``trace_sample.json``: a slice of a ``bigann-int8.sat`` window)."""
import json
import os

import numpy as np
import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


def _events():
    # window 0..100 ms; device ops overlap at 10-30 and 20-40, one more at
    # 60-70, one that starts before the window; host spans cover 0-50
    # (step) and 50-100 (wait)
    ops = [[10 * MS, 20 * MS, "fusion.1"], [20 * MS, 20 * MS, "fusion.2"],
           [60 * MS, 10 * MS, "fusion.3"], [-5 * MS, 7 * MS, "copy.1"]]
    mods = [[-5 * MS, 7 * MS, "jit_other(1)"],
            [10 * MS, 30 * MS, "jit_beam_search_batch(7)"],
            [60 * MS, 10 * MS, "jit__exact_pairs(9)"]]
    host = [[0, 100 * MS, "bench.window"], [0, 50 * MS, "bench.step"],
            [50 * MS, 50 * MS, "bench.wait"]]
    return {"chips": [{"plane": "/device:TPU:0", "modules": mods,
                       "ops": ops}], "host": host}


def test_union_merges_overlaps_and_clips():
    got = trace.union([(10, 20), (20, 20), (60, 10), (-5, 7), (95, 50)],
                      0, 100)
    assert got == [[0, 2], [10, 40], [60, 70], [95, 100]]
    assert trace.covered(got, 5, 65) == 35


def test_reduce_busy_idle_layers_and_gaps():
    table = {"phase1": ["beam_search_batch"], "rerank": ["_exact_pairs"]}
    red = trace.reduce(_events(), table)
    assert red["window_s"] == pytest.approx(0.1)
    # busy: 0-2 (clipped copy), 10-40, 60-70 -> 42 ms
    assert red["busy_s"] == pytest.approx(0.042)
    assert red["idle_share"] == pytest.approx(0.58)
    assert red["layer_s"]["phase1"] == pytest.approx(0.030)
    assert red["layer_s"]["rerank"] == pytest.approx(0.010)
    assert red["layer_s"]["other"] == pytest.approx(0.002)
    # the gap 40-60 is cut where the step ends and the wait begins
    gaps = sorted((lab, round(s * 1e3, 6)) for lab, s in red["idle_gaps"])
    assert gaps == [("bench.step", 8.0), ("bench.step", 10.0),
                    ("bench.wait", 10.0), ("bench.wait", 30.0)]
    assert red["span_idle_s"]["bench.step"] == pytest.approx(0.050 - 0.032)
    assert red["span_idle_s"]["bench.wait"] == pytest.approx(0.040)
    assert red["span_count"] == {"bench.step": 1, "bench.wait": 1}


def test_two_chips_average():
    ev = _events()
    second = json.loads(json.dumps(ev["chips"][0]))
    second["ops"] = [[0, 100 * MS, "fusion.9"]]
    ev["chips"].append(second)
    red = trace.reduce(ev, {})
    assert red["busy_s"] == pytest.approx((0.042 + 0.1) / 2)


def _sweep_busy(ops, lo, hi, step):
    t = np.arange(lo, hi, step) + step / 2
    on = np.zeros(t.size, bool)
    for s, d, _ in ops:
        on |= (t >= s) & (t < s + d)
    return on.sum() * step / 1e9


def test_recorded_chip_trace():
    path = os.path.join(HERE, "trace_sample.json")
    with open(path) as f:
        ev = json.load(f)
    red = trace.reduce(ev, trace.layers())
    win = next(h for h in ev["host"] if h[2] == "bench.window")
    lo, hi = win[0], win[0] + win[1]
    chip = ev["chips"][0]
    assert red["busy_s"] == pytest.approx(
        _sweep_busy(chip["ops"], lo, hi, 1000), rel=2e-3)
    assert 0.0 < red["idle_share"] < 1.0
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    # every program's device time lands in exactly one layer
    mods = sum(max(0, min(s + d, hi) - max(s, lo)) for s, d, _ in
               chip["modules"]) / 1e9
    assert sum(red["layer_s"].values()) == pytest.approx(mods)
    assert {"phase1", "phase2", "rerank"} <= set(red["layer_s"])
    assert all(lab.startswith("bench.") or lab == "no span"
               for lab, _ in red["idle_gaps"])
