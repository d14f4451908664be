"""The program's spans and counters as the benchmark reads them: a small
``RangeServer`` traced on the CPU and read back through ``bench.spans``; the
innermost-span reduction on hand-made and recorded events; and each reader
of a per-layer metric that rests on them, on a fixed context."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    """Three lockstep steps of an int8 server (phase 2 and the rerank both
    run) inside a ``bench.window`` span, under the profiler."""
    from repro.core import (RangeConfig, RangeSearchEngine, SearchConfig,
                            build_knn_graph)
    from repro.serve import RangeServer, Request, ServerConfig
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((6, 12)).astype(np.float32) * 3
    pts = jnp.asarray(centers[rng.integers(0, 6, 1200)]
                      + rng.standard_normal((1200, 12)).astype(np.float32))
    eng = RangeSearchEngine.from_graph(pts, build_knn_graph(pts, k=12),
                                       corpus_dtype="int8")
    cfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16, visit_cap=64,
                                          corpus_dtype="int8"),
                      mode="greedy", result_cap=512)
    srv = RangeServer(eng, cfg, ServerConfig(max_batch=16))
    qs = np.asarray(pts[:40]) + 0.05
    for i, q in enumerate(qs):
        srv.submit(Request(req_id=i, query=q, radius=16.0))
    srv.step()  # compile outside the trace
    before = dict(srv.stats)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        out = [srv.step(), srv.step()]
    jax.profiler.stop_trace()
    return spans.load(log_dir), out, before, srv.stats


def test_server_step_writes_nested_program_spans(traced_steps):
    ev, out, _, _ = traced_steps
    host = [h for h in ev["host"] if h[2].startswith("range.")]
    steps = sorted((h for h in host if h[2] == spans.STEP),
                   key=lambda h: h[0])
    assert [len(o) for o in out] == [16, 8]
    assert [(h[3]["batch"], h[3]["n"], h[3]["bucket"]) for h in steps] == [
        (1, 16, 16), (2, 8, 8)]
    for st in steps:
        kids = sorted((h for h in host if h[2] != spans.STEP
                       and st[0] <= h[0] and h[0] + h[1] <= st[0] + st[1]),
                      key=lambda h: h[0])
        # each child once, in order, and no two overlap
        assert tuple(h[2] for h in kids) == spans.CHILDREN
        assert all(a[0] + a[1] <= b[0] for a, b in zip(kids, kids[1:]))
        args = {h[2]: h[3] for h in kids}
        assert args["range.compact"]["active"] >= 1
        assert args["range.compact"]["bucket"] >= args["range.compact"][
            "active"]
        assert set(spans.COUNTERS) <= set(args["range.respond"])
    # every range.* span lies inside a range.step
    assert all(any(st[0] <= h[0] and h[0] + h[1] <= st[0] + st[1]
                   for st in steps) for h in host)


def test_span_counts_add_up_to_the_server_counters(traced_steps):
    ev, _, before, after = traced_steps
    resp = [h[3] for h in ev["host"] if h[2] == "range.respond"]
    assert len(resp) == 2
    for k in spans.COUNTERS:
        assert sum(a[k] for a in resp) == after[k] - before[k] > 0


def _nested_events():
    # window 0..100 ms; device busy 10-20 and 60-70. bench.step 0-50 holds
    # range.step 2-48, which holds range.batch 2-5, range.merge 30-40 and
    # range.respond 40-45;
    # bench.step 50-100 holds range.step 52-98 with no children
    ops = [[10 * MS, 10 * MS, "fusion.1"], [60 * MS, 10 * MS, "fusion.2"]]
    host = [[0, 100 * MS, "bench.window"], [0, 50 * MS, "bench.step"],
            [2 * MS, 46 * MS, "range.step", {"n": 16}],
            [2 * MS, 3 * MS, "range.batch", {}],
            [30 * MS, 10 * MS, "range.merge", {}],
            [40 * MS, 5 * MS, "range.respond",
             {"n_dist": 7, "n_visited": 3, "p2_lane_rounds": 5,
              "p2_slot_rounds": 8}],
            [50 * MS, 50 * MS, "bench.step"],
            [52 * MS, 46 * MS, "range.step", {"n": 4}]]
    return {"chips": [{"plane": "/device:TPU:0", "modules": [], "ops": ops}],
            "host": host}


def test_idle_goes_to_the_innermost_span():
    red = spans.reduce(_nested_events(), top=20)
    gaps = sorted((lab, round(s * 1e3, 6)) for lab, s in red["idle_gaps"])
    # 0-10: bench.step 0-2, range.batch 2-5, range.step 5-10; 20-60:
    # range.step 20-30 and 45-48, range.merge 30-40, range.respond 40-45,
    # bench.step 48-50 and 50-52, range.step 52-60; 70-100: range.step
    # 70-98, bench.step 98-100
    assert gaps == sorted([
        ("bench.step", 2.0), ("range.batch", 3.0), ("range.step", 5.0),
        ("range.step", 10.0), ("range.merge", 10.0), ("range.respond", 5.0),
        ("range.step", 3.0), ("bench.step", 2.0), ("bench.step", 2.0),
        ("range.step", 8.0), ("range.step", 28.0), ("bench.step", 2.0)])
    assert len(spans.reduce(_nested_events())["idle_gaps"]) == 10
    idle = red["span_idle_s"]
    assert idle["bench.step"] == pytest.approx(0.080)
    assert idle["range.step"] == pytest.approx(0.036 + 0.036)
    assert idle["range.batch"] == pytest.approx(0.003)
    assert idle["range.merge"] == pytest.approx(0.010)
    assert idle["range.respond"] == pytest.approx(0.005)
    assert red["span_count"] == {"bench.step": 2, "range.step": 2,
                                 "range.batch": 1, "range.merge": 1,
                                 "range.respond": 1}
    assert red["counters"] == {"n_dist": 7, "n_visited": 3,
                               "p2_lane_rounds": 5, "p2_slot_rounds": 8,
                               "served": 20}


def test_without_program_spans_gaps_match_bench_trace():
    """On the recorded chip trace, which has only the harness's spans, the
    innermost-span labels are those of ``bench.trace``."""
    with open(os.path.join(HERE, "trace_sample.json")) as f:
        ev = json.load(f)
    old = trace.reduce(ev, trace.layers())
    new = spans.reduce(ev)
    assert new["idle_gaps"] == old["idle_gaps"]
    assert new["span_idle_s"] == old["span_idle_s"]
    assert new["span_count"] == old["span_count"]
    assert new["window_s"] == old["window_s"]
    assert sum(new["span_idle_s"].values()) <= (
        old["window_s"] - old["busy_s"])


def test_scope_seconds_are_unions_per_scope():
    ev = _nested_events()
    ev["chips"][0]["scoped"] = [
        [10 * MS, 6 * MS, "range.phase2"], [14 * MS, 4 * MS, "range.phase2"],
        [60 * MS, 10 * MS, "range.rerank"], [-5 * MS, 10 * MS,
                                             "range.phase1"]]
    red = spans.reduce(ev)
    assert red["scope_s"] == pytest.approx(
        {"range.phase2": 0.008, "range.rerank": 0.010,
         "range.phase1": 0.005})


@pytest.mark.parametrize("path,want", [
    ("jit(greedy_search)/range.phase2/while/body/scatter", "range.phase2"),
    ("jit(range_phase1)/range.phase1/jit(beam_search_batch)/range.phase1/"
     "vmap(jit(beam_search))/while", "range.phase1"),
    ("jit(_exact_pairs)/range.rerank/jit(_take)/gather", "range.rerank"),
    ("jit(_take)/gather", None), ("", None)])
def test_scope_of_tf_op_path(path, want):
    assert spans.scope_of(path) == want


def test_xspace_reader_finds_scoped_ops(tmp_path):
    xspace = spans._xspace_class()
    sp = xspace()
    plane = sp.planes.add(name="/device:TPU:0")
    plane.stat_metadata[7].name = "tf_op"
    plane.stat_metadata[7].id = 7
    for k, path in ((1, "jit(greedy_search)/range.phase2/while"),
                    (2, "jit(_take)/gather")):
        plane.event_metadata[k].id = k
        plane.event_metadata[k].stats.add(metadata_id=7, str_value=path)
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=3_000)
    line.events.add(metadata_id=2, offset_ps=9_000_000, duration_ps=5_000)
    sp.planes.add(name="/host:CPU")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(sp.SerializeToString())
    assert spans._scoped_ops(str(path)) == {
        "/device:TPU:0": [[1000 + 2000.0, 3.0, "range.phase2"]]}


# one reduction every reader below reads: 4 steps, 10 ms idle a step
_PROGRAM = {
    "window_s": 1.0,
    "span_idle_s": {"bench.step": 0.042, "range.step": 0.040,
                    "range.batch": 0.002, "range.phase1": 0.001,
                    "range.compact": 0.012, "range.phase2": 0.001,
                    "range.merge": 0.008, "range.rerank": 0.010,
                    "range.respond": 0.004},
    "span_count": {"bench.step": 4, "range.step": 4},
    "counters": {"n_dist": 51200, "n_visited": 2048, "p2_lane_rounds": 300,
                 "p2_slot_rounds": 1200, "served": 512},
}
READINGS = {
    "step_host_ms.sat": 10.0, "compact_host_ms.sat": 3.0,
    "merge_host_ms.sat": 2.0, "rerank_host_ms.sat": 2.5,
    "respond_host_ms.sat": 1.0, "p2_lane_util.sat": 0.25,
    "dist_per_q.sat": 100.0,
}


def _ctx(program):
    return {"trace": {"idle_share": 0.1, "program": program},
            "answered_in_window": 512}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_metric_reader(metric):
    assert harness.reader(metric)(_ctx(_PROGRAM)) == pytest.approx(
        READINGS[metric])


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_metric_reader_is_silent_without_program_spans(metric):
    """A program that writes no range.* spans (the parent of this change)
    and an untraced run both leave the metric out."""
    parent = dict(_PROGRAM, span_count={"bench.step": 4},
                  counters=dict.fromkeys(_PROGRAM["counters"], 0))
    read = harness.reader(metric)
    assert read(_ctx(parent)) is None
    assert read({"trace": {}, "answered_in_window": 0}) is None


def test_new_metrics_are_declared_for_the_cell():
    manifest = harness.load_manifest()
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in READINGS:
        m = per_layer[name]
        assert m["workloads"] == ["bigann-int8.sat"] and m["moves"] == "qps"
        assert m["source"] == ("program_counter" if name in (
            "p2_lane_util.sat", "dist_per_q.sat") else "program_span")
