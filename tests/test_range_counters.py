"""Work counters of the lockstep served path: per-lane phase-2 rounds in
``RangeResult.p2_rounds`` and the server's ``n_dist`` / ``n_visited`` /
``p2_lane_rounds`` / ``p2_slot_rounds`` / ``p2_slices``, against a hand
computation; the sliced greedy phase 2 against one vmapped
``greedy_search``; each slice's bucket on the traced ``range.phase2`` span;
and the same answers and counters with a profiler trace on and off."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import (
    BuildConfig, RangeConfig, RangeSearchEngine, SearchConfig,
    beam_search_batch, build_vamana, greedy_lane_done, greedy_resume_batch,
    greedy_search, greedy_seed_batch, quantize_corpus, range_search_compacted,
)
from repro.core import range_search
from repro.core.range_search import _needs_phase2
from repro.serve import RangeServer, Request, ServerConfig
from repro.utils import next_pow2

RADIUS = 6.0
# a slice schedule scaled to the toy corpora below, so that buckets shrink
SMALL_ENDS = (32, 64, 128)


@pytest.fixture(scope="module")
def engine():
    """Clustered points and a radius at which some queries saturate their
    beam (phase 2) and others stop at phase 1."""
    rng = np.random.default_rng(3)
    centers = rng.standard_normal((8, 12)).astype(np.float32) * 3
    pts = jnp.asarray(centers[rng.integers(0, 8, 1500)]
                      + rng.standard_normal((1500, 12)).astype(np.float32)
                      * 0.6)
    g = build_vamana(pts, BuildConfig(max_degree=16, beam=32,
                                      insert_batch=256))
    qs = np.concatenate([np.asarray(pts[:14]) + 0.02,
                         rng.standard_normal((6, 12)).astype(np.float32) * 6])
    return RangeSearchEngine.from_graph(pts, g), qs


def _cfg(mode="greedy"):
    return RangeConfig(search=SearchConfig(beam=16, max_beam=32,
                                           visit_cap=64),
                       mode=mode, result_cap=512)


def test_p2_rounds_are_the_greedy_lanes_rounds(engine):
    eng, qs = engine
    cfg = _cfg()
    q = jnp.asarray(qs)
    res = range_search_compacted(corpus=eng.points, graph=eng.graph,
                                 queries=q, start_ids=eng.start_ids,
                                 r=RADIUS, cfg=cfg)
    # by hand: phase 1, the trigger, then every lane's greedy run
    rj = jnp.full((q.shape[0],), RADIUS, jnp.float32)
    st = beam_search_batch(eng.points, eng.graph, q, eng.start_ids, rj,
                           cfg.search)
    active = np.asarray(jax.vmap(
        lambda s, r: _needs_phase2(s, r, cfg.lam))(st, rj))
    gs = jax.vmap(lambda q_, r_, s_: greedy_search(
        eng.points, eng.graph, q_, r_, s_, cfg.result_cap,
        cfg.frontier_rounds, cfg.search))(q, rj, st)
    want = np.where(active, np.asarray(gs.rounds), -1)
    assert 0 < active.sum() < len(active)
    assert isinstance(res.p2_rounds, np.ndarray)
    np.testing.assert_array_equal(res.p2_rounds, want)
    assert (want[active] > 0).all()


def _sliced_by_hand(eng, cfg, q, rj):
    """Re-run the compacted greedy phase 2 slice by slice (gathering the
    live lanes after every slice): the lane-rounds dispatched, over the
    slices bucket x the slowest lane's advance, and the number of slices."""
    cap, budget = cfg.result_cap, cfg.frontier_rounds
    st = beam_search_batch(eng.points, eng.graph, q, eng.start_ids, rj,
                           cfg.search)
    sel = np.nonzero(np.asarray(jax.vmap(
        lambda s, r: _needs_phase2(s, r, cfg.lam))(st, rj)))[0]
    pad = np.concatenate([sel, np.repeat(sel[:1],
                                         next_pow2(len(sel)) - len(sel))])
    gs = greedy_seed_batch(eng.points, jax.tree.map(lambda x: x[pad], st),
                           rj[pad], cap, cfg.search)
    qs, rs, on = q[pad], rj[pad], np.arange(len(pad)) < len(sel)
    before, slots, slices, start = np.zeros(len(pad), np.int32), 0, 0, 0
    for end in SMALL_ENDS + (budget,):
        to_end = len(on) == 1 or end >= budget
        gs = greedy_resume_batch(eng.points, eng.graph, qs, rs, gs,
                                 jnp.asarray(on), cap, budget,
                                 budget if to_end else end - start,
                                 cfg.search)
        rounds = np.asarray(gs.rounds)
        slots += len(on) * int((rounds - before).max())
        slices += 1
        live = on & ~greedy_lane_done(gs, budget)[0]
        if to_end or not live.any():
            return slots, slices
        start, keep = end, np.nonzero(live)[0]
        keep = np.concatenate([keep, np.repeat(
            keep[:1], next_pow2(len(keep)) - len(keep))])
        gs, qs, rs = jax.tree.map(lambda x: x[keep], (gs, qs, rs))
        on = np.arange(len(keep)) < live.sum()
        before = rounds[keep]


@pytest.mark.parametrize("mode", ["greedy", "doubling", "beam"])
def test_server_counters_match_the_results(engine, mode, monkeypatch):
    monkeypatch.setattr(range_search, "P2_SLICE_ENDS", SMALL_ENDS)
    eng, qs = engine
    cfg = _cfg(mode)
    srv = RangeServer(eng, cfg, ServerConfig(max_batch=32))
    n = 20  # one batch, padded to 32 with repeats of the first query
    for i in range(n):
        srv.submit(Request(req_id=i, query=qs[i], radius=RADIUS))
    assert len(srv.step()) == n
    q = np.concatenate([qs[:n], np.repeat(qs[:1], 32 - n, axis=0)])
    res = range_search_compacted(
        corpus=eng.points, graph=eng.graph, queries=jnp.asarray(q),
        start_ids=eng.start_ids, r=jnp.full((32,), RADIUS, jnp.float32),
        cfg=cfg)
    assert srv.stats["n_dist"] == int(np.asarray(res.n_dist)[:n].sum()) > 0
    assert srv.stats["n_visited"] == int(
        np.asarray(res.n_visited)[:n].sum()) > 0
    if mode != "greedy":  # no greedy lanes to count
        assert res.p2_rounds is None
        assert (srv.stats["p2_lane_rounds"] == srv.stats["p2_slot_rounds"]
                == srv.stats["p2_slices"] == 0)
        return
    p2 = res.p2_rounds
    lane = sum(int(p2[i]) for i in range(n) if p2[i] >= 0)
    slots, slices = _sliced_by_hand(eng, cfg, jnp.asarray(q),
                                    jnp.full((32,), RADIUS, jnp.float32))
    assert srv.stats["p2_lane_rounds"] == lane > 0
    assert srv.stats["p2_slot_rounds"] == res.p2_slot_rounds == slots
    assert srv.stats["p2_slices"] == res.p2_slices == slices > 1
    # one vmapped run over the first bucket (pad lanes too) bounds it above
    dispatched = next_pow2(sum(1 for x in p2 if x >= 0))
    assert lane <= slots <= dispatched * int(p2.max())


@pytest.fixture(scope="module")
def skewed():
    """Clusters of 700 down to 30 points with the queries at their centres:
    the phase-2 lanes stop at very different rounds, and the 700-cluster
    lane spends its whole budget with its frontier still open."""
    rng = np.random.default_rng(5)
    sizes, per_cluster = (700, 260, 120, 60, 30), (1, 1, 2, 3, 4)
    centers = rng.standard_normal((len(sizes), 8)).astype(np.float32) * 6
    pts = np.concatenate(
        [c + rng.standard_normal((k, 8)).astype(np.float32) * 0.35
         for c, k in zip(centers, sizes)]
        + [rng.standard_normal((400, 8)).astype(np.float32) * 6])
    g = build_vamana(jnp.asarray(pts), BuildConfig(max_degree=16, beam=32,
                                                   insert_batch=256))
    qs = np.concatenate(
        [c + rng.standard_normal((k, 8)).astype(np.float32) * 0.1
         for c, k in zip(centers, per_cluster)]
        + [rng.standard_normal((3, 8)).astype(np.float32) * 6])
    return RangeSearchEngine.from_graph(jnp.asarray(pts), g), qs


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_sliced_phase2_matches_one_shot(skewed, dtype, monkeypatch):
    monkeypatch.setattr(range_search, "P2_SLICE_ENDS", SMALL_ENDS)
    shrinks = []
    retire = range_search._retire_lanes

    def counted(out, gs, rows, keep, carry):
        shrinks.extend([] if keep is None else [(len(rows), len(keep))])
        return retire(out, gs, rows, keep, carry)

    monkeypatch.setattr(range_search, "_retire_lanes", counted)
    eng, qs = skewed
    pts = (eng.points if dtype == "float32"
           else quantize_corpus(eng.points))
    cfg = RangeConfig(search=SearchConfig(beam=8, max_beam=8, visit_cap=32),
                      mode="greedy", result_cap=1024, frontier_rounds=400,
                      rerank=False)
    q = jnp.asarray(qs)
    rj = jnp.full((q.shape[0],), 1.5, jnp.float32)
    res = range_search_compacted(corpus=pts, graph=eng.graph, queries=q,
                                 start_ids=eng.start_ids, r=rj, cfg=cfg)
    # one shot: phase 1, the trigger, one vmapped greedy_search
    st = beam_search_batch(pts, eng.graph, q, eng.start_ids, rj, cfg.search)
    on = np.asarray(jax.vmap(
        lambda s, r: _needs_phase2(s, r, cfg.lam))(st, rj))
    gs = jax.vmap(lambda q_, r_, s_, a_: greedy_search(
        pts, eng.graph, q_, r_, s_, cfg.result_cap, cfg.frontier_rounds,
        cfg.search, a_))(q, rj, st, jnp.asarray(on))
    gs = jax.tree.map(np.asarray, gs)
    # the straggler overflows on its budget, not on the buffer
    assert ((gs.rounds >= cfg.frontier_rounds)
            & (gs.expand_ptr < gs.res_count) & gs.overflow & on).any()
    assert len(shrinks) >= 2 and res.p2_slices > 1
    np.testing.assert_array_equal(np.asarray(res.ids)[on], gs.res_ids[on])
    np.testing.assert_array_equal(np.asarray(res.dists)[on],
                                  gs.res_dists[on])
    np.testing.assert_array_equal(np.asarray(res.count)[on],
                                  gs.res_count[on])
    np.testing.assert_array_equal(np.asarray(res.overflow)[on],
                                  gs.overflow[on])
    np.testing.assert_array_equal(np.asarray(res.n_dist)[on],
                                  np.asarray(st.n_dist)[on] + gs.n_dist[on])
    np.testing.assert_array_equal(res.p2_rounds, np.where(on, gs.rounds, -1))


def _serve(eng, qs, log_dir=None):
    srv = RangeServer(eng, _cfg(), ServerConfig(max_batch=8))
    for i, q in enumerate(qs):
        srv.submit(Request(req_id=i, query=q, radius=RADIUS))
    if log_dir is not None:
        jax.profiler.start_trace(log_dir)
    try:
        out = srv.run_until_drained()
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()
    return sorted(out, key=lambda r: r.req_id), srv.stats


def test_answers_and_counters_same_with_profiler_on(engine, tmp_path):
    eng, qs = engine
    off, s_off = _serve(eng, qs)
    on, s_on = _serve(eng, qs, str(tmp_path))
    assert s_on == s_off
    assert s_off["p2_slot_rounds"] > 0
    for a, b in zip(off, on):
        assert a.req_id == b.req_id and a.count == b.count
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)


def test_phase2_span_names_each_slice_bucket(engine, tmp_path, monkeypatch):
    """The traced ``range.phase2`` span carries the slice count and each
    slice's bucket, from the bucket ``range.compact`` picked downwards."""
    monkeypatch.setattr(range_search, "P2_SLICE_ENDS", SMALL_ENDS)
    eng, qs = engine
    _serve(eng, qs, str(tmp_path))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = sorted((e.start_ns, e.name, dict(e.stats))
                for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:") for line in p.lines
                for e in line.events
                if e.name in ("range.compact", "range.phase2"))
    pairs = [(a, b) for a, b in zip(ev, ev[1:]) if b[1] == "range.phase2"]
    assert pairs and all(a[1] == "range.compact" for a, _ in pairs)
    pairs = [(a[2], b[2]) for a, b in pairs]
    for compact, p2 in pairs:
        buckets = [int(b) for b in str(p2["buckets"]).split("-")]
        assert len(buckets) == p2["slices"]
        assert buckets[0] == compact["bucket"] >= compact["active"]
        assert buckets == sorted(buckets, reverse=True)
    assert max(p2["slices"] for _, p2 in pairs) > 1
