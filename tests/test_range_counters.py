"""Work counters of the lockstep served path: per-lane phase-2 rounds in
``RangeResult.p2_rounds`` and the server's ``n_dist`` / ``n_visited`` /
``p2_lane_rounds`` / ``p2_slot_rounds``, against a hand computation; and
the same answers and counters with a profiler trace on and off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BuildConfig, RangeConfig, RangeSearchEngine, SearchConfig,
    beam_search_batch, build_vamana, greedy_search, range_search_compacted,
)
from repro.core.range_search import _needs_phase2
from repro.serve import RangeServer, Request, ServerConfig
from repro.utils import next_pow2

RADIUS = 6.0


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


@pytest.mark.parametrize("mode", ["greedy", "doubling", "beam"])
def test_server_counters_match_the_results(engine, mode):
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
        assert srv.stats["p2_lane_rounds"] == srv.stats["p2_slot_rounds"] == 0
        return
    p2 = res.p2_rounds
    lane = sum(int(p2[i]) for i in range(n) if p2[i] >= 0)
    dispatched = next_pow2(sum(1 for x in p2 if x >= 0))  # pad lanes too
    assert srv.stats["p2_lane_rounds"] == lane > 0
    assert srv.stats["p2_slot_rounds"] == dispatched * int(p2.max())
    assert lane <= srv.stats["p2_slot_rounds"]


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
