"""System-behaviour + property tests for the paper's range-search core."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ES_D_VISITED, BuildConfig, RangeConfig, RangeSearchEngine, SearchConfig, average_precision, beam_search_batch, build_vamana, exact_range_search, exact_topk, from_lists, recall_at_k, robust_prune, zero_result_accuracy,
)
from repro.core.radius import default_grid, match_histogram, select_radius, sweep
from repro.utils import INVALID_ID


def _toy(n=800, d=12, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((8, d)).astype(np.float32) * 3
    pts = (centers[rng.integers(0, 8, n)] +
           rng.standard_normal((n, d)).astype(np.float32) * 0.4)
    return jnp.asarray(pts)


@pytest.fixture(scope="module")
def corpus():
    # Vamana, not plain kNN: a directed kNN graph on clustered data is
    # disconnected across clusters — navigability is exactly what the
    # alpha-pruned build provides (and what the paper's index assumes).
    pts = _toy()
    graph = build_vamana(pts, BuildConfig(max_degree=16, beam=32,
                                          insert_batch=256, two_pass=True))
    eng = RangeSearchEngine.from_graph(pts, graph)
    qs = pts[:64] + 0.01
    return pts, graph, eng, qs


# ---------------------------------------------------------------------------
# exact oracles
# ---------------------------------------------------------------------------

def test_exact_range_counts_match_bruteforce(corpus):
    pts, _, _, qs = corpus
    r = 2.0
    ids, dists, counts = exact_range_search(pts, qs, r)
    pd = np.asarray(((np.asarray(qs)[:, None, :] - np.asarray(pts)[None]) ** 2).sum(-1))
    np.testing.assert_array_equal(np.asarray(counts), (pd <= r).sum(1))
    # returned dists sorted ascending and within radius
    dd = np.asarray(dists)
    assert all((np.diff(row[np.isfinite(row)]) >= -1e-6).all() for row in dd)
    assert np.nanmax(np.where(np.isfinite(dd), dd, 0)) <= r + 1e-6


def test_exact_topk_matches_numpy(corpus):
    pts, _, _, qs = corpus
    ids, dists = exact_topk(pts, qs, k=5)
    pd = np.asarray(((np.asarray(qs)[:, None, :] - np.asarray(pts)[None]) ** 2).sum(-1))
    want = np.sort(pd, axis=1)[:, :5]
    # matmul-form distances (|q|^2+|x|^2-2qx) carry ~|q||x|*eps absolute
    # error, which dominates for near-zero distances
    np.testing.assert_allclose(np.asarray(dists), want, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# beam search invariants
# ---------------------------------------------------------------------------

def test_beam_finds_nearest_on_connected_graph(corpus):
    pts, graph, eng, qs = corpus
    cfg = SearchConfig(beam=48, max_beam=48, visit_cap=256)
    st_ = beam_search_batch(pts, graph, qs, eng.start_ids,
                            jnp.asarray(np.inf, jnp.float32), cfg)
    gt_ids, _ = exact_topk(pts, qs, k=1)
    got = np.asarray(st_.ids[:, 0])
    assert (got == np.asarray(gt_ids[:, 0])).mean() > 0.9


def test_beam_monotone_in_width(corpus):
    """Recall@10 must not decrease when the beam widens (paper's QPS knob)."""
    pts, graph, eng, qs = corpus
    gt_ids, _ = exact_topk(pts, qs, k=10)
    recalls = []
    for b in (8, 16, 32, 64):
        cfg = SearchConfig(beam=b, max_beam=b, visit_cap=4 * b)
        st_ = beam_search_batch(pts, graph, qs, eng.start_ids,
                                jnp.asarray(np.inf, jnp.float32), cfg)
        recalls.append(recall_at_k(np.asarray(gt_ids), np.asarray(st_.ids), 10))
    assert all(b >= a - 0.02 for a, b in zip(recalls, recalls[1:])), recalls
    assert recalls[-1] > 0.85


def test_beam_never_revisits(corpus):
    pts, graph, eng, qs = corpus
    cfg = SearchConfig(beam=32, max_beam=32, visit_cap=128)
    st_ = beam_search_batch(pts, graph, qs[:8], eng.start_ids,
                            jnp.asarray(np.inf, jnp.float32), cfg)
    for row, n in zip(np.asarray(st_.visited_ids), np.asarray(st_.n_visited)):
        v = row[: min(n, row.shape[0])]
        v = v[v != INVALID_ID]
        assert len(np.unique(v)) == len(v)


# ---------------------------------------------------------------------------
# range modes: beam <= doubling <= exact; greedy completes clusters
# ---------------------------------------------------------------------------

def _ap(eng, qs, r, cfg, gt, es=None):
    res = eng.range(qs, r, cfg=cfg, es_radius=es)
    return average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                             np.asarray(res.ids), np.asarray(res.count)), res


def test_mode_ordering(corpus):
    pts, graph, eng, qs = corpus
    r = 2.5
    gt = exact_range_search(pts, qs, r)
    ap_beam, _ = _ap(eng, qs, r, RangeConfig(
        search=SearchConfig(beam=16, max_beam=16, visit_cap=128), mode="beam"), gt)
    ap_dbl, _ = _ap(eng, qs, r, RangeConfig(
        search=SearchConfig(beam=16, max_beam=128, visit_cap=512), mode="doubling"), gt)
    ap_greedy, _ = _ap(eng, qs, r, RangeConfig(
        search=SearchConfig(beam=16, max_beam=16, visit_cap=128), mode="greedy"), gt)
    assert ap_dbl >= ap_beam - 0.02
    assert ap_greedy >= ap_beam - 0.02
    assert ap_greedy > 0.5


def test_greedy_results_all_in_range(corpus):
    pts, graph, eng, qs = corpus
    r = 2.5
    cfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16, visit_cap=128),
                      mode="greedy")
    res = eng.range(qs, r, cfg=cfg)
    dd = np.asarray(res.dists)
    ids = np.asarray(res.ids)
    assert np.all(dd[ids != INVALID_ID] <= r + 1e-5)
    # count equals number of valid ids when no overflow
    valid = (ids != INVALID_ID).sum(1)
    no_of = ~np.asarray(res.overflow)
    np.testing.assert_array_equal(valid[no_of], np.asarray(res.count)[no_of])


def test_fused_equals_compacted(corpus):
    pts, graph, eng, qs = corpus
    r = 2.5
    cfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16, visit_cap=128),
                      mode="greedy")
    a = eng.range(qs, r, cfg=cfg, compacted=True)
    b = eng.range(qs, r, cfg=cfg, compacted=False)
    np.testing.assert_array_equal(np.asarray(a.count), np.asarray(b.count))
    for ra, rb in zip(np.asarray(a.ids), np.asarray(b.ids)):
        assert set(ra[ra != INVALID_ID]) == set(rb[rb != INVALID_ID])


def test_early_stopping_cuts_work_not_results(corpus):
    pts, graph, eng, qs_near = corpus
    rng = np.random.default_rng(3)
    far = jnp.asarray(rng.standard_normal((64, pts.shape[1])).astype(np.float32) * 20)
    qs = jnp.concatenate([qs_near, far])
    r = 2.5
    gt = exact_range_search(pts, qs, r)
    base_cfg = SearchConfig(beam=32, max_beam=32, visit_cap=256)
    es_cfg = dataclasses.replace(base_cfg, es_metric=ES_D_VISITED, es_visit_limit=8)
    ap0, res0 = _ap(eng, qs, r, RangeConfig(search=base_cfg, mode="greedy"), gt)
    ap1, res1 = _ap(eng, qs, r, RangeConfig(search=es_cfg, mode="greedy"), gt, es=2.0 * r)
    assert np.asarray(res1.n_visited).sum() < np.asarray(res0.n_visited).sum()
    assert int(np.asarray(res1.es_stopped).sum()) > 0
    assert ap1 >= ap0 - 0.05
    # far queries answer zero results either way
    assert zero_result_accuracy(np.asarray(gt[2]), np.asarray(res1.count)) > 0.9


_EXPAND_CORPUS: dict = {}


def _expand_corpus():
    """Small cached Vamana index for the expand-width property test (the
    hypothesis stub can't drive pytest fixtures)."""
    if not _EXPAND_CORPUS:
        pts = _toy(500, seed=7)
        graph = build_vamana(pts, BuildConfig(max_degree=12, beam=24,
                                              insert_batch=256))
        _EXPAND_CORPUS["v"] = (pts, RangeSearchEngine.from_graph(pts, graph),
                               pts[:48] + 0.01)
    return _EXPAND_CORPUS["v"]


@given(st.integers(2, 8), st.floats(2.0, 3.5))
@settings(max_examples=6, deadline=None)
def test_expand_width_matches_single_node_ap(e, r):
    """Multi-node expansion (fused path) must match the single-node
    reference loop's AP within tolerance — E is a perf knob, not an
    accuracy knob."""
    pts, eng, qs = _expand_corpus()
    gt = exact_range_search(pts, qs, r)
    aps = {}
    for ew in (1, e):
        cfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                              visit_cap=128, expand_width=ew),
                          mode="greedy")
        aps[ew], _ = _ap(eng, qs, r, cfg, gt)
    assert aps[e] >= aps[1] - 0.02, aps


# ---------------------------------------------------------------------------
# Vamana build
# ---------------------------------------------------------------------------

def test_vamana_beats_random_graph():
    pts = _toy(600)
    qs = pts[:48] + 0.01
    g = build_vamana(pts, BuildConfig(max_degree=16, beam=32, insert_batch=256))
    eng = RangeSearchEngine.from_graph(pts, g)
    ids, _ = eng.topk(qs, k=10)
    gt_ids, _ = exact_topk(pts, qs, k=10)
    assert recall_at_k(np.asarray(gt_ids), np.asarray(ids), 10) > 0.8
    deg = np.asarray(g.degrees())
    assert deg.max() <= 16 and deg.mean() > 2


def test_robust_prune_selects_closest_and_diverse():
    pts = jnp.asarray(np.random.default_rng(0).standard_normal((50, 8)), jnp.float32)
    p = pts[0]
    cand = jnp.arange(1, 50, dtype=jnp.int32)
    d = jnp.sum((pts[cand] - p) ** 2, axis=-1)
    out = robust_prune(pts, p, cand, d, alpha=1.2, R=8)
    out = np.asarray(out)
    sel = out[out != INVALID_ID]
    assert len(sel) > 0 and len(np.unique(sel)) == len(sel)
    # the closest candidate always survives
    assert int(cand[np.argmin(np.asarray(d))]) in sel


# ---------------------------------------------------------------------------
# metrics + radius methodology properties (hypothesis)
# ---------------------------------------------------------------------------

@given(st.integers(1, 30), st.integers(0, 29), st.integers(1, 1000))
@settings(max_examples=25, deadline=None)
def test_ap_bounds_and_perfection(n_gt, n_hit, seed):
    rng = np.random.default_rng(seed)
    n_hit = min(n_hit, n_gt)
    gt = rng.choice(10_000, size=n_gt, replace=False).astype(np.int64)
    res = np.concatenate([gt[:n_hit], 10_000 + np.arange(5)])
    cap = max(n_gt, len(res))
    gt_ids = np.full((1, cap), INVALID_ID, np.int64)
    gt_ids[0, :n_gt] = gt
    res_ids = np.full((1, cap), INVALID_ID, np.int64)
    res_ids[0, :len(res)] = res
    ap = average_precision(gt_ids, np.array([n_gt]), res_ids, np.array([len(res)]))
    assert 0.0 <= ap <= 1.0
    np.testing.assert_allclose(ap, n_hit / n_gt)


@given(st.floats(0.5, 0.99))
@settings(max_examples=10, deadline=None)
def test_radius_selection_hits_target(target):
    pts = _toy(500, seed=2)
    qs = pts[:64] + 0.01
    grid = default_grid(np.asarray(pts), np.asarray(qs), "l2", num=24)
    prof = sweep(pts, qs, grid)
    r, gi = select_radius(prof, target_zero_frac=target, robustness_weight=0.0)
    assert grid[0] <= r <= grid[-1]
    # zero fraction monotonically decreases as radius grows
    zf = prof.zero_frac
    assert all(b <= a + 1e-9 for a, b in zip(zf, zf[1:]))


@pytest.mark.parametrize("n", [50_000, 400_000])
def test_default_grid_low_edge_keeps_its_match_count(n):
    """Regression: the grid's low edge was a fixed quantile of query-to-point
    distances, so the matches a query expects there grew with the corpus
    (about 500 at 1M rows) and radius selection fell onto the capture
    plateau. It now stays near GRID_LO_MATCHES at every size."""
    from repro.core.radius import GRID_LO_MATCHES
    rng = np.random.default_rng(4)
    pts = rng.standard_normal((n, 8)).astype(np.float32)
    qs = rng.standard_normal((256, 8)).astype(np.float32)
    grid = default_grid(pts, qs, "l2", num=8)
    mean = sweep(jnp.asarray(pts), jnp.asarray(qs), grid[:1]).counts.mean()
    assert GRID_LO_MATCHES / 2 <= mean <= 2 * GRID_LO_MATCHES, mean


def test_match_histogram_buckets():
    h = match_histogram(np.array([0, 0, 3, 11, 99, 1000, 99999]))
    assert h["0"] == 2 and h["<=1e1"] == 1 and h["<=1e2"] == 2
    assert h["<=1e3"] == 1 and h["<=1e5"] == 1


def test_match_histogram_overflow_bucket_sums_to_total():
    """Regression: counts past the paper's last printed column (>1e5) used
    to vanish from the table. They must land in the terminal overflow
    bucket, and the buckets must always partition the queries."""
    counts = np.array([0, 5, 100_000, 100_001, 250_000, 10**7])
    h = match_histogram(counts)
    assert h[">1e5"] == 3
    assert h["<=1e5"] == 1  # 100_000 is inclusive in the last printed column
    assert sum(h.values()) == len(counts)


@given(st.lists(st.integers(0, 10**7), min_size=1, max_size=50))
@settings(max_examples=50, deadline=None)
def test_match_histogram_partitions_any_counts(counts):
    h = match_histogram(np.array(counts))
    assert sum(h.values()) == len(counts)
    assert all(v >= 0 for v in h.values())


def test_select_radius_raises_on_infeasible_grid():
    """Regression: an all-infeasible grid (every radius → zero matches for
    every query) argmin'd to index 0 and silently blessed a vacuous
    benchmark radius; it must raise instead. The single-radius grid also
    exercises the np.gradient guard in sweep(), which crashed on < 2
    samples."""
    pts = _toy(64, seed=5)
    qs = np.asarray(pts[:8]) + 100.0  # far from every corpus point
    prof = sweep(pts, jnp.asarray(qs), np.array([1e-6], np.float32))
    assert prof.robustness.shape == (1,) and prof.robustness[0] == 0.0
    assert (prof.zero_frac == 1.0).all()
    with pytest.raises(ValueError, match="no feasible radius"):
        select_radius(prof)


def test_select_radius_single_feasible_grid_point():
    """A one-point grid with matches is degenerate but legal: sweep() must
    not crash on the gradient and select_radius must return that point."""
    pts = _toy(64, seed=5)
    qs = np.asarray(pts[:8]) + 0.01
    prof = sweep(pts, jnp.asarray(qs), np.array([10.0], np.float32))
    r, gi = select_radius(prof, target_zero_frac=0.5)
    assert gi == 0 and r == np.float32(10.0)


# ---------------------------------------------------------------------------
# graph container
# ---------------------------------------------------------------------------

def test_graph_out_neighbors_invalid_safe():
    g = from_lists([[1, 2], [0], [0, 1]])
    rows = g.out_neighbors(jnp.asarray([0, INVALID_ID], jnp.int32))
    assert np.asarray(rows)[1].tolist() == [INVALID_ID, INVALID_ID]


def test_graph_lane_padded():
    g = from_lists([[1, 2], [0], [0, 1]])
    gp = g.lane_padded(8)
    assert gp.max_degree == 8 and gp.num_nodes == g.num_nodes
    np.testing.assert_array_equal(np.asarray(gp.neighbors[:, :2]),
                                  np.asarray(g.neighbors))
    assert (np.asarray(gp.neighbors[:, 2:]) == INVALID_ID).all()
    assert g.lane_padded(2) is g  # already aligned -> no copy


@given(st.integers(2, 40), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_random_regular_no_self_loops(n, deg):
    g = __import__("repro.core.graph", fromlist=["random_regular"]).random_regular(
        jax.random.PRNGKey(n), n, deg)
    nbrs = np.asarray(g.neighbors)
    row = np.arange(n)[:, None]
    assert not (nbrs == row).any()
