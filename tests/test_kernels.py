"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize_corpus, query_quant_err
from repro.kernels import (
    expand_frontier, expand_frontier_ref, flash_attention, flash_attention_ref,
    gatherdist, gatherdist_ref, rangescan, rangescan_ref,
)
from repro.utils import INVALID_ID


def _int8_tol(pts, qs, d_ref, metric):
    """Allowed kernel-vs-ref gap for int8 distances: the kernel quantizes
    the query (and subtracts its exact error), the XLA ref keeps it f32 —
    both certified lower bounds, differing by at most ~2 * err_q *
    (sqrt(d_max) + err_q) per candidate in the l2 sqrt domain, and
    ~2 * err_q * max||x|| for ip."""
    eq = float(np.max(np.asarray(query_quant_err(qs))))
    if metric == "ip":
        nmax = float(np.max(np.linalg.norm(np.asarray(pts), axis=1)))
        return 2.5 * eq * nmax + 1e-4
    dmax = float(np.nanmax(np.where(np.isfinite(d_ref), np.abs(d_ref), 0.0)))
    return 4.0 * eq * (np.sqrt(max(dmax, 1e-9)) + eq) + 1e-4


# ---------------------------------------------------------------------------
# rangescan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("q,n,d,k,bq,bn", [
    (20, 300, 64, 16, 8, 128),
    (7, 100, 33, 8, 8, 64),      # non-divisible everything
    (1, 512, 128, 32, 8, 256),   # single query
    (33, 64, 16, 64, 16, 64),    # k > in-range count
])
def test_rangescan_matches_ref(metric, q, n, d, k, bq, bn):
    kq = jax.random.PRNGKey(q * 7 + n)
    queries = jax.random.normal(kq, (q, d), jnp.float32)
    points = jax.random.normal(jax.random.PRNGKey(1), (n, d), jnp.float32)
    r = jnp.float32(1.1 * d * 0.5 if metric == "l2" else -0.2)
    ids, dd, c = rangescan(queries, points, r, k=k, block_q=bq, block_n=bn,
                           metric=metric, interpret=True)
    rids, rd, rc = rangescan_ref(queries, points, r, k=k, metric=metric)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    fin = np.isfinite(np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(ids)[fin], np.asarray(rids)[fin])


def test_rangescan_bf16_inputs():
    q = jax.random.normal(jax.random.PRNGKey(0), (8, 32), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32), jnp.bfloat16)
    ids, dd, c = rangescan(q, x, jnp.float32(20.0), k=8, block_q=8,
                           block_n=64, interpret=True)
    rids, rd, rc = rangescan_ref(q, x, jnp.float32(20.0), k=8)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd), rtol=2e-2, atol=2e-2)


def test_rangescan_counts_exceed_k():
    """counts must be exact even when more than k points are in range."""
    x = jnp.zeros((256, 8), jnp.float32)
    q = jnp.zeros((4, 8), jnp.float32)
    ids, dd, c = rangescan(q, x, jnp.float32(1.0), k=16, block_q=4,
                           block_n=64, interpret=True)
    assert (np.asarray(c) == 256).all()
    assert (np.asarray(ids) != INVALID_ID).sum() == 4 * 16


# ---------------------------------------------------------------------------
# gatherdist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,d,q,r", [(100, 32, 6, 9), (64, 7, 3, 5), (17, 128, 1, 4)])
def test_gatherdist_matches_ref(metric, n, d, q, r):
    pts = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    qs = jax.random.normal(jax.random.PRNGKey(1), (q, d), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (q, r), 0, n, jnp.int32)
    ids = ids.at[0, 0].set(INVALID_ID)
    got = gatherdist(pts, ids, qs, metric=metric, interpret=True)
    want = gatherdist_ref(pts, ids, qs, metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=1e-5)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,d,q,r", [(100, 32, 6, 9), (64, 16, 3, 5)])
def test_gatherdist_int8_matches_ref(metric, n, d, q, r):
    """Int8 kernel vs int8 XLA ref: ids/masking identical; distances agree
    within the query-quantization envelope (the kernel quantizes the query,
    the ref does not — both certified lower bounds)."""
    pts = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    qs = jax.random.normal(jax.random.PRNGKey(1), (q, d), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (q, r), 0, n, jnp.int32)
    ids = ids.at[0, 0].set(INVALID_ID)
    qc = quantize_corpus(pts)
    got = np.asarray(gatherdist(qc, ids, qs, metric=metric, interpret=True))
    want = np.asarray(gatherdist_ref(qc, ids, qs, metric=metric))
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin],
                               atol=_int8_tol(pts, qs, want, metric),
                               rtol=1e-3)


def test_gatherdist_int8_certified_lower_bound():
    """Both int8 paths must lower-bound the exact f32 distances — the
    contract every in-loop `dist <= r` test relies on."""
    pts = jax.random.normal(jax.random.PRNGKey(3), (80, 24), jnp.float32)
    qs = jax.random.normal(jax.random.PRNGKey(4), (5, 24), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(5), (5, 7), 0, 80, jnp.int32)
    qc = quantize_corpus(pts)
    for metric in ("l2", "ip"):
        exact = np.asarray(gatherdist_ref(pts, ids, qs, metric=metric))
        for lb in (np.asarray(gatherdist_ref(qc, ids, qs, metric=metric)),
                   np.asarray(gatherdist(qc, ids, qs, metric=metric,
                                         interpret=True))):
            assert np.all(lb <= exact + 1e-5), metric


# ---------------------------------------------------------------------------
# expand (fused frontier expansion)
# ---------------------------------------------------------------------------

def _expand_fixture(n, r, d, q, e, seed=0):
    pts = jax.random.normal(jax.random.PRNGKey(seed), (n, d), jnp.float32)
    adj = np.array(jax.random.randint(jax.random.PRNGKey(seed + 1),
                                      (n, r), 0, n, jnp.int32))
    adj[:, -max(1, r // 4):] = INVALID_ID      # INVALID-padded adjacency rows
    if r >= 2:
        adj[0, 1] = adj[0, 0]                  # duplicate neighbor in-row
        adj[1, :2] = adj[0, :2]                # duplicates across rows
    qs = jax.random.normal(jax.random.PRNGKey(seed + 2), (q, d), jnp.float32)
    fr = np.array(jax.random.randint(jax.random.PRNGKey(seed + 3),
                                     (q, e), 0, n, jnp.int32))
    if e >= 2:
        fr[0, 1] = fr[0, 0]                    # duplicate frontier node
        fr[-1, -1] = INVALID_ID                # padded frontier lane
    return pts, jnp.asarray(adj), jnp.asarray(fr), qs


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,r,d,q,e", [
    (150, 8, 32, 6, 4),
    (64, 5, 17, 3, 2),    # ragged degree/dim
    (40, 4, 16, 1, 6),    # E > eligible variety, single query
])
def test_expand_matches_ref(metric, n, r, d, q, e):
    pts, adj, fr, qs = _expand_fixture(n, r, d, q, e)
    ids, dd, nd = expand_frontier(pts, adj, fr, qs, metric=metric,
                                  use_pallas=True, interpret=True)
    rids, rd, rnd = expand_frontier_ref(pts, adj, fr, qs, metric=metric)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    # kernel uses the matmul (MXU) distance form; ref uses the diff form
    np.testing.assert_allclose(np.asarray(dd), np.asarray(rd),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(rnd))


def test_expand_dedups_within_tile():
    """Duplicate adjacency entries and duplicate frontier nodes must survive
    exactly once across the whole E*R tile."""
    pts, adj, fr, qs = _expand_fixture(100, 6, 16, 4, 3)
    ids, dd, _ = expand_frontier(pts, adj, fr, qs, use_pallas=True,
                                 interpret=True)
    for row in np.asarray(ids):
        live = row[row != INVALID_ID]
        assert len(np.unique(live)) == len(live)
    # invalid frontier lane contributes an all-INVALID row
    last = np.asarray(ids)[-1].reshape(3, -1)[-1]
    assert (last == INVALID_ID).all()


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("n,r,d,q,e", [
    (150, 8, 32, 6, 4),
    (64, 5, 17, 3, 2),    # ragged degree/dim
])
def test_expand_int8_matches_ref(metric, n, r, d, q, e):
    """Int8 expand kernel (packed code-row DMA + in-VMEM dequantization) vs
    the int8 XLA ref: identical ids/dedup/n_dist; the same lower-bound
    distances up to f32 summation order (both keep the query in f32)."""
    pts, adj, fr, qs = _expand_fixture(n, r, d, q, e)
    qc = quantize_corpus(pts)
    ids, dd, nd = expand_frontier(qc, adj, fr, qs, metric=metric,
                                  use_pallas=True, interpret=True)
    rids, rd, rnd = expand_frontier_ref(qc, adj, fr, qs, metric=metric)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(rnd))
    got, want = np.asarray(dd), np.asarray(rd)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-3, atol=1e-4)


def test_expand_int8_dedups_and_lower_bounds():
    """Dedup semantics carry over to the int8 kernel, and its distances
    lower-bound the exact f32 ones."""
    pts, adj, fr, qs = _expand_fixture(100, 6, 16, 4, 3)
    qc = quantize_corpus(pts)
    ids, dd, _ = expand_frontier(qc, adj, fr, qs, use_pallas=True,
                                 interpret=True)
    for row in np.asarray(ids):
        live = row[row != INVALID_ID]
        assert len(np.unique(live)) == len(live)
    exact_ids, exact_dd, _ = expand_frontier_ref(pts, adj, fr, qs)
    # same surviving ids as the f32 path (dedup is distance-independent)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(exact_ids))
    fin = np.isfinite(np.asarray(exact_dd))
    assert np.all(np.asarray(dd)[fin] <= np.asarray(exact_dd)[fin] + 1e-5)


def test_expand_bf16_corpus():
    pts, adj, fr, qs = _expand_fixture(80, 6, 32, 4, 2)
    a = expand_frontier(pts.astype(jnp.bfloat16), adj, fr, qs,
                        use_pallas=True, interpret=True)
    b = expand_frontier_ref(pts.astype(jnp.bfloat16), adj, fr, qs)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# flashattn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,skv,dh,causal,window,cap,qoff", [
    (2, 4, 2, 64, 64, 32, True, 0, 0.0, 0),
    (1, 8, 2, 37, 37, 16, True, 0, 50.0, 0),      # softcap, ragged len
    (1, 4, 4, 16, 128, 32, True, 64, 0.0, 112),   # decode w/ window+offset
    (2, 2, 1, 33, 65, 64, False, 0, 0.0, 0),      # non-causal MQA
    (1, 6, 3, 128, 128, 64, True, 32, 30.0, 0),   # window + softcap
])
def test_flash_matches_ref(b, hq, hkv, sq, skv, dh, causal, window, cap, qoff):
    q = jax.random.normal(jax.random.PRNGKey(5), (b, hq, sq, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(6), (b, hkv, skv, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(7), (b, hkv, skv, dh), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                        q_offset=qoff, block_q=32, block_k=32, interpret=True)
    ro = flash_attention_ref(q, k, v, causal=causal, window=window,
                             softcap=cap, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro), rtol=2e-4, atol=2e-4)


def test_flash_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 64, 32), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 32), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 32), jnp.bfloat16)
    o = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ro = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ro, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_xla_fallback_matches():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32, 16), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 32, 16), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 32, 16), jnp.float32)
    a = flash_attention(q, k, v, use_pallas=False)
    b = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# expand kernel inside the search loop (vmapped, in the while loops)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_search_with_expand_kernel_matches_xla(monkeypatch, dtype):
    """The served path with ``use_expand_kernel`` (kernel in interpret mode)
    answers like the XLA expand path: the same ids per query on an f32
    corpus; on int8, exact answers (no false positives after the rerank)
    at no less than the XLA path's recall, since the kernel computes the
    same lower bounds up to f32 summation order."""
    import dataclasses
    import sys
    from functools import partial

    from repro.core import BuildConfig, RangeConfig, RangeSearchEngine, SearchConfig
    from repro.core import exact_range_search
    from repro.data.synthetic import make_corpus

    ds = make_corpus("bigann-like", n=800, n_queries=16)
    pts, qs = jnp.asarray(ds.points), jnp.asarray(ds.queries)
    eng = RangeSearchEngine.build(pts, BuildConfig(max_degree=16, beam=32),
                                  corpus_dtype=dtype)
    r = 0.06
    cfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16, visit_cap=64,
                                          expand_width=4, corpus_dtype=dtype),
                      mode="greedy", result_cap=256)
    xla = eng.range(qs, r, cfg=cfg)
    bs = sys.modules["repro.core.beam_search"]
    monkeypatch.setattr(bs, "expand_frontier",
                        partial(bs.expand_frontier, interpret=True))
    kcfg = dataclasses.replace(cfg, search=dataclasses.replace(
        cfg.search, use_expand_kernel=True))
    ker = eng.range(qs, r, cfg=kcfg)
    gt_ids, _, gt_cnt = exact_range_search(pts, qs, r)

    def rows(res):
        return [set(np.asarray(res.ids[i][:res.count[i]]).tolist())
                for i in range(qs.shape[0])]

    truth = [set(np.asarray(gt_ids[i][:gt_cnt[i]]).tolist())
             for i in range(qs.shape[0])]
    assert sum(map(len, truth)) > 50  # the radius must select something
    if dtype == "float32":
        assert rows(ker) == rows(xla)
    else:
        assert all(k <= t for k, t in zip(rows(ker), truth))
        found = lambda res: sum(len(x & t) for x, t in zip(rows(res), truth))
        assert found(ker) >= found(xla)
