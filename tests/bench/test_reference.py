"""The reference, AP, the frozen generator and radius rule, and the
harness's refusal to report without a chip."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import corpus, radius, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name="bigann-int8"):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _boundary_case(metric):
    """Points on, just inside and just outside the radius of query 0; the
    first point is the one bfloat16 misplaces."""
    pts = np.zeros((6, 4), np.float32)
    q = np.zeros((2, 4), np.float32)
    if metric == "l2":
        # 1 + 2^-12 rounds to 1.0 in bfloat16, so a bfloat16 distance puts
        # the first point on the radius; its exact distance lies outside
        pts[0, 0] = 1.0 + 2.0 ** -12
        pts[1, 0] = 1.0 - 2.0 ** -12
        pts[2, 0] = 1.0
        pts[3, 1] = 3.0
        pts[4, :] = 0.25
        pts[5, 2] = -0.5
        q[1, 1] = 3.0
        return pts, q, 1.0, [[1, 2, 4, 5], [3]]
    # -q.x <= -1: q.x at least 1. 1 - 2^-12 rounds to 1.0 in bfloat16, so
    # a bfloat16 distance puts the first point on the radius; its exact
    # distance lies outside. The third and fifth lie exactly on it.
    q[0, 0] = 1.0
    q[1, :] = [0.5, 0.25, 0.0, -2.0]
    pts[0, 0] = 1.0 - 2.0 ** -12
    pts[1, 0] = 1.0 + 2.0 ** -12
    pts[2, 0] = 1.0
    pts[3, :] = [1.0, 2.0, 0.0, 0.0]      # q1.x = 1 exactly
    pts[4, :] = [1.0, 0.0, 7.0, -0.25]
    pts[5, :] = [-4.0, 0.0, 0.0, -3.0]    # q1.x = 4
    return pts, q, -1.0, [[1, 2, 3, 4], [3, 4, 5]]


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_exact_range_decides_the_boundary_exactly(metric):
    pts, q, r, want = _boundary_case(metric)
    got = reference.exact_range(pts, q, r, metric)
    assert [g.tolist() for g in got] == want
    import jax.numpy as jnp
    bf = np.asarray(jnp.asarray(pts, jnp.bfloat16).astype(jnp.float32),
                    np.float64)
    d_bf = reference.exact_dists(bf, q[0], np.array([0]), metric)[0]
    assert d_bf <= r < reference.exact_dists(pts, q[0], np.array([0]),
                                             metric)[0]


@pytest.mark.parametrize("metric,data,r", [
    ("l2", "normal", 9.0), ("ip", "normal", -9.0),
    ("l2", "grid", 40.0), ("ip", "grid", -20.0)])
def test_exact_range_matches_a_full_float64_scan(metric, data, r):
    rng = np.random.default_rng(3)
    if data == "normal":
        pts = rng.standard_normal((3000, 16)).astype(np.float32)
        qs = rng.standard_normal((40, 16)).astype(np.float32)
    else:
        # small whole numbers: every distance is a whole number, so many
        # points lie exactly on the radius
        pts = rng.integers(-3, 4, (3000, 16)).astype(np.float32)
        qs = rng.integers(-3, 4, (40, 16)).astype(np.float32)
    got = reference.exact_range(pts, qs, r, metric, block=7)
    on = 0
    for i, q in enumerate(qs):
        x, qq = pts.astype(np.float64), q.astype(np.float64)
        d = ((x - qq) ** 2).sum(1) if metric == "l2" else -(x @ qq)
        assert np.array_equal(np.nonzero(d <= r)[0], got[i])
        on += int((d == r).sum())
    assert sum(map(len, got)) > 0
    assert (on > 0) == (data == "grid")


def test_exact_range_refuses_an_unknown_metric():
    pts = np.zeros((2, 2), np.float32)
    with pytest.raises(ValueError, match="cosine"):
        reference.exact_range(pts, pts, 1.0, "cosine")


def test_average_precision_by_hand():
    t = [np.array([1, 2, 3, 4]), np.array([], np.int64), np.array([7])]
    a = [np.array([1, 2, 9]), np.array([5]), np.array([7])]
    assert reference.average_precision(t, a) == pytest.approx(3 / 5)
    assert reference.average_precision([np.array([], np.int64)],
                                       [np.array([1])]) == 1.0


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_compare_flags_wrong_answers(metric):
    # the second point lies inside, the third just outside, the fourth far
    # outside; max_excess is the third's excess relative to |r|
    pts = np.zeros((4, 2), np.float32)
    q = np.zeros((1, 2), np.float32)
    if metric == "l2":
        r = 1.0
        pts[1, 0] = 0.5
        pts[2, 0] = 1.0 + 2.0 ** -10
        pts[3, 0] = 3.0
        excess = (1 + 2.0 ** -10) ** 2 - 1
    else:
        # -q.x <= -2; the third point's distance is -2 + 2^-9, so
        # (d - r) / |r| = 2^-10, where (d - r) / r would read below 0
        r = -2.0
        q[0, 0] = 1.0
        pts[0, 0] = 3.0
        pts[1, 0] = 2.5
        pts[2, 0] = 2.0 - 2.0 ** -9
        pts[3, 0] = -3.0
        excess = 2.0 ** -10
    ok = reference.compare(pts, q, r, [(0, np.array([0, 1]))], metric)
    assert ok["ap"] == 1.0 and ok["max_excess"] == 0.0
    assert ok["bad_ids"] == 0 and ok["false_positives"] == 0
    out = reference.compare(pts, q, r, [(0, np.array([0, 2]))], metric)
    assert out["false_positives"] == 1
    assert out["max_excess"] == pytest.approx(excess)
    assert out["ap"] == pytest.approx(0.5)
    far = reference.compare(pts, q, r, [(0, np.array([3]))], metric)
    assert far["max_excess"] > 1.0
    bad = reference.compare(pts, q, r, [(0, np.array([0, 0, 1])),
                                        (0, np.array([7]))], metric)
    assert bad["bad_ids"] == 2


def test_frozen_generator_and_radius_are_pinned():
    p, q = corpus.make_corpus(_cfg()["profile"], 2000, 256, 0, 1)
    digest = hashlib.sha256(p.tobytes() + q.tobytes()).hexdigest()
    assert digest == ("255174e7a2e3d0446fc785bcfdd1973e"
                      "4aeaeb303deb3e7452625ba2f1e4ab48")
    sel = radius.select_radius(p, q, "l2")
    assert sel["grid_index"] == 0 and sel["max_matches"] == 33
    assert sel["radius"] == pytest.approx(0.018829556182026863, rel=1e-6)


def test_seed_draws_rows_but_not_the_distribution():
    prof = _cfg()["profile"]
    a, qa = corpus.make_corpus(prof, 1000, 64, 0, 1)
    b, qb = corpus.make_corpus(prof, 1000, 64, 0, 2**31 + 7)
    c, _ = corpus.make_corpus(prof, 1000, 64, 0, 1)
    assert np.array_equal(a, c)
    assert not np.array_equal(a, b) and not np.array_equal(qa, qb)
    # one embedding: both draws span the same 16-dim subspace
    basis = np.linalg.svd(a, full_matrices=False)[2][:16]
    resid = b - (b @ basis.T) @ basis
    assert np.abs(resid).max() < 0.01


def test_seed_only_orders_the_pool():
    cfg = _cfg()
    mb = cfg["server"]["max_batch"]
    a, qa = corpus.deployment(cfg, 1, n=1000, pool=4 * mb)
    b, qb = corpus.deployment(cfg, 2**31 + 7, n=1000, pool=4 * mb)
    assert np.array_equal(a, b)
    assert not np.array_equal(qa, qb)
    key = lambda q: q[np.lexsort(q.T[::-1])]
    assert np.array_equal(key(qa), key(qb))
    # the batches are the same sets of queries, in another order
    ba = sorted(key(qa[i:i + mb]).tobytes() for i in range(0, len(qa), mb))
    bb = sorted(key(qb[i:i + mb]).tobytes() for i in range(0, len(qb), mb))
    assert ba == bb
    # the pool is the head of the draw that the radius was selected on
    _, q = corpus.make_corpus(cfg["profile"], 1000, cfg["draw_queries"],
                              cfg["distribution_seed"],
                              cfg["distribution_seed"])
    assert np.array_equal(key(qa), key(q[:4 * mb]))


def test_config_radius_is_the_rule_on_the_calibration_draw():
    cfg = _cfg()
    p, q = corpus.make_corpus(cfg["profile"], cfg["n"], cfg["draw_queries"],
                              cfg["distribution_seed"],
                              cfg["distribution_seed"])
    assert radius.select_radius(p, q[:256], cfg["profile"]["metric"])[
        "radius"] == pytest.approx(cfg["radius"], rel=1e-6)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_radius_leaves_some_queries_empty_and_some_not(metric):
    prof = dict(_cfg()["profile"], metric=metric)
    p, q = corpus.make_corpus(prof, 2000, 256, 0, 1)
    sel = radius.select_radius(p, q, metric)
    assert (sel["radius"] < 0) == (metric == "ip")
    counts = np.array([len(t) for t in reference.exact_range(
        p, q, sel["radius"], metric)])
    assert 0 < (counts > 0).sum() < len(q)
    assert counts.max() == sel["max_matches"]
    assert (counts == 0).mean() == pytest.approx(sel["zero_frac"])
    # the grid is geometric for l2, linear over -q.x for ip
    g = radius.default_grid(p, q, metric).astype(np.float64)
    steps = g[1:] / g[:-1] if metric == "l2" else np.diff(g)
    assert np.allclose(steps, steps[0], rtol=1e-3)


def _run(args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, os.path.join("bench", "run.py")]
                          + args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_no_result_without_a_tpu():
    p = _run(["--workload", "bigann-int8.sat", "--seed", "1",
              "--seconds", "1"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_rehearsal_completes_and_prints_no_result():
    p = _run(["--workload", "bigann-int8.sat", "--seed", "3",
              "--seconds", "1", "--rehearse", "--n", "1500", "--pool",
              "256"])
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "check max_excess" in p.stderr
    assert "rehearsal, not a device result" in p.stderr
