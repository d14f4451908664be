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


def test_exact_range_decides_the_boundary_exactly():
    # 1 + 2^-12 rounds to 1.0 in bfloat16, so a bfloat16 distance puts the
    # first point on the radius; its exact distance lies outside
    pts = np.zeros((6, 4), np.float32)
    pts[0, 0] = 1.0 + 2.0 ** -12
    pts[1, 0] = 1.0 - 2.0 ** -12
    pts[2, 0] = 1.0
    pts[3, 1] = 3.0
    pts[4, :] = 0.25
    pts[5, 2] = -0.5
    q = np.zeros((2, 4), np.float32)
    q[1, 1] = 3.0
    got = reference.exact_range(pts, q, 1.0)
    assert got[0].tolist() == [1, 2, 4, 5]
    assert got[1].tolist() == [3]
    import jax.numpy as jnp
    bf = jnp.asarray(pts, jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.sum(bf[0] ** 2)) <= 1.0   # what bfloat16 would keep


def test_exact_range_matches_a_full_float64_scan():
    rng = np.random.default_rng(3)
    pts = rng.standard_normal((3000, 16)).astype(np.float32)
    qs = rng.standard_normal((40, 16)).astype(np.float32)
    r = 9.0
    got = reference.exact_range(pts, qs, r, block=7)
    for i, q in enumerate(qs):
        d = ((pts.astype(np.float64) - q.astype(np.float64)) ** 2).sum(1)
        assert np.array_equal(np.nonzero(d <= r)[0], got[i])


def test_average_precision_by_hand():
    t = [np.array([1, 2, 3, 4]), np.array([], np.int64), np.array([7])]
    a = [np.array([1, 2, 9]), np.array([5]), np.array([7])]
    assert reference.average_precision(t, a) == pytest.approx(3 / 5)
    assert reference.average_precision([np.array([], np.int64)],
                                       [np.array([1])]) == 1.0


def test_compare_flags_wrong_answers():
    pts = np.zeros((4, 2), np.float32)
    pts[1, 0] = 0.5
    pts[2, 0] = 1.0 + 2.0 ** -10
    pts[3, 0] = 3.0
    q = np.zeros((1, 2), np.float32)
    ok = reference.compare(pts, q, 1.0, [(0, np.array([0, 1]))])
    assert ok["ap"] == 1.0 and ok["max_excess"] == 0.0
    assert ok["bad_ids"] == 0 and ok["false_positives"] == 0
    out = reference.compare(pts, q, 1.0, [(0, np.array([0, 2]))])
    assert out["false_positives"] == 1
    assert out["max_excess"] == pytest.approx((1 + 2.0 ** -10) ** 2 - 1)
    assert out["ap"] == pytest.approx(0.5)
    bad = reference.compare(pts, q, 1.0, [(0, np.array([0, 0, 1])),
                                          (0, np.array([7]))])
    assert bad["bad_ids"] == 2


def test_frozen_generator_and_radius_are_pinned():
    p, q = corpus.make_corpus(_cfg()["profile"], 2000, 256, 0, 1)
    digest = hashlib.sha256(p.tobytes() + q.tobytes()).hexdigest()
    assert digest == ("255174e7a2e3d0446fc785bcfdd1973e"
                      "4aeaeb303deb3e7452625ba2f1e4ab48")
    sel = radius.select_radius(p, q)
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
    assert radius.select_radius(p, q[:256])["radius"] == pytest.approx(
        cfg["radius"], rel=1e-6)


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
