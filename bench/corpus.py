"""Frozen copy of the bigann-like corpus generator.

A mixture of Gaussian clusters with power-law sizes plus a uniform
background, on a low-dimensional manifold linearly embedded in ``dim``, with
queries that either probe a cluster (they find matches) or sit off the data
shell (they find none). Copied from the program's synthetic-corpus module so
that a change there cannot move the benchmark's inputs.

One departure: the distribution (cluster centers, cluster sizes, embedding
basis) and the sample drawn from it take separate seeds. The embedding is
multiplied out in float64, so the rows are the same on every machine.

``deployment`` is what a run serves: rows and query pool are one fixed draw
(both seeds set to the configuration's ``distribution_seed``), and the run's
``--seed`` only puts the pool's batches, and the queries inside each, in
another order. Every seed then does the same work: the same index and the
same batches, sent in another order.
"""
from __future__ import annotations

import numpy as np

PROFILE_KEYS = ("dim", "metric", "n_clusters", "zipf_a", "cluster_std",
                "background_frac", "query_hit_frac", "query_std",
                "latent_dim")


def _zipf_sizes(rng, n_items: int, n_clusters: int, a: float) -> np.ndarray:
    w = rng.zipf(a, size=n_clusters).astype(np.float64)
    w = w / w.sum()
    sizes = np.floor(w * n_items).astype(np.int64)
    sizes[0] += n_items - sizes.sum()
    return sizes


def make_corpus(profile: dict, n: int, n_queries: int, distribution_seed: int,
                seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Returns ``(points (n, dim), queries (n_queries, dim))``, float32."""
    p = profile
    rng_dist = np.random.default_rng([distribution_seed, 1])
    rng = np.random.default_rng([seed, 2])
    rng_q = np.random.default_rng([seed, 3])
    ld = min(p["latent_dim"], p["dim"])
    n_clusters = max(4, p["n_clusters"] // 4)
    centers = rng_dist.standard_normal((n_clusters, ld)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)

    n_bg = int(n * p["background_frac"])
    n_cl = n - n_bg
    sizes = _zipf_sizes(rng_dist, n_cl, n_clusters, p["zipf_a"])
    assign = np.repeat(np.arange(n_clusters), sizes)
    lat_cl = centers[assign] + (p["cluster_std"] * rng.standard_normal(
        (n_cl, ld))).astype(np.float32)
    lat_bg = rng.standard_normal((n_bg, ld)).astype(np.float32)
    lat_bg /= np.linalg.norm(lat_bg, axis=1, keepdims=True)
    latent = np.concatenate([lat_cl, lat_bg]).astype(np.float32)
    rng.shuffle(latent, axis=0)

    n_hit = int(n_queries * p["query_hit_frac"])
    probs = sizes / sizes.sum()
    q_assign = rng_q.choice(n_clusters, size=n_hit, p=probs)
    q_hit = centers[q_assign] + (p["query_std"] * rng_q.standard_normal(
        (n_hit, ld))).astype(np.float32)
    q_bg = rng_q.standard_normal((n_queries - n_hit, ld)).astype(np.float32)
    q_bg /= np.linalg.norm(q_bg, axis=1, keepdims=True)
    q_bg *= 1.25
    q_latent = np.concatenate([q_hit, q_bg]).astype(np.float32)
    rng_q.shuffle(q_latent, axis=0)

    if p["metric"] == "ip":
        scale = rng.lognormal(mean=0.0, sigma=0.25,
                              size=(latent.shape[0], 1)).astype(np.float32)
        latent = latent * scale

    basis, _ = np.linalg.qr(rng_dist.standard_normal((p["dim"], ld)))
    basis = basis.astype(np.float32)
    points = _embed(latent, basis)
    points += (0.01 * p["cluster_std"]) * rng.standard_normal(
        points.shape).astype(np.float32)
    queries = _embed(q_latent, basis)
    return points, queries


def _embed(latent: np.ndarray, basis: np.ndarray) -> np.ndarray:
    # in float64 and rounded once, so that every BLAS gives the same rows
    return (latent.astype(np.float64) @ basis.T.astype(np.float64)).astype(
        np.float32)


def deployment(cfg: dict, seed: int, n: int = 0,
               pool: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """``(points, pool)`` of a configuration. The pool is the first ``pool``
    queries of the fixed draw, cut into blocks of the server's
    ``max_batch``; ``seed`` puts the blocks, and the queries inside each
    block, in another order. A closed loop's batches are these blocks, so
    every seed serves the same batches. ``n`` and ``pool`` shrink the draw
    for a rehearsal."""
    ds = cfg["distribution_seed"]
    points, queries = make_corpus(cfg["profile"], n or cfg["n"],
                                  max(cfg["draw_queries"], pool), ds, ds)
    queries = queries[:pool or cfg["pool"]]
    b = cfg["server"]["max_batch"]
    rng = np.random.default_rng([seed, 4])
    blocks = np.arange(len(queries)).reshape(-1, b)
    blocks = rng.permuted(blocks[rng.permutation(len(blocks))], axis=1)
    return points, queries[blocks.ravel()]
