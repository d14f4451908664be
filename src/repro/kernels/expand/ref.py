"""Pure-jnp oracle for the fused frontier-expand kernel.

Semantics (shared with the Pallas kernel):

* frontier entries that are INVALID_ID or out of range yield all-INVALID
  rows (no distances, no n_dist contribution);
* every valid adjacency entry gets a distance (this is what ``n_dist``
  counts — it is the number of distance computations performed, duplicates
  included, matching the unfused path's accounting);
* only the **first occurrence** of each neighbor id within the flattened
  E*R tile survives; later duplicates are masked to INVALID/+inf.

The Pallas path (``ops.expand_frontier``) runs this same function with the
kernel as its distance backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...utils import INVALID_ID


def expand_frontier_1(
    points,                  # (N, d) corpus (any float dtype; math in f32)
                             # or a core.corpus.QuantizedCorpus (duck-typed
                             # via .codes to keep kernels import-cycle-free)
    neighbors: jnp.ndarray,  # (N, R) int32 adjacency, INVALID_ID padded
    frontier: jnp.ndarray,   # (E,) int32 nodes to expand (INVALID_ID padded)
    q: jnp.ndarray,          # (d,) query
    metric: str = "l2",
    dists_fn=None,
):
    """Single-query fused expansion -> (ids (E*R,), dists (E*R,), n_dist ()).

    ``dists_fn(safe_ids (E*R,), q) -> (E*R,)`` computes the candidate
    distances; the default is the XLA gather below, and ``ops`` passes the
    Pallas kernel. Masks and dedup are the same for both.

    An int8 quantized corpus gathers 1-byte codes + a 12-byte metadata row
    per candidate (the ~4x HBM saving), dequantizes in-register, and
    returns each candidate's *certified lower-bound* distance
    (``core.corpus.lower_bound_dists``) so the search loop's ``dist <= r``
    tests keep a provable superset at the original radius.
    """
    quant = getattr(points, "codes", None) is not None
    n = (points.codes if quant else points).shape[0]
    f_ok = (frontier >= 0) & (frontier < n)
    rows = jnp.take(neighbors, jnp.where(f_ok, frontier, 0), axis=0)  # (E, R)
    flat = jnp.where(f_ok[:, None], rows, INVALID_ID).reshape(-1)     # (E*R,)

    valid = (flat >= 0) & (flat < n)
    safe = jnp.where(valid, flat, 0)
    if dists_fn is not None:
        d = dists_fn(safe, q)
    elif quant:
        from ...core.corpus import quantized_gather_lb
        d = quantized_gather_lb(points, safe, q.astype(jnp.float32), metric)
    else:
        qf = q.astype(jnp.float32)
        vecs = jnp.take(points, safe, axis=0).astype(jnp.float32)  # (E*R, d)
        if metric == "l2":
            diff = vecs - qf[None, :]
            d = jnp.sum(diff * diff, axis=-1)
        else:  # ip
            d = -(vecs @ qf)

    dup = _first_occurrence_dup(flat, valid)
    keep = valid & ~dup
    ids = jnp.where(keep, flat, INVALID_ID)
    dists = jnp.where(keep, d, jnp.inf)
    return ids, dists, jnp.sum(valid).astype(jnp.int32)


def _first_occurrence_dup(flat: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """First-occurrence dedup as one vectorized (T, T) compare. (A
    sort-based O(T log T) dedup was tried and lost in-loop: XLA's sort
    comparator costs far more per element than a broadcast compare at tile
    sizes of a few hundred.)"""
    t = jnp.arange(flat.shape[0])
    return jnp.any(
        (flat[:, None] == flat[None, :])
        & (t[None, :] < t[:, None])
        & valid[None, :] & valid[:, None],
        axis=1,
    )


def expand_frontier_ref(points, neighbors, frontier, queries, *, metric: str = "l2"):
    """Batched oracle: frontier (Q, E), queries (Q, d) ->
    (ids (Q, E*R), dists (Q, E*R), n_dist (Q,))."""
    fn = lambda f, q: expand_frontier_1(points, neighbors, f, q, metric)
    return jax.vmap(fn)(frontier, queries)
