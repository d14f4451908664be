"""jit'd public wrapper for the expand kernel.

``use_pallas=False`` runs the pure-jnp reference, which is what the search
loop runs by default on every platform (``SearchConfig.use_expand_kernel``
is off). ``use_pallas=True`` runs the Pallas kernels, compiled for the TPU;
``interpret=True`` emulates them on CPU for the kernel tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import expand_dists, expand_dists_int8, pack_int8_rows
from .ref import expand_frontier_1, expand_frontier_ref


def _int8_lower_bounds(corpus, packed, safe, q, metric, interpret):
    """Kernel distances of the dequantized rows -> certified lower bounds,
    exactly as ``core.corpus.quantized_gather_lb`` bounds the XLA ones."""
    from ...core.corpus import lower_bound_dists
    qf = q.astype(jnp.float32)
    meta = jnp.take(corpus.meta, safe, axis=0)            # (T, 3)
    d_hat = expand_dists_int8(packed, meta[None, :, 0], safe[None], qf[None],
                              metric=metric, interpret=interpret)[0]
    return lower_bound_dists(meta, d_hat, jnp.float32(0.0),
                             jnp.sqrt(jnp.sum(qf * qf)), metric)


@partial(jax.jit, static_argnames=("metric", "use_pallas", "interpret"))
def expand_frontier(
    points,                  # (N, d) array, or a core.corpus.QuantizedCorpus
    neighbors: jnp.ndarray,  # (N, R) int32 adjacency (INVALID_ID padded)
    frontier: jnp.ndarray,   # (Q, E) int32 nodes to expand (INVALID_ID padded)
    queries: jnp.ndarray,    # (Q, d)
    *,
    metric: str = "l2",
    use_pallas: bool = True,
    interpret: bool = False,
    packed=None,             # pack_int8_rows(points.codes), if precomputed
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused frontier expansion.

    Returns ``(ids (Q, E*R), dists (Q, E*R), n_dist (Q,))`` where each
    query's tile is first-occurrence-deduped and INVALID/+inf padded, and
    ``n_dist`` counts distances computed (pre-dedup).

    A quantized corpus (duck-typed via ``.codes``) routes to the int8
    kernel, which returns the same certified lower bounds as the XLA
    reference. The search loop passes ``packed`` once per dispatch; without
    it the codes are packed here, on every call.
    """
    if not use_pallas:
        return expand_frontier_ref(points, neighbors, frontier, queries,
                                   metric=metric)
    if getattr(points, "codes", None) is not None:
        if packed is None:
            packed = pack_int8_rows(points.codes)
        dists_fn = partial(_int8_lower_bounds, points, packed, metric=metric,
                           interpret=interpret)
    else:
        dists_fn = lambda safe, q: expand_dists(
            points, safe[None], q[None], metric=metric,
            interpret=interpret)[0]
    fn = lambda f, q: expand_frontier_1(points, neighbors, f, q, metric,
                                        dists_fn=dists_fn)
    return jax.vmap(fn)(frontier, queries)
