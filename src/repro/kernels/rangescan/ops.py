"""jit'd public wrapper for the rangescan kernel (padding + dispatch)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...utils import round_up
from .kernel import rangescan_pallas
from .ref import rangescan_ref


@partial(jax.jit, static_argnames=("k", "block_q", "block_n", "metric", "use_pallas", "interpret"))
def rangescan(
    queries: jnp.ndarray,   # (Q, d)
    points: jnp.ndarray,    # (N, d)
    r: jnp.ndarray,
    *,
    k: int = 128,
    block_q: int = 128,
    block_n: int = 512,
    metric: str = "l2",
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Fused exact range scan: (ids (Q,k), dists (Q,k), counts (Q,)).

    ``use_pallas=False`` routes to the pure-jnp oracle (the XLA path used for
    dry-run lowering, where Pallas TPU custom calls are unavailable on the
    CPU host platform).
    """
    if not use_pallas:
        return rangescan_ref(queries, points, r, k=k, metric=metric)
    qn, d = queries.shape
    n, _ = points.shape
    bq = min(block_q, max(8, qn))
    qp = round_up(qn, bq)
    np_ = round_up(n, block_n)
    q_pad = jnp.pad(queries, ((0, qp - qn), (0, 0)))
    x_pad = jnp.pad(points, ((0, np_ - n), (0, 0)))
    ids, dists, counts = rangescan_pallas(
        q_pad, x_pad, r, n_total=n, k=k, block_q=bq, block_n=block_n,
        metric=metric, interpret=interpret,
    )
    return ids[:qn], dists[:qn], counts[:qn]
