"""jit'd public wrapper for gatherdist (flatten + clamp + mask)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...utils import INVALID_ID
from .kernel import gatherdist_pallas, gatherdist_pallas_int8
from .ref import gatherdist_ref


@partial(jax.jit, static_argnames=("metric", "use_pallas", "interpret"))
def gatherdist(
    points,                # (N, d) array, or a core.corpus.QuantizedCorpus
    ids: jnp.ndarray,      # (Q, R) int32 (INVALID_ID-padded)
    queries: jnp.ndarray,  # (Q, d)
    *,
    metric: str = "l2",
    use_pallas: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """(Q, R) fused gather+distance; invalid ids map to +inf.

    A quantized corpus (duck-typed via ``.codes``) routes to the int8
    kernel variant (int8 row stream + MXU int8 dot + accumulator dequant).
    """
    quant = getattr(points, "codes", None) is not None
    if not use_pallas:
        return gatherdist_ref(points, ids, queries, metric=metric)
    qn, r = ids.shape
    n = (points.codes if quant else points).shape[0]
    valid = (ids != INVALID_ID) & (ids < n)
    flat_ids = jnp.where(valid, ids, 0).reshape(-1)
    qidx = jnp.broadcast_to(jnp.arange(qn, dtype=jnp.int32)[:, None], (qn, r)).reshape(-1)
    if quant:
        d = gatherdist_pallas_int8(points.codes, points.meta, flat_ids, qidx,
                                   queries, metric=metric,
                                   interpret=interpret).reshape(qn, r)
    else:
        d = gatherdist_pallas(points, flat_ids, qidx, queries, metric=metric,
                              interpret=interpret).reshape(qn, r)
    return jnp.where(valid, d, jnp.inf)
