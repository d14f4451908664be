"""Dispatch wrapper for the rerank-fetch kernel.

`use_pallas=False` runs the XLA reference; `use_pallas=True,
interpret=True` emulates the kernel on CPU for the parity suite. The TPU
compiler refuses the kernel (its `(1, 16)` output block on a `(16, 16)`
array), and nothing calls this wrapper: the tiered corpus fetches its rows
with `jax.device_put` on every platform.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import fetch_rerank_dists_pallas
from .ref import fetch_rerank_dists_ref


@partial(jax.jit, static_argnames=("metric", "use_pallas", "tile", "interpret"))
def fetch_rerank_dists(
    raw,                  # (N, d) raw f32 rows
    ids,                  # (P,) int32 row ids (pad entries clamped in-range)
    qv,                   # (P, d) pre-gathered per-pair query rows
    *,
    metric: str = "l2",
    use_pallas: bool = False,
    tile: int = 16,
    interpret: bool = False,
) -> jnp.ndarray:
    ids = jnp.clip(jnp.asarray(ids, jnp.int32), 0, raw.shape[0] - 1)
    if not use_pallas:
        return fetch_rerank_dists_ref(raw, ids, qv, metric)
    return fetch_rerank_dists_pallas(raw, ids, qv, metric=metric,
                                     tile=tile, interpret=interpret)
