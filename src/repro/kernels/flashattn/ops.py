"""jit'd public wrapper for flash attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


@partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "q_offset", "scale",
    "block_q", "block_k", "use_pallas", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # (B, Hq, Sq, dh)
    k: jnp.ndarray,  # (B, Hkv, Skv, dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    if not use_pallas:
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, q_offset=q_offset, scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, q_offset=q_offset, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
