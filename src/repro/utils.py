"""Small shared utilities: padding, pytree helpers, timing."""
from __future__ import annotations

import os
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel id used for padded slots in id arrays. We deliberately use a large
# positive int32 (not -1) so that ``jnp.take(..., mode="clip")`` and sorts keep
# padded entries at the *end* of ascending id orderings.
INVALID_ID = np.int32(2**31 - 1)
INF = np.float32(np.inf)

# the checkout's own compile-cache directory (listed in .gitignore)
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout: a fixed path, because the directory is part
    of what a later process must find again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def next_pow2(x: int) -> int:
    if x <= 1:
        return 1
    return 1 << (int(x) - 1).bit_length()


def pad_rows(x: np.ndarray, target: int, fill) -> np.ndarray:
    """Pad axis 0 of ``x`` to ``target`` rows with ``fill``."""
    if x.shape[0] == target:
        return x
    pad = np.full((target - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def tree_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_count(tree: Any) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def block_until_ready(tree: Any) -> Any:
    return jax.tree.map(lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x, tree)


def timeit(fn: Callable[[], Any], *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-clock seconds per call of ``fn`` (which must block)."""
    for _ in range(warmup):
        block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


@partial(jax.jit, static_argnames=("axis",))
def masked_min(x: jnp.ndarray, mask: jnp.ndarray, axis: int = -1):
    """Min over ``x`` where ``mask``; returns (value, index). Empty -> (+inf, 0)."""
    masked = jnp.where(mask, x, INF)
    idx = jnp.argmin(masked, axis=axis)
    val = jnp.min(masked, axis=axis)
    return val, idx


def stable_compact_indices(active: jnp.ndarray):
    """Indices that gather active rows to the front (stable), plus inverse.

    Returns (perm, inv_perm, n_active): ``x[perm]`` puts active rows first in
    original order; ``y[inv_perm]`` undoes it.
    """
    # argsort of (not active) is stable in jnp.argsort(kind default is stable
    # for integers); False(0) sorts before True(1) -> active rows first.
    perm = jnp.argsort(jnp.logical_not(active), stable=True)
    inv_perm = jnp.argsort(perm, stable=True)
    return perm, inv_perm, jnp.sum(active.astype(jnp.int32))
