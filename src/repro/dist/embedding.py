"""Row-sharded embedding lookup over the mesh.

The recsys (F, V, d) field tables are the largest arrays in the system
(two-tower: 16 fields x 10.5M rows). They shard over the *vocab* row axis
across the whole mesh; a lookup becomes: every device resolves the ids that
land in its row range and contributes zeros elsewhere, and one ``psum``
assembles the full (B, F, d) activation — the shard_map formulation of the
one-hot-matmul identity that GSPMD uses for sharded gathers.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def sharded_lookup(mesh: Mesh, tables, idx, *, axis=("data", "model")):
    """tables (F, V, d) row-sharded over ``axis``; idx (B, F) replicated
    -> (B, F, d) replicated. ``axis`` is one mesh axis name or a tuple
    (sharding V over their product, major-to-minor in tuple order)."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    sizes = [mesh.shape[a] for a in axes]

    def local_fn(tab, ix):
        # linear shard index in PartitionSpec order
        lin = jnp.zeros((), jnp.int32)
        for a, s in zip(axes, sizes):
            lin = lin * s + jax.lax.axis_index(a)
        v_local = tab.shape[1]
        loc = ix - lin * v_local
        valid = (loc >= 0) & (loc < v_local)
        safe = jnp.where(valid, loc, 0)
        rows = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                        in_axes=(0, 1), out_axes=1)(tab, safe)  # (B, F, d)
        rows = jnp.where(valid[..., None], rows, 0)
        return jax.lax.psum(rows, axes)

    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(P(None, axes, None), P(None, None)),
                   out_specs=P(None, None, None), check_vma=False)
    return fn(tables, jnp.asarray(idx, jnp.int32))
