"""Pallas TPU kernels of the fused frontier expansion: the neighbor-row
gather and the distance reduction in one pass.

For one query and its ``T = E*R`` candidate ids (the adjacency rows of the
E frontier nodes, flattened), a grid step DMAs the T candidate rows
straight from the corpus in HBM (``pl.ANY``) into a ``(T, d)`` VMEM
scratch and reduces them against the query there, so the gathered
``(Q, T, d)`` tile never exists in HBM. The adjacency gather, validity
masks and first-occurrence dedup stay in XLA
(``ref.expand_frontier_1``): they move ``T`` ids, not ``T*d`` values.

Layout rules the TPU compiler (Mosaic) enforces, and how each is met:

* A block's last two dims must divide by (8, 128) or equal the array's.
  Per-query operands are therefore shaped ``(Q, 1, X)`` with ``(1, 1, X)``
  blocks; under ``vmap`` (the search loop) the batch dim is prepended and
  the same rule holds.
* The candidate ids drive DMA addresses, so they are a blocked SMEM input
  (not scalar prefetch: ``vmap`` of a ``pallas_call`` whose scalar-prefetch
  operands are batched falls back to a loop of one-query calls).
* A DMA'd row slice must cover whole tiles of the HBM layout. An f32 row
  with ``d % 128 == 0`` does. An int8 row does not (int8 packs four rows
  per 32-bit word), so the int8 variant reads ``pack_int8_rows``: four
  corpus rows interleaved bytewise in one int32 row of ``D`` lanes.
* Distances come out lane-major (one ``(1, T)`` row per query): both
  kernels reduce the transposed ``(d, T)`` tile over sublanes.

Both use the diff form ``sum((x - q)^2)`` in f32 with the query in f32, as
the XLA reference does, so the paths round alike; the int8 kernel
dequantizes each code row by its scale in VMEM, and the wrapper applies the
certified lower bound. (Quantizing the query for an int8 MXU contraction
widens every bound by the query's own error: measured on a TPU v5e at
100k rows, it cost 0.013 AP against the XLA int8 path.)

Compiled for TPU v5e at N=1M, d=128, R=32 and R=128, E=4, Q=128
(``tests/test_tpu_compile.py``). A bf16 corpus runs only in interpret mode:
its rows, like int8 ones, are packed across sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_rows(ids_ref, src_hbm, dst_vmem, sem):
    """DMA ``src_hbm[ids[j]]`` into ``dst_vmem[j]`` for every j: start all
    copies, then wait for all, so the row fetches overlap."""
    def copy(j):
        return pltpu.make_async_copy(src_hbm.at[pl.ds(ids_ref[0, 0, j], 1)],
                                     dst_vmem.at[pl.ds(j, 1)], sem)

    def start(j, c):
        copy(j).start()
        return c

    def wait(j, c):
        copy(j).wait()
        return c

    jax.lax.fori_loop(0, dst_vmem.shape[0], start, 0)
    jax.lax.fori_loop(0, dst_vmem.shape[0], wait, 0)


def _dist_kernel(ids_ref, q_ref, pts_hbm, out_ref, vec_ref, sem, *, metric):
    _gather_rows(ids_ref, pts_hbm, vec_ref, sem)
    x = vec_ref[...].astype(jnp.float32)      # (T, d)
    q = q_ref[0].astype(jnp.float32)          # (1, d)
    if metric == "l2":
        diff = x - q
        v = diff * diff
    else:  # ip
        v = -(x * q)
    out_ref[0] = jnp.sum(jnp.transpose(v), axis=0, keepdims=True)   # (1, T)


def expand_dists(points, ids, queries, *, metric: str = "l2",
                 interpret: bool = False):
    """(Q, T) distances of ``points[ids]`` to each query. ``ids`` (Q, T)
    int32 must be pre-clamped to [0, N)."""
    d = points.shape[1]
    qn, t = ids.shape
    return pl.pallas_call(
        functools.partial(_dist_kernel, metric=metric),
        grid=(qn,),
        in_specs=[
            pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, 1, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, d), points.dtype),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="expand_dists",
    )(ids[:, None, :], queries[:, None, :], points)[:, 0]


def pack_int8_rows(codes: jnp.ndarray) -> jnp.ndarray:
    """(N, d) int8 codes -> (ceil(N/4), D) int32, D = d rounded up to 128.

    Byte k of word ``[i, c]`` is ``codes[4i + k, c]`` (zero-padded), so one
    lane-aligned int32 row DMA fetches corpus row ``4i + k`` along with its
    three neighbors. One elementwise pass over the codes; the search calls
    it once per dispatch, outside its loop."""
    n, d = codes.shape
    n4 = -(-n // 4) * 4
    lanes = -(-d // 128) * 128
    c = jnp.pad(codes, ((0, n4 - n), (0, lanes - d))).astype(jnp.int32) & 0xFF
    return c[0::4] | (c[1::4] << 8) | (c[2::4] << 16) | (c[3::4] << 24)


def _dist_int8_kernel(rows_ref, seg_ref, scale_ref, q_ref, packed_hbm,
                      out_ref, vec_ref, sem, *, metric):
    _gather_rows(rows_ref, packed_hbm, vec_ref, sem)
    v = vec_ref[...]                          # (T, D) int32, 4 rows per word
    seg = seg_ref[0]                          # (1, T) byte holding each id
    scale = scale_ref[0]                      # (1, T) per-row code scale
    q = q_ref[0]                              # (D, 1) f32 query column
    out = jnp.zeros(seg.shape, jnp.float32)
    for k in range(4):
        # sign-extend byte k, dequantize in f32, reduce over sublanes
        codes = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(v, 24 - 8 * k), 24)
        x = jnp.transpose(codes.astype(jnp.float32)) * scale   # (D, T)
        if metric == "l2":
            diff = x - q
            d = jnp.sum(diff * diff, axis=0, keepdims=True)
        else:  # ip
            d = -jnp.sum(x * q, axis=0, keepdims=True)
        out = out + jnp.where(seg == k, d, 0.0)
    out_ref[0] = out


def expand_dists_int8(packed, scales, ids, queries, *, metric: str = "l2",
                      interpret: bool = False):
    """(Q, T) distances of the dequantized rows ``codes[ids] * scales`` to
    each f32 query: the quantity ``core.corpus.quantized_gather_lb`` bounds.

    ``packed`` is ``pack_int8_rows(codes)``; ``scales`` (Q, T) are the rows'
    code scales; ``ids`` (Q, T) int32 are pre-clamped to [0, N)."""
    lanes = packed.shape[1]
    qn, t = ids.shape
    q = jnp.pad(queries.astype(jnp.float32),
                ((0, 0), (0, lanes - queries.shape[1])))
    return pl.pallas_call(
        functools.partial(_dist_int8_kernel, metric=metric),
        grid=(qn,),
        in_specs=[
            pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, lanes, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, t), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((qn, 1, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, lanes), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
        name="expand_dists_int8",
    )((ids // 4)[:, None, :], (ids % 4)[:, None, :],
      scales.astype(jnp.float32)[:, None, :], q[:, :, None], packed)[:, 0]
