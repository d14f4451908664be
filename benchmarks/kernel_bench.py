"""Kernel microbench: rangescan / gatherdist / expand / flashattn.

Wall-clock on CPU is meaningless for TPU kernels, so this reports two
things per shape: (a) XLA-path wall time (the ref oracle jit'd — a real
measurement of the fallback used on CPU), and (b) the v5e roofline-term
ESTIMATE for the Pallas kernel (FLOPs / bytes analytically from the tiling,
against 197 TFLOP/s + 819 GB/s), which is what the TPU deployment would be
bounded by. The expand section additionally times the *unfused* expansion
dataflow (adjacency gather + vector gather + distance + broadcast dedups —
what the search loop ran before the fused path) against the fused oracle,
and runs the Pallas kernel itself in interpret mode on CPU (compiled on a
real TPU) as a correctness-exercising smoke measurement.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import HBM_BW, PEAK_FLOPS, corpus_bytes_per_distance
from repro.core import quantize_corpus
from repro.kernels import (
    expand_frontier, expand_frontier_ref, flash_attention_ref,
    gatherdist_ref, rangescan_ref,
)
from repro.utils import INVALID_ID, block_until_ready
from .common import print_table


def _wall(fn, iters=3):
    block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run():
    rows = []
    key = jax.random.PRNGKey(0)

    # rangescan: retrieval_cand-ish shapes
    for (q, n, d) in [(16, 100_000, 128), (1, 1_000_000, 256)]:
        qs = jax.random.normal(key, (q, d), jnp.float32)
        xs = jax.random.normal(key, (n, d), jnp.float32)
        f = jax.jit(lambda a, b: rangescan_ref(a, b, jnp.float32(1.0), k=128))
        t = _wall(lambda: f(qs, xs))
        flops = 2.0 * q * n * d
        byts = 4.0 * (q * d + n * d + q * n)
        rows.append(["rangescan", f"{q}x{n}x{d}", t * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])

    # gatherdist: beam expansion shapes, f32 rows vs the int8 quantized
    # corpus (codes + 12B metadata — the v5e memory term drops ~4x; that
    # roofline column, not the CPU wall ms, is the claim of record)
    for (q, r, n, d) in [(256, 32, 100_000, 128), (1024, 64, 100_000, 96)]:
        pts = jax.random.normal(key, (n, d), jnp.float32)
        qs = jax.random.normal(key, (q, d), jnp.float32)
        ids = jax.random.randint(key, (q, r), 0, n, jnp.int32)
        f = jax.jit(lambda p, i, u: gatherdist_ref(p, i, u))
        t = _wall(lambda: f(pts, ids, qs))
        flops = 3.0 * q * r * d
        byts = 4.0 * (q * r * d + q * d + q * r)
        rows.append(["gatherdist", f"{q}x{r}x{d}", t * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])
        qc = quantize_corpus(pts)
        f8 = jax.jit(lambda i, u: gatherdist_ref(qc, i, u))
        t8 = _wall(lambda: f8(ids, qs))
        byts8 = (q * r * corpus_bytes_per_distance(d, "int8")
                 + 4.0 * (q * d + q * r))
        rows.append(["gatherdist(int8)", f"{q}x{r}x{d}", t8 * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts8 / HBM_BW * 1e6])

    # expand: fused multi-node frontier expansion vs the unfused dataflow
    def unfused_expand(points, neighbors, frontier, queries):
        """The pre-fusion search-loop expansion: row gather, vector gather,
        distance, then three O(T^2)-ish broadcast dedups."""
        n = points.shape[0]
        f_ok = (frontier >= 0) & (frontier < n)
        rows = jnp.take(neighbors, jnp.where(f_ok, frontier, 0), axis=0)
        flat = jnp.where(f_ok[..., None], rows, INVALID_ID)
        flat = flat.reshape(frontier.shape[0], -1)              # (Q, E*R)
        d = gatherdist_ref(points, flat, queries)
        t = jnp.arange(flat.shape[1])
        dup = jnp.any((flat[:, :, None] == flat[:, None, :])
                      & (t[None, None, :] < t[None, :, None])
                      & (flat[:, :, None] != INVALID_ID), axis=2)
        return jnp.where(dup, INVALID_ID, flat), jnp.where(dup, jnp.inf, d)

    for (q, e, n, r, d) in [(256, 4, 100_000, 64, 128), (64, 8, 100_000, 32, 96)]:
        pts = jax.random.normal(key, (n, d), jnp.float32)
        nbrs = jax.random.randint(key, (n, r), 0, n, jnp.int32)
        qs = jax.random.normal(key, (q, d), jnp.float32)
        fr = jax.random.randint(jax.random.PRNGKey(e), (q, e), 0, n, jnp.int32)
        f_fused = jax.jit(lambda p, g, f, u: expand_frontier_ref(p, g, f, u))
        f_unfused = jax.jit(unfused_expand)
        t_f = _wall(lambda: f_fused(pts, nbrs, fr, qs))
        t_u = _wall(lambda: f_unfused(pts, nbrs, fr, qs))
        flops = 3.0 * q * e * r * d
        byts = 4.0 * (q * e * r * d + q * d + q * e * r * 2)
        rows.append(["expand(fused)", f"{q}x{e}x{r}x{d}", t_f * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])
        rows.append(["expand(unfused)", f"{q}x{e}x{r}x{d}", t_u * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])
        # int8 corpus through both dataflows (certified lower-bound
        # distances): the unfused-int8 row routes unfused_expand through
        # the same quantized gather, so fused-vs-unfused at int8 isolates
        # the fusion while int8-vs-f32 per dataflow isolates the dtype
        qc = quantize_corpus(pts)
        f_fused8 = jax.jit(lambda g, f, u: expand_frontier_ref(qc, g, f, u))
        f_unfused8 = jax.jit(lambda g, f, u: unfused_expand(qc, g, f, u))
        t_f8 = _wall(lambda: f_fused8(nbrs, fr, qs))
        t_u8 = _wall(lambda: f_unfused8(nbrs, fr, qs))
        byts8 = (q * e * r * corpus_bytes_per_distance(d, "int8")
                 + 4.0 * (q * d + q * e * r * 2))
        rows.append(["expand(fused,int8)", f"{q}x{e}x{r}x{d}", t_f8 * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts8 / HBM_BW * 1e6])
        rows.append(["expand(unfused,int8)", f"{q}x{e}x{r}x{d}", t_u8 * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts8 / HBM_BW * 1e6])

    # the Pallas expand kernel itself: interpret mode on CPU (the DMAs are
    # emulated — wall time is an upper bound, not a TPU prediction)
    pts = jax.random.normal(key, (2_000, 64), jnp.float32)
    nbrs = jax.random.randint(key, (2_000, 16), 0, 2_000, jnp.int32)
    qs = jax.random.normal(key, (4, 64), jnp.float32)
    fr = jax.random.randint(jax.random.PRNGKey(7), (4, 4), 0, 2_000, jnp.int32)
    interp = jax.default_backend() != "tpu"  # compiled only where it lowers
    t_k = _wall(lambda: expand_frontier(pts, nbrs, fr, qs, use_pallas=True,
                                        interpret=interp), iters=1)
    flops = 3.0 * 4 * 4 * 16 * 64
    byts = 4.0 * (4 * 4 * 16 * 64 + 4 * 64 + 4 * 4 * 16 * 2)
    rows.append(["expand(pallas)" + ("[interp]" if interp else ""),
                 "4x4x16x64", t_k * 1e3,
                 flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])
    # the int8 Pallas expand kernel (packed code-row DMA + VMEM dequant),
    # same interpret-mode caveat
    qc_small = quantize_corpus(pts)
    t_k8 = _wall(lambda: expand_frontier(qc_small, nbrs, fr, qs,
                                         use_pallas=True, interpret=interp),
                 iters=1)
    byts8 = (4 * 4 * 16 * corpus_bytes_per_distance(64, "int8")
             + 4.0 * (4 * 64 + 4 * 4 * 16 * 2))
    rows.append(["expand(pallas,int8)" + ("[interp]" if interp else ""),
                 "4x4x16x64", t_k8 * 1e3,
                 flops / PEAK_FLOPS * 1e6, byts8 / HBM_BW * 1e6])

    # flashattn: prefill + decode shapes (small batch; CPU wall time)
    for (b, hq, hkv, sq, skv, dh) in [(1, 8, 2, 1024, 1024, 128),
                                      (4, 8, 2, 1, 8192, 128)]:
        q = jax.random.normal(key, (b, hq, sq, dh), jnp.bfloat16)
        k = jax.random.normal(key, (b, hkv, skv, dh), jnp.bfloat16)
        v = jax.random.normal(key, (b, hkv, skv, dh), jnp.bfloat16)
        f = jax.jit(lambda a, c, e: flash_attention_ref(a, c, e))
        t = _wall(lambda: f(q, k, v))
        flops = 4.0 * b * hq * sq * skv * dh
        byts = 2.0 * (b * hq * sq * dh + 2 * b * hkv * skv * dh)
        rows.append(["flashattn", f"b{b}h{hq}/{hkv}s{sq}/{skv}", t * 1e3,
                     flops / PEAK_FLOPS * 1e6, byts / HBM_BW * 1e6])

    print_table("kernel bench: CPU-XLA wall ms + v5e roofline-term estimate",
                ["kernel", "shape", "cpu_ms", "v5e_compute_us", "v5e_mem_us"],
                rows)
    return rows


if __name__ == "__main__":
    run()
