"""`TieredCorpus`: device-resident codes, host-resident rerank rows.

The hot arm (`device`) is what the search loop sees: an int8
`QuantizedCorpus` whose ``raw`` field is None (codes + 12-byte meta only),
or — the degenerate f32/bf16 tier — the cast point array itself. The cold
arm is a `HostRowStore` of exact f32 rows, consumed exclusively by the
guard-band rerank through :meth:`TieredCorpus.exact_pairs`.

Bitwise-parity contract: ``exact_pairs`` returns the *same f32 bits* as
the resident ``_exact_pairs`` for every real (lane, slot) pair. It
assembles the deduplicated rows into a pow2-padded (U_pad, d) device
buffer and computes ``point_dist(take(rows, inverse), take(queries,
lanes))`` — identical per-pair shapes, identical f32 reduction order, so
cache state, fetch bucketing, and eviction history can never change a
result bit.

A `TieredCorpus` is deliberately NOT a pytree: it hashes by identity, so
it can ride in static fields (e.g. `ShardedCorpus.tiers`), and it must
never be passed into jit — public entry points unwrap ``tier.device``.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.corpus import (
    META_BYTES,
    QuantizedCorpus,
    corpus_cast,
    quantize_corpus,
)
from ..core.distances import point_dist
from ..utils import next_pow2
from .budget import MemoryBudget
from .cache import DeviceRowCache
from .planner import plan_fetch
from .store import HostRowStore

# CI memory-cap hook: forces a tiny resident cache (streaming + eviction
# paths) on every default-constructed tier without touching call sites.
_CACHE_ROWS_ENV = "REPRO_TIER_CACHE_ROWS"


@dataclasses.dataclass
class TierCounters:
    """Cumulative fetch-path telemetry for one tier (shared across
    ``with_device`` views, so sharded/live wrappers aggregate for free)."""

    pairs: int = 0            # (lane, slot) rerank pairs planned
    unique_rows: int = 0      # after dedup
    fetched_rows: int = 0     # host→device rows actually copied
    fetched_bytes: int = 0
    fetch_batches: int = 0    # pow2 buckets issued
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0

    @property
    def dedup_ratio(self) -> float:
        return self.pairs / max(1, self.unique_rows)

    @property
    def hit_rate(self) -> float:
        probes = self.cache_hits + self.cache_misses
        return self.cache_hits / max(1, probes)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["dedup_ratio"] = round(self.dedup_ratio, 4)
        d["cache_hit_rate"] = round(self.hit_rate, 4)
        return d


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_at(dst, pos, rows):
    # OOB pos (== dst height) → mode="drop" makes padding a no-op
    return dst.at[pos].set(rows, mode="drop")


@partial(jax.jit, static_argnames=("metric",))
def _pair_dists(rows_u, inv_p, queries, lanes_p, metric: str):
    """Bit-for-bit the resident `_exact_pairs`, with the gather retargeted
    from the full (N, d) raw array to the assembled (U_pad, d) buffer."""
    vecs = jnp.take(rows_u, inv_p, axis=0).astype(jnp.float32)
    qv = jnp.take(queries, lanes_p, axis=0).astype(jnp.float32)
    return point_dist(vecs, qv, metric)


class TieredCorpus:
    """Two-tier corpus: device hot arm + host-RAM raw-row store."""

    is_tiered = True  # duck-typing marker (core never imports this module)

    def __init__(self, device: Any, store: HostRowStore,
                 cache: DeviceRowCache, counters: Optional[TierCounters] = None,
                 fetch_bucket: int = 1024):
        self.device = device
        self.store = store
        self.cache = cache
        self.counters = counters if counters is not None else TierCounters()
        self.fetch_bucket = int(fetch_bucket)

    # -- structure -----------------------------------------------------------
    def with_device(self, device: Any) -> "TieredCorpus":
        """A view with a different hot arm, SHARING store/cache/counters
        (sharded slicing, live snapshot updates)."""
        return TieredCorpus(device, self.store, self.cache, self.counters,
                            self.fetch_bucket)

    @property
    def n(self) -> int:
        return len(self.store)

    @property
    def dim(self) -> int:
        return self.store.dim

    @property
    def quantized(self) -> bool:
        return isinstance(self.device, QuantizedCorpus)

    def raw_array(self) -> jnp.ndarray:
        """Materialize the full host store on device (consolidation /
        checkpointing at test scale — never on the query path)."""
        if not self.quantized:
            return jnp.asarray(self.store.to_array())
        return jax.device_put(self.store.to_array())

    # -- accounting ----------------------------------------------------------
    def budget(self) -> MemoryBudget:
        device: dict = {}
        if self.device is None:
            # detached shard view: the hot arm lives in the ShardedCorpus
            # stack — only this tier's cache + store are attributable
            pass
        elif self.quantized:
            device["codes"] = int(self.device.codes.nbytes)
            device["meta"] = int(self.device.meta.nbytes)
        else:
            device["points"] = int(self.device.nbytes)
        device["row_cache"] = int(self.cache.nbytes)
        return MemoryBudget(device=device,
                            host={"row_store": int(self.store.nbytes)})

    # -- the rerank fetch path ----------------------------------------------
    def exact_pairs(self, queries, ids_p, lanes_p, metric: str,
                    n_real: Optional[int] = None) -> jnp.ndarray:
        """Exact f32 distances for flat pow2-padded (corpus id, lane) pairs.

        Only the first ``n_real`` pairs are planned/fetched (the tail is
        jit padding whose distances are discarded by the caller's keep
        mask); pad inverse entries point at unique 0 so shapes match."""
        ids_np = np.asarray(jax.device_get(ids_p)).astype(np.int64)
        n_pairs = ids_np.size if n_real is None else int(n_real)
        if not self.quantized:
            # degenerate f32/bf16 tier: the hot arm IS the raw data
            return _pair_dists(jnp.asarray(self.device), jnp.asarray(ids_p),
                               queries, jnp.asarray(lanes_p), metric)

        plan = plan_fetch(ids_np[:n_pairs], self.cache, self.fetch_bucket)
        c = self.counters
        if plan is None:  # all-padding call — nothing real to fetch
            u_pad = 1
            rows_u = jnp.zeros((u_pad, self.dim), jnp.float32)
            inv = np.zeros(ids_np.size, np.int32)
            return _pair_dists(rows_u, jnp.asarray(inv), queries,
                               jnp.asarray(lanes_p), metric)
        c.pairs += plan.n_pairs
        c.unique_rows += plan.n_unique
        c.cache_hits += int(plan.hit_mask.sum())
        c.cache_misses += plan.n_miss

        u_pad = next_pow2(plan.n_unique)
        rows_u = jnp.zeros((u_pad, self.dim), jnp.float32)

        def scatter(pos: np.ndarray, rows) -> None:
            nonlocal rows_u
            m = next_pow2(pos.size)
            pos_p = np.full(m, u_pad, np.int32)  # OOB → drop
            pos_p[: pos.size] = pos
            rows_p = jnp.zeros((m, self.dim), jnp.float32)
            rows_p = rows_p.at[: pos.size].set(rows)
            rows_u = _scatter_rows_at(rows_u, jnp.asarray(pos_p), rows_p)

        hit_pos = np.nonzero(plan.hit_mask)[0].astype(np.int32)
        if hit_pos.size:
            scatter(hit_pos, self.cache.rows(plan.hit_lines[plan.hit_mask]))

        # Double-buffered streaming of the miss buckets: the host→device
        # copy for bucket i+1 is issued (async dispatch) while bucket i's
        # device-side scatter runs (`jax.device_put` overlap, on every
        # platform).
        miss_pos = np.nonzero(~plan.hit_mask)[0].astype(np.int32)
        chunks = plan.miss_chunks
        nxt = jax.device_put(self.store.gather(chunks[0])) if chunks else None
        done = 0
        for i, chunk in enumerate(chunks):
            cur = nxt
            if i + 1 < len(chunks):
                nxt = jax.device_put(self.store.gather(chunks[i + 1]))
            scatter(miss_pos[done:done + chunk.size], cur)
            done += chunk.size
            c.fetch_batches += 1
            c.fetched_rows += int(chunk.size)
            c.fetched_bytes += int(chunk.size) * self.dim * 4
            c.cache_evictions += self.cache.insert(chunk, cur)

        inv = np.zeros(ids_np.size, np.int32)
        inv[:n_pairs] = plan.inverse
        return _pair_dists(rows_u, jnp.asarray(inv), queries,
                           jnp.asarray(lanes_p), metric)


def tiered_corpus(points, *, corpus_dtype: str = "int8",
                  cache_rows: Optional[int] = None,
                  resident_mb: Optional[float] = None,
                  fetch_bucket: int = 1024) -> TieredCorpus:
    """Split ``points`` into a `TieredCorpus`.

    ``points`` is an (N, d) array or an already-quantized `QuantizedCorpus`
    (its raw rows move to the host store). For float dtypes the tier is
    degenerate — the hot arm is the cast array, the store exists only so
    insert/consolidate/checkpoint plumbing is uniform, and queries never
    fetch. ``resident_mb`` caps the device row cache in MB (wins over
    ``cache_rows``); with neither given, the default is n/8 rows, and the
    ``REPRO_TIER_CACHE_ROWS`` env var (CI memory-cap job) overrides it.
    """
    if isinstance(points, QuantizedCorpus):
        if points.raw is None:
            raise ValueError("tiered_corpus needs raw rows to populate the "
                             "host store (got QuantizedCorpus with raw=None)")
        raw = np.asarray(jax.device_get(points.raw), np.float32)
        device = dataclasses.replace(points, raw=None)
    elif corpus_dtype in ("int8", "quantized"):
        qc = quantize_corpus(jnp.asarray(points), keep_raw=True)
        raw = np.asarray(jax.device_get(qc.raw), np.float32)
        device = dataclasses.replace(qc, raw=None)
    else:
        arr = jnp.asarray(points)
        raw = np.asarray(jax.device_get(arr), np.float32)
        device = corpus_cast(arr, corpus_dtype)

    n, d = raw.shape
    store = HostRowStore(raw)
    if resident_mb is not None:
        cap = int(resident_mb * (1 << 20)) // max(1, d * 4)
    elif cache_rows is not None:
        cap = int(cache_rows)
    else:
        cap = int(os.environ.get(_CACHE_ROWS_ENV, max(1, n // 8)))
    cache = DeviceRowCache(d, cap)
    return TieredCorpus(device, store, cache, fetch_bucket=fetch_bucket)
