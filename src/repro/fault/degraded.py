"""Fault-tolerant sharded range search: host fan-out with degradation.

The collective path (``dist.sharded_range_search``) assumes every shard
answers; one ``shard_map`` program either completes or fails as a unit.
This module is the serving-side alternative: shards are searched
independently from the host — concurrently, one worker thread per shard —
so a shard that times out, errors, or returns garbage degrades the answer
instead of destroying it.

Per shard: retry with jittered, capped exponential backoff for transient
faults, validate every answer against invariants no honest shard can
violate (ids inside the shard's global range, finite in-radius distances,
consistent counts), and on exhaustion mark the shard lost in a validity
mask. The union merge runs over surviving shards only, **in shard order**
regardless of thread completion order, so the merged result is bitwise
independent of scheduling. Because the shards partition the corpus and
each per-shard search is deterministic, the merged result over surviving
shards is **exact-mode-identical** to a healthy run restricted to those
shards — degradation truncates coverage, never corrupts results.

With replication (``fleet=``, see :mod:`repro.fault.replica`) the
per-shard worker additionally fails over across replicas, hedges slow
primaries, and respects per-replica circuit breakers; a shard is lost
only when *every* replica of it is exhausted.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.beam_search import broadcast_radius
from ..core.graph import Graph
from ..core.labels import LabelFilter
from ..core.range_search import RangeConfig, RangeResult, range_search_fused
from ..dist.sharded_engine import (
    ShardedCorpus, _remap_global, shard_view, union_merge,
)
from ..tier import TierFetchError
from ..utils import INVALID_ID
from .errors import SHARD_LOST
from .injector import FaultInjector, ShardFault


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Transient-fault retry: ``max_attempts`` tries per shard, sleeping
    ``min(backoff_s * backoff_factor**attempt, backoff_max_s)`` between
    them (``backoff_s=0`` = no sleep, the right setting under test where
    faults are scripted, not timed). ``jitter > 0`` stretches each delay
    by a uniform factor in ``[1, 1 + jitter]`` drawn from a counter-based
    seeded stream (key = ``[seed, shard, attempt]``), so retries across
    shards de-synchronize deterministically instead of thundering-herding
    a recovering shard; the default ``jitter=0.0`` keeps delays exact.

    Also carries the result-validation tolerances (``atol``, ``rtol``)
    used by :func:`validate_shard_result` on this retry path: a distance
    is in-radius up to ``atol + rtol * r``. Distances scale with the
    radius, so a purely absolute tolerance mislabels honest large-radius
    int8 answers as garbage; the relative term tracks the float error
    actually accrued. Plumbed through ``RangeServer(retry=)``.
    """

    max_attempts: int = 3
    backoff_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_max_s: float = 5.0
    jitter: float = 0.0
    seed: int = 0
    atol: float = 1e-4
    rtol: float = 1e-5

    def delay_s(self, attempt: int, key: int = 0) -> float:
        """Backoff before retrying ``attempt`` (0-based), for shard ``key``."""
        d = min(self.backoff_s * self.backoff_factor ** attempt,
                self.backoff_max_s)
        if self.jitter > 0.0 and d > 0.0:
            u = float(np.random.default_rng(
                [int(self.seed), int(key), int(attempt)]).random())
            d *= 1.0 + self.jitter * u
        return d


@dataclasses.dataclass
class DegradedResult:
    """A merged RangeResult plus the per-shard health that produced it."""

    result: RangeResult
    shard_ok: np.ndarray        # (S,) bool — shard's results present in the merge
    attempts: np.ndarray        # (S,) int32 — search attempts per shard
    faults: List[Optional[str]]  # last injected/observed fault kind per shard

    @property
    def shards_total(self) -> int:
        return int(self.shard_ok.shape[0])

    @property
    def shards_ok(self) -> int:
        return int(self.shard_ok.sum())

    @property
    def complete(self) -> bool:
        return self.shards_ok == self.shards_total

    @property
    def coverage(self) -> float:
        """Fraction of shards contributing to the merge (3/4 when one of
        four shards is lost — the corpus fraction actually searched)."""
        return self.shards_ok / max(1, self.shards_total)

    @property
    def code(self) -> Optional[str]:
        return None if self.complete else SHARD_LOST


def validate_shard_result(
    res: RangeResult,
    offset: int,
    shard_rows: int,
    n_total: int,
    radii: np.ndarray,
    atol: float = 1e-4,
    rtol: float = 0.0,
) -> bool:
    """Invariants no honest shard can violate (``res`` already global-id):

    - every valid id lies inside the shard's global row range and the corpus;
    - every valid distance is finite, non-negative, and within the lane's
      radius up to ``atol + rtol * r`` (the relative term because float
      error scales with the radius — see :class:`RetryPolicy`);
    - per-lane counts never exceed the result buffer.

    A shard returning garbage (bit flips, wrong shard's rows, stale radius)
    fails here and is treated like any other transient fault — the merge
    never trusts an unvalidated answer.
    """
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    valid = ids != INVALID_ID
    lo, hi = int(offset), min(int(offset) + int(shard_rows), int(n_total))
    if np.any(valid & ((ids < lo) | (ids >= hi))):
        return False
    d = np.where(valid, dists, 0.0)
    if not np.all(np.isfinite(d)) or np.any(d < 0):
        return False
    r = np.asarray(radii, np.float32).reshape(-1, 1)
    if np.any(valid & (dists > r + (atol + rtol * r))):
        return False
    if np.any(np.asarray(res.count) > ids.shape[1]):
        return False
    return True


def _corrupt_result(res: RangeResult, rng: np.random.Generator) -> RangeResult:
    """Deterministically garble a result the way a sick shard would:
    random out-of-range ids plus a guaranteed-invalid negative distance,
    so validation MUST catch it (no lucky passes)."""
    ids = rng.integers(0, 2**31 - 2, size=np.asarray(res.ids).shape, dtype=np.int32)
    dists = rng.uniform(-1.0, 1.0, size=np.asarray(res.dists).shape).astype(np.float32)
    dists[:, 0] = -1.0  # airtight: a negative distance is never valid
    return dataclasses.replace(
        res, ids=jnp.asarray(ids), dists=jnp.asarray(dists),
        count=jnp.full_like(res.count, ids.shape[1]))


def _search_one_shard(corpus: ShardedCorpus, s: int, queries, radii, cfg,
                      es_vec, tombstones,
                      label_filter: Optional[LabelFilter] = None) -> RangeResult:
    """Exact per-shard search with shard-local ids remapped to global —
    the same per-shard program the collective path runs, minus the mesh.
    A tiered corpus composes shard ``s``'s host store back onto its slice
    of the stacked device arm, so the per-shard rerank fetches that
    shard's raw rows (shard-local slot space) before the global remap."""
    shard_pts = jax.tree.map(lambda x: shard_view(x, s), corpus.points)
    tiers = getattr(corpus, "tiers", None)
    if tiers is not None:
        shard_pts = tiers[s].with_device(shard_pts)
    res = range_search_fused(
        corpus=shard_pts, graph=Graph(neighbors=shard_view(corpus.neighbors, s)),
        queries=queries, start_ids=shard_view(corpus.start_ids, s), r=radii,
        cfg=cfg,
        es_radius=es_vec,
        tombstones=None if tombstones is None else tombstones[s],
        labels=None if label_filter is None else corpus.labels[s],
        label_filter=label_filter)
    gids = _remap_global(res.ids, corpus.offsets[s], corpus.n_total)
    res = dataclasses.replace(
        res, ids=gids,
        dists=jnp.where(gids == INVALID_ID, jnp.inf, res.dists),
        count=jnp.sum(gids != INVALID_ID, axis=1).astype(jnp.int32))
    # a shard placed on its own device answers there; merge on the default
    return jax.device_put(res, jax.devices()[0])


def merge_shard_results(per_shard: List[Optional[RangeResult]],
                        shard_ok: np.ndarray, n_q: int,
                        cap: int) -> RangeResult:
    """Union-merge surviving shards' results, in shard order.

    The merge is a pure function of the surviving results and their shard
    order — never of which thread or replica produced them — which is what
    makes the concurrent/replicated paths bitwise-identical to the serial
    single-replica reference.
    """
    ok = [per_shard[s] for s in range(len(per_shard)) if shard_ok[s]]
    if not ok:  # every shard lost: an empty (but well-formed) result
        return RangeResult(
            ids=jnp.full((n_q, cap), INVALID_ID, jnp.int32),
            dists=jnp.full((n_q, cap), jnp.inf, jnp.float32),
            count=jnp.zeros(n_q, jnp.int32),
            overflow=jnp.zeros(n_q, bool),
            n_visited=jnp.zeros(n_q, jnp.int32),
            n_dist=jnp.zeros(n_q, jnp.int32),
            es_stopped=jnp.zeros(n_q, bool),
            phase2=jnp.zeros(n_q, bool),
            n_rerank=jnp.zeros(n_q, jnp.int32),
        )
    ids = jnp.concatenate([p.ids for p in ok], axis=1)
    dists = jnp.concatenate([p.dists for p in ok], axis=1)
    if ids.shape[1] < cap:  # fewer candidates than the cap: pad the merge
        pad = cap - ids.shape[1]
        ids = jnp.concatenate(
            [ids, jnp.full((n_q, pad), INVALID_ID, ids.dtype)], axis=1)
        dists = jnp.concatenate(
            [dists, jnp.full((n_q, pad), jnp.inf, dists.dtype)], axis=1)
    ids, dists = union_merge(ids, dists, cap)
    total = sum(p.count for p in ok)
    return RangeResult(
        ids=ids,
        dists=dists,
        count=jnp.minimum(total, cap).astype(jnp.int32),
        overflow=jnp.logical_or(
            sum(p.overflow.astype(jnp.int32) for p in ok) > 0,
            total > cap),
        n_visited=sum(p.n_visited for p in ok),
        n_dist=sum(p.n_dist for p in ok),
        es_stopped=sum(p.es_stopped.astype(jnp.int32) for p in ok) > 0,
        phase2=sum(p.phase2.astype(jnp.int32) for p in ok) > 0,
        n_rerank=sum(p.n_rerank for p in ok),
    )


def run_shard_workers(fn: Callable[[int], object], s_total: int,
                      max_workers: Optional[int]) -> List[object]:
    """Run ``fn(s)`` for every shard, returning outcomes indexed by shard.

    ``max_workers=None`` sizes the pool to the shard count; ``0`` runs
    serially on the calling thread — the reference path the determinism
    tests compare the threaded fan-out against.
    """
    if max_workers is None:
        max_workers = s_total
    if max_workers <= 0 or s_total <= 1:
        return [fn(s) for s in range(s_total)]
    with ThreadPoolExecutor(max_workers=min(max_workers, s_total)) as pool:
        return list(pool.map(fn, range(s_total)))


def fault_tolerant_sharded_search(
    *,
    corpus: Optional[ShardedCorpus] = None,
    queries,
    r,
    cfg: RangeConfig,
    es_radius=None,
    tombstones=None,
    label_filter: Optional[LabelFilter] = None,
    injector: Optional[FaultInjector] = None,
    retry: Optional[RetryPolicy] = None,
    sleep: Callable[[float], None] = time.sleep,
    max_workers: Optional[int] = None,
    fleet=None,
    hedge=None,
) -> DegradedResult:
    """Union range search over ``corpus`` that survives shard loss.

    Shards are searched concurrently (host fan-out, one worker per shard;
    ``max_workers=0`` forces the serial reference path). Injected or
    observed faults retry up to ``retry.max_attempts`` with jittered,
    capped exponential backoff; answers are validated before they may join
    the merge, and a shard that exhausts its retries is marked lost rather
    than failing the query. The returned :class:`DegradedResult` carries
    the merged global ``RangeResult`` over surviving shards plus the
    per-shard validity mask / attempt counts; ``coverage`` is
    ``shards_ok / shards_total``.

    ``label_filter`` is a per-query :class:`~repro.core.labels.LabelFilter`
    over the corpus's attached labels (``build_sharded(..., labels=)``);
    each shard evaluates the predicate locally at the result stage, exactly
    as the collective path does.

    With ``fleet=`` (a :class:`~repro.fault.replica.ReplicaFleet`) the
    search runs replicated: per-shard failover across R bitwise-identical
    replicas, optional hedging of slow primaries (``hedge=`` a
    :class:`~repro.fault.replica.HedgePolicy`), and per-replica circuit
    breakers; ``corpus`` is then taken from the fleet and the result is a
    :class:`~repro.fault.replica.ReplicatedResult`.

    With every shard healthy the merge is exact-mode-identical to the
    collective ``sharded_range_search`` (same per-shard program, same
    union merge); with shards lost it equals that healthy merge restricted
    to surviving shards. The threaded fan-out merges in shard order, so it
    is bitwise-identical to the serial loop under every fault script.
    """
    if fleet is not None:
        from .replica import replicated_fan_out
        return replicated_fan_out(
            fleet=fleet, queries=queries, r=r, cfg=cfg, es_radius=es_radius,
            tombstones=tombstones, label_filter=label_filter,
            injector=injector, retry=retry, sleep=sleep,
            max_workers=max_workers, hedge=hedge)
    if corpus is None:
        raise ValueError("pass corpus= (or fleet= for replicated search)")
    retry = retry or RetryPolicy()
    if label_filter is not None and corpus.labels is None:
        raise ValueError(
            "corpus has no labels attached; build_sharded(..., labels=) to "
            "use filtered range search")
    queries = jnp.asarray(queries)
    n_q = queries.shape[0]
    radii = broadcast_radius(r, n_q)
    es_vec = broadcast_radius(es_radius, n_q)
    radii_np = np.asarray(radii)
    s_total = corpus.n_shards
    rows = corpus.shard_size
    cap = cfg.result_cap
    offsets_np = np.asarray(corpus.offsets)

    def run_shard(s: int):
        """One shard's retry loop; returns (ok, result, attempts, fault)."""
        offset = int(offsets_np[s])
        fault: Optional[str] = None
        for attempt in range(retry.max_attempts):
            try:
                kind = (injector.raise_if_faulted(s, attempt)
                        if injector is not None else None)
                res = _search_one_shard(
                    corpus, s, queries, radii, cfg, es_vec, tombstones,
                    label_filter)
                if kind == "garbage":
                    res = _corrupt_result(res, injector.rng(s, attempt))
                if not validate_shard_result(
                        res, offset, rows, corpus.n_total, radii_np,
                        atol=retry.atol, rtol=retry.rtol):
                    fault = "garbage"
                    raise ShardFault("garbage", s, attempt)
                return True, res, attempt + 1, fault
            except (ShardFault, TierFetchError) as e:
                # a failed host-store fetch degrades exactly like a lost
                # shard: retry, then annotate — never crash the batch
                fault = getattr(e, "kind", "tier_fetch")
                if attempt + 1 < retry.max_attempts:
                    d = retry.delay_s(attempt, key=s)
                    if d > 0:
                        sleep(d)
        return False, None, retry.max_attempts, fault

    outcomes = run_shard_workers(run_shard, s_total, max_workers)

    shard_ok = np.zeros(s_total, bool)
    attempts = np.zeros(s_total, np.int32)
    faults: List[Optional[str]] = [None] * s_total
    per_shard: List[Optional[RangeResult]] = [None] * s_total
    for s, (ok, res, n_att, fault) in enumerate(outcomes):
        shard_ok[s] = ok
        per_shard[s] = res
        attempts[s] = n_att
        faults[s] = fault

    merged = merge_shard_results(per_shard, shard_ok, n_q, cap)
    return DegradedResult(result=merged, shard_ok=shard_ok,
                          attempts=attempts, faults=faults)
