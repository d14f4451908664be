"""RangeServer: the serving layer around the range engine.

Production anatomy (single-process simulation of the real service):

* **admission queue** — requests land with an id + deadline; the batcher
  drains up to ``max_batch`` or until ``max_wait_s`` passes (micro-batching:
  the standard accelerator-serving latency/throughput knob). Radii are
  per-request: a micro-batch freely mixes radii, each lane answered at its
  own (the paper's queries are radius-heterogeneous by nature). Admission is
  **bounded**: beyond ``max_queue`` pending requests, ``submit`` rejects
  (and counts) instead of growing the deque without limit — queue growth
  under overload is a latency bomb, load shedding is the production answer.
* **bucketed dispatch** — batches are padded to power-of-two sizes so jit
  compiles O(log B) programs total.
* **lockstep execution** (default) — one ``range_search_compacted`` program
  per micro-batch: phase 1 (uniform beam) over the batch, compacted
  survivors run the greedy/doubling phase, the whole batch returns together.
* **continuous batching** (``ServerConfig.continuous``) — the tail-latency
  mode. Phase 1 still runs per micro-batch, but λ-saturated lanes hand
  their ``GreedyState`` checkpoints to a persistent ``LaneScheduler`` pool
  advanced ``slice_rounds`` expansions per step; cheap lanes answer at
  phase 1 and leave immediately. A dense-region straggler occupies one pool
  slot while point queries flow past it — it no longer sets the batch's
  critical path. An optional ``EffortPredictor`` splits each drain into a
  cheap wide-batch dispatch and a separate heavy dispatch (predicted match
  count vs ``effort_threshold``); prediction shapes batch composition only,
  results are identical either way.
* **latency accounting** — every response carries ``timings``
  (queue/service/total) and feeds per-op + end-to-end log-bucket
  histograms (``latency_summary()``); tails, not means, are the SLO.
* **multi-shard** — given a mesh + ShardedCorpus, dispatch goes through
  dist.sharded_range_search and merges per-shard unions (lockstep only).
* **live mutation** — given a ``repro.live.LiveIndex``, requests may carry
  ``op="insert"`` / ``op="delete"`` alongside range queries in the same
  admission queue. The batcher applies a micro-batch's mutations first
  (coalesced in arrival order), triggers threshold consolidation, then
  refreshes its **epoch snapshot** and answers the batch's queries against
  that one consistent ``(graph, corpus, tombstones, epoch)`` view — queries
  never observe a half-applied mutation batch. In continuous mode the pool
  drains to completion against the old snapshot before mutations apply
  (consolidation permutes slots; a checkpoint must not cross an epoch).
  Returned ids are external ids.
* **filtered range retrieval** — range requests may carry ``filter_labels``
  (+ ``filter_mode``) when the served corpus is labeled; filtered and
  unfiltered requests share micro-batches (unfiltered/pad lanes ride an
  all-pass predicate, which is bitwise-neutral), inserts may tag their
  vector with labels, and ``stats["filtered_batches"]`` counts batches
  that carried at least one predicate lane.
* per-request stats (visited, distance comps, early-stopped) surface in the
  response for monitoring.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.corpus import corpus_dtype_name
from ..core.engine import RangeSearchEngine
from ..core.labels import LabelFilter, make_label_filter, make_mask
from ..core.range_search import (
    RangeConfig, RangeResult, _maybe_rerank_host, _tier_of, finalize_results,
    greedy_coverage, greedy_lane_done, greedy_resume_batch, greedy_seed_batch,
    range_phase1, range_search_compacted,
)
from ..dist.sharded_engine import ShardedCorpus, sharded_range_search
from ..fault.degraded import RetryPolicy, fault_tolerant_sharded_search
from ..fault.replica import HedgePolicy, ReplicaFleet, ReplicatedCorpus
from ..fault.errors import DEADLINE_EXPIRED, QUEUE_FULL, SHARD_LOST
from ..fault.injector import FaultInjector
from ..utils import INVALID_ID, next_pow2
from .latency import LatencyHistogram
from .scheduler import LaneScheduler, _gather_lanes

#: ops a Request may carry. "count" is the aggregate-only query shape:
#: |S_r(q)| as a per-lane certified match count (post-rerank, the same
#: number a range answer's ``count`` field carries) with NO ids/dists
#: payload — the paper's dedup/count workload. Count requests ride the
#: same admission queue, micro-batches, and search programs as range
#: requests; only the response materialization differs.
REQUEST_OPS = ("range", "count", "insert", "delete")


@dataclasses.dataclass(kw_only=True)
class Request:
    """One unit of admitted work, op-tagged. Construct by keyword.

    ``deadline_s`` is a latency budget in seconds, measured from
    ``submit``: a range request still queued past its budget is shed with
    ``code="deadline_expired"``; one whose phase-2 lane is mid-search is
    force-finalized into a certified partial answer (``complete=False``)
    instead of resumed. ``None`` means no budget (never expires).

    ``filter_labels`` (range op, labeled corpus only) restricts the answer
    to points carrying those labels — ``filter_mode="and"`` requires all of
    them, ``"or"`` any. Filtered and unfiltered requests batch together
    freely (unfiltered lanes ride an all-pass predicate). ``labels``
    (insert op) tags the inserted vector with label ids."""
    req_id: int
    op: str = "range"                   # range | count | insert | delete
    query: Optional[np.ndarray] = None  # range/count/insert: the vector
    radius: Optional[float] = None      # per-request; batches mix radii freely
    deadline_s: Optional[float] = None  # latency budget (seconds from submit)
    delete_ids: Optional[np.ndarray] = None  # delete: external ids to remove
    filter_labels: Optional[np.ndarray] = None  # range: predicate label ids
    filter_mode: str = "and"            # range: "and" | "or" over filter_labels
    labels: Optional[np.ndarray] = None  # insert: label ids of the new vector


@dataclasses.dataclass(kw_only=True)
class Response:
    """Op-tagged answer. ``timings`` decomposes ``latency_s`` into
    queue (submit→drain) and service (drain→response) seconds.

    Degradation surface (``repro.fault``): ``complete`` is False when the
    answer is a certified partial — deadline-truncated search or shard
    loss. ``coverage`` estimates the searched fraction (visited-frontier
    fraction for deadline truncation, ``shards_ok/shards_total`` for shard
    loss; 1.0 when complete). ``code`` carries the machine-readable reason
    from :mod:`repro.fault.errors` (``queue_full`` / ``deadline_expired``
    / ``shard_lost``; None when healthy). Partial results are truncated,
    never corrupted: every returned id is exact-distance-certified within
    the request radius."""
    req_id: int
    op: str = "range"               # range | count | insert | delete | error
    ids: np.ndarray = None          # count op: empty (count-only payload)
    dists: np.ndarray = None
    count: int = 0
    overflow: bool = False
    es_stopped: bool = False
    latency_s: float = 0.0
    radius: float = float("nan")  # the radius this request was answered at
    epoch: int = 0                # index epoch the request was served/applied at
    timings: Optional[dict] = None  # {"queue_s", "service_s", "total_s"}
    complete: bool = True           # False: partial (deadline / shard loss)
    coverage: float = 1.0           # searched fraction estimate (1.0 = full)
    code: Optional[str] = None      # fault.errors taxonomy; None = healthy
    shards_ok: Optional[int] = None     # sharded serving: shards merged
    shards_total: Optional[int] = None  # sharded serving: shards configured
    replicas_ok: Optional[int] = None     # replicated serving: healthy replicas
    replicas_total: Optional[int] = None  # replicated serving: S * R
    filtered: bool = False          # answered under a label predicate


@dataclasses.dataclass
class ServerConfig:
    max_batch: int = 256
    max_wait_s: float = 0.005
    default_radius: float = 1.0
    es_radius_factor: float = 0.0   # >0 enables early stopping at factor*r
    expand_width: int = 0           # DEPRECATED: deploy-time search overrides
                                    # belong on EngineDeployConfig.overrides()
    max_queue: int = 8192           # admission bound; 0 disables admission
                                    # entirely (drain-only maintenance mode)
    auto_consolidate: bool = True   # live engines: threshold consolidation
                                    # between micro-batches
    # -- continuous batching (tail-latency mode) ----------------------------
    continuous: bool = False        # persistent-lane phase-2 scheduling
    lanes: int = 32                 # pool width (rounded up to pow2)
    slice_rounds: int = 8           # greedy expansions per lane per tick
    effort_threshold: float = 64.0  # predicted matches >= this -> heavy bucket

    def __post_init__(self):
        if self.expand_width > 0:
            warnings.warn(
                "ServerConfig.expand_width is deprecated; deploy-time "
                "search overrides belong on "
                "EngineDeployConfig.overrides(expand_width=...)",
                DeprecationWarning, stacklevel=3)


class RangeServer:
    def __init__(
        self,
        engine: Optional[RangeSearchEngine],
        cfg: RangeConfig,
        server_cfg: ServerConfig = ServerConfig(),
        *,
        mesh=None,
        sharded=None,
        live=None,
        effort=None,
        injector: Optional[FaultInjector] = None,
        retry: Optional[RetryPolicy] = None,
        replicas: int = 1,
        hedge: Optional[HedgePolicy] = None,
        clock=time.perf_counter,
    ):
        """``live`` is a ``repro.live.LiveIndex``; it supersedes ``engine``
        (pass ``engine=None``) and enables insert/delete requests.
        ``effort`` is a fitted ``repro.models.EffortPredictor``; continuous
        mode uses it to split each drain into cheap/heavy dispatches.

        Sharded serving without a ``mesh`` (or with an ``injector``) goes
        through the fault-tolerant host fan-out
        (``fault.fault_tolerant_sharded_search``): per-shard retries with
        ``retry`` backoff, validated answers, and graceful degradation on
        permanent shard loss (responses annotated ``shards_ok/shards_total``,
        ``code="shard_lost"``). ``injector`` is a seeded
        ``fault.FaultInjector`` for chaos testing. ``clock`` is the
        monotonic time source for queueing/deadline decisions — injectable
        so deadline tests advance a fake clock deterministically.

        ``replicas=R`` (R > 1) serves ``sharded`` R-way replicated through
        the hedged fan-out (``sharded`` may equivalently be a pre-built
        ``fault.ReplicatedCorpus`` or a ``fault.ReplicaFleet`` to share
        breaker state); ``hedge`` is a ``fault.HedgePolicy`` deriving the
        hedge delay from the fleet's per-shard latency histograms. Replica
        health rides the completeness contract: ``coverage < 1.0`` only
        when every replica of a shard is exhausted, ``code="replica_lost"``
        when the answer is whole but redundancy is degraded. ``step()``
        runs one fleet recovery sweep per micro-batch."""
        if replicas > 1 and sharded is None:
            raise ValueError("replicas > 1 needs a sharded corpus")
        if engine is None and live is None and sharded is None:
            raise ValueError("need an engine, a sharded corpus, or a live index")
        if injector is not None and sharded is None:
            raise ValueError("fault injection targets shards; pass sharded=")
        self.fleet: Optional[ReplicaFleet] = None
        if isinstance(sharded, ReplicaFleet):
            self.fleet = sharded
        elif isinstance(sharded, ReplicatedCorpus):
            self.fleet = ReplicaFleet(sharded)
        elif replicas > 1:
            if sharded is None:
                raise ValueError("replicas > 1 needs a sharded corpus")
            self.fleet = ReplicaFleet(ReplicatedCorpus.replicate(
                sharded, replicas))
        if self.fleet is not None:
            if mesh is not None:
                raise ValueError("replicated serving is host fan-out; "
                                 "drop mesh= or serve unreplicated")
            sharded = self.fleet.corpus.replica(0)
        self.hedge = hedge
        self.engine = engine
        self.live = live
        if server_cfg.expand_width > 0:
            cfg = dataclasses.replace(cfg, search=dataclasses.replace(
                cfg.search, expand_width=server_cfg.expand_width))
        # the declarative SearchConfig.corpus_dtype is a deploy contract:
        # what the config promises must be what the served corpus actually
        # stores (an f32 corpus behind an "int8" config would silently
        # serve at 4x the planned HBM budget, and vice versa would skip
        # the planned rerank stage)
        if live is not None:
            served = live.points
        elif sharded is not None:
            served = sharded.points
        else:
            served = engine.points
        actual = corpus_dtype_name(served)
        if cfg.search.corpus_dtype != actual:
            raise ValueError(
                f"SearchConfig.corpus_dtype={cfg.search.corpus_dtype!r} but "
                f"the served corpus stores {actual!r}")
        self.cfg = cfg
        self.scfg = server_cfg
        self.mesh = mesh
        self.sharded = sharded
        self.effort = effort
        self.injector = injector
        self.retry = retry or RetryPolicy()
        self._clock = clock
        self.queue: deque[tuple[Request, float]] = deque()
        self._view = live.snapshot() if live is not None else None
        self._pool: Optional[LaneScheduler] = None
        if server_cfg.continuous:
            if sharded is not None or mesh is not None:
                raise ValueError("continuous batching is single-shard; "
                                 "drop continuous=True for sharded serving")
            if cfg.mode != "greedy":
                raise ValueError("continuous batching schedules the greedy "
                                 f"phase; cfg.mode={cfg.mode!r}")
            self._pool = LaneScheduler(self._device_corpus(), self._graph(), cfg,
                                       server_cfg.lanes,
                                       server_cfg.slice_rounds)
        self.hist = {"all": LatencyHistogram(),
                     "service": LatencyHistogram()}
        self.stats = {
            "served": 0, "batches": 0, "es_stopped": 0, "overflow": 0,
            # bounded admission: requests shed at the queue limit (the
            # overload signal capacity planning alarms on)
            "rejected": 0,
            # live mutation counters; epoch mirrors the served snapshot
            "inserts": 0, "deletes": 0, "consolidations": 0, "epoch": 0,
            # quantized-corpus two-pass: candidates that fell in the radius
            # guard band and were exact-reranked (0 on f32/bf16 corpora);
            # the band hit rate is what capacity planning watches — a wide
            # band means the corpus scales are too coarse for the traffic's
            # radii
            "reranked": 0,
            # radius-dispersion counters: mixed-radius batches are the
            # heterogeneous-traffic regime the per-query radius path exists
            # for; the running moments let dashboards derive mean/std
            "mixed_radius_batches": 0,
            "radius_min": float("inf"), "radius_max": float("-inf"),
            "radius_sum": 0.0, "radius_sumsq": 0.0,
            # continuous-batching counters: pool_rotations counts retire
            # events that freed slots while OTHER lanes stayed in flight —
            # the lockstep-break actually happening, not just configured
            "pool_admitted": 0, "pool_retired": 0, "pool_ticks": 0,
            "pool_rotations": 0, "pool_oneshot": 0,
            "bucket_cheap": 0, "bucket_heavy": 0,
            # fault-tolerance counters: deadline_shed = expired while still
            # queued (no results), deadline_partial = force-finalized lanes
            # (certified partials); shard_retries / shards_lost come from
            # the degraded fan-out path
            "deadline_shed": 0, "deadline_partial": 0,
            "shard_retries": 0, "shards_lost": 0, "degraded_batches": 0,
            # replication counters (mirrors of ReplicaFleet.stats):
            # hedges_fired/hedge_wins = hedged reads launched / won the
            # race, breaker_trips = circuit breakers opened, replicas_lost/
            # recovered = fleet membership churn
            "hedges_fired": 0, "hedge_wins": 0, "breaker_trips": 0,
            "replicas_lost": 0, "replicas_recovered": 0,
            # filtered range retrieval: micro-batches that carried at least
            # one label-predicate lane (filtered + unfiltered lanes batch
            # together; unfiltered lanes ride an all-pass predicate)
            "filtered_batches": 0, "filtered_requests": 0,
            # aggregate-only workload: op="count" requests served (certified
            # per-lane match counts, no ids/dists payload)
            "count_requests": 0,
            # work per request (lockstep path): distance computations and
            # phase-1 expansions summed over served lanes; and greedy
            # phase-2 straggler waste — expansion rounds of the request-
            # carrying phase-2 lanes against the lane-rounds dispatched
            # (per slice, bucket incl. pow2 padding x the slowest lane's
            # advance); their ratio is the phase-2 lane utilization, and
            # p2_slices counts the phase-2 dispatches
            "n_dist": 0, "n_visited": 0,
            "p2_lane_rounds": 0, "p2_slot_rounds": 0, "p2_slices": 0,
        }

    # -- served view ---------------------------------------------------------
    def _corpus(self):
        return self._view.points if self.live is not None else self.engine.points

    def _device_corpus(self):
        """The jit-safe hot arm of the served corpus: a `TieredCorpus` never
        enters a jitted walk — phase 1 / greedy resume run on its device
        codes; `_finalize` hands the full tier to the host rerank."""
        pts = self._corpus()
        tier = _tier_of(pts)
        return tier.device if tier is not None else pts

    def _finalize(self, qj, rj, res, lf):
        """`finalize_results` (tombstones, label predicate, fused resident
        rerank) plus the tiered corpus's host-fetched guard-band rerank —
        the continuous-path twin of `_walk_compacted`'s finish()."""
        res = finalize_results(self._device_corpus(), qj, rj, res, self.cfg,
                               self._tombstones(),
                               None if lf is None else self._labels(), lf)
        pts = self._corpus()
        if _tier_of(pts) is not None:
            res = _maybe_rerank_host(pts, qj, rj, res, self.cfg)
        return res

    def _graph(self):
        return self._view.graph if self.live is not None else self.engine.graph

    def _start_ids(self):
        return (self._view.start_ids if self.live is not None
                else self.engine.start_ids)

    def _tombstones(self):
        return self._view.tombstones if self.live is not None else None

    def _labels(self):
        """Packed label store of the served view (slot space), or None for
        an unlabeled corpus. Sharded serving keeps labels per shard — the
        store here is only a capability/width probe; per-shard evaluation
        happens inside the fan-out."""
        if self.live is not None:
            return self._view.labels
        if self.sharded is not None:
            return self.sharded.labels
        return self.engine.labels if self.engine is not None else None

    def _num_labels(self) -> int:
        """Label-id space the packed store can represent (32 per word)."""
        lab = self._labels()
        return 0 if lab is None else 32 * int(lab.shape[-1])

    def _batch_filter(self, reqs, bucket: int) -> Optional[LabelFilter]:
        """Per-lane predicate for one padded micro-batch, or None when no
        lane filters. Unfiltered and pad lanes get the all-pass predicate
        (AND over the empty mask), which is bitwise-neutral."""
        if all(rq.filter_labels is None for rq in reqs):
            return None
        pad = bucket - len(reqs)
        entries = [rq.filter_labels for rq in reqs] + [None] * pad
        modes = [rq.filter_mode for rq in reqs] + ["and"] * pad
        return make_label_filter(entries, self._num_labels(), modes=modes)

    def _epoch(self) -> int:
        return self._view.epoch if self._view is not None else 0

    def _externalize(self, ids: np.ndarray) -> np.ndarray:
        if self.live is None:
            return ids
        from ..live.index import externalize_ids
        return externalize_ids(self._view.ext_ids, ids)

    # -- admission -------------------------------------------------------
    def submit(self, req: Request) -> Optional[Response]:
        """Admit a request; returns ``None`` on admission, or a structured
        rejection ``Response(op="error", code="queue_full")`` when the
        queue is at ``max_queue`` — the shed is counted AND delivered, so
        drivers see every rejected request instead of silently dropping it.
        Malformed requests are rejected HERE, at the client's call site —
        one bad request admitted into a micro-batch would otherwise take
        down every other request batched with it."""
        if req.op not in REQUEST_OPS:
            raise ValueError(f"unknown op {req.op!r}")
        if req.op in ("insert", "delete") and self.live is None:
            raise ValueError(f"{req.op!r} requests need a live index")
        if req.op == "delete":
            if req.delete_ids is None:
                raise ValueError("delete requests need delete_ids")
        elif req.query is None:
            raise ValueError(f"{req.op!r} requests need a query vector")
        if req.filter_labels is not None:
            if req.op not in ("range", "count"):
                raise ValueError(
                    "filter_labels applies to range/count requests")
            if self._labels() is None:
                raise ValueError(
                    "served corpus has no labels attached; filtered range "
                    "requests need a labeled engine/index")
            if req.filter_mode not in ("and", "or"):
                raise ValueError(f"filter_mode must be 'and' or 'or', "
                                 f"got {req.filter_mode!r}")
            fl = np.atleast_1d(np.asarray(req.filter_labels))
            if fl.size and int(fl.max()) >= self._num_labels():
                raise ValueError(
                    f"filter label id {int(fl.max())} out of range for a "
                    f"{self._num_labels()}-label corpus")
        if req.labels is not None:
            if req.op != "insert":
                raise ValueError("labels= applies to insert requests")
            if self.live is None or self.live.labels is None:
                raise ValueError(
                    "labeled inserts need a labeled live index")
        if req.deadline_s is not None and req.deadline_s < 0:
            raise ValueError("deadline_s must be >= 0 (or None for no budget)")
        if len(self.queue) >= self.scfg.max_queue:
            self.stats["rejected"] += 1
            return self._record(self._error_response(
                req, QUEUE_FULL, latency_s=0.0))
        self.queue.append((req, self._clock()))
        return None

    @staticmethod
    def _error_response(req: Request, code: str,
                        latency_s: float = 0.0, timings=None) -> Response:
        return Response(
            req_id=req.req_id, op="error", ids=np.zeros(0, np.int64),
            dists=np.zeros(0, np.float32), count=0,
            latency_s=latency_s, timings=timings,
            radius=float("nan") if req.radius is None else float(req.radius),
            complete=False, coverage=0.0, code=code)

    @staticmethod
    def _deadline_at(req: Request, arrive: float) -> float:
        return (float("inf") if req.deadline_s is None
                else arrive + req.deadline_s)

    def _shed_expired(self, batch, svc0: float):
        """Split a drained micro-batch into (alive, expired-error responses).

        Only query (range/count) requests expire — a mutation's effect is
        wanted no matter how late it applies. Expiry is strict
        (``now > deadline``) so a zero budget still gets the work done at
        the instant of submission under a frozen test clock."""
        alive, out = [], []
        for rq, arrive in batch:
            if (rq.op in ("range", "count")
                    and svc0 > self._deadline_at(rq, arrive)):
                self.stats["deadline_shed"] += 1
                out.append(self._record(self._error_response(
                    rq, DEADLINE_EXPIRED, latency_s=svc0 - arrive,
                    timings=self._timings(arrive, svc0, svc0))))
            else:
                alive.append((rq, arrive))
        return alive, out

    def pending(self) -> int:
        return len(self.queue)

    def in_flight(self) -> int:
        """Lanes checkpointed in the continuous pool (0 in lockstep mode)."""
        return self._pool.occupancy if self._pool is not None else 0

    # -- batching ------------------------------------------------------------
    def _drain(self) -> list[tuple[Request, float]]:
        out = []
        t0 = self._clock()
        while self.queue and len(out) < self.scfg.max_batch:
            out.append(self.queue.popleft())
            if not self.queue and (self._clock() - t0) < self.scfg.max_wait_s:
                time.sleep(0)  # yield; more requests may land in a real server
                break
        return out

    # -- response plumbing ---------------------------------------------------
    def _record(self, resp: Response) -> Response:
        self.hist["all"].record(resp.latency_s)
        if resp.timings is not None:
            self.hist["service"].record(resp.timings["service_s"])
        if resp.op not in self.hist:
            self.hist[resp.op] = LatencyHistogram()
        self.hist[resp.op].record(resp.latency_s)
        return resp

    def latency_summary(self) -> dict:
        """Per-op + end-to-end latency quantiles (ms); see LatencyHistogram."""
        return {k: h.summary() for k, h in self.hist.items()}

    @staticmethod
    def _timings(arrive: float, svc0: float, now: float) -> dict:
        return {"queue_s": svc0 - arrive, "service_s": now - svc0,
                "total_s": now - arrive}

    def _track_radii(self, radii: np.ndarray) -> None:
        rb = np.asarray(radii, np.float64)
        if rb.size == 0:
            return
        self.stats["mixed_radius_batches"] += int(rb.min() != rb.max())
        self.stats["radius_min"] = min(self.stats["radius_min"], float(rb.min()))
        self.stats["radius_max"] = max(self.stats["radius_max"], float(rb.max()))
        self.stats["radius_sum"] += float(rb.sum())
        self.stats["radius_sumsq"] += float((rb * rb).sum())

    # -- mutation ------------------------------------------------------------
    def _apply_mutations(self, muts: list[tuple[Request, float]],
                         svc0: float) -> list[Response]:
        """Apply a micro-batch's mutations: ONE coalesced insert batch, then
        ONE coalesced delete batch.

        Reordering within the micro-batch is sound because external ids are
        never reused: insert-then-delete of the same id inside one batch
        lands in the same final state either way, and a delete can never
        precede "its" insert across the reorder (the id did not exist when
        the delete was submitted). Coalescing is what makes churn traffic
        cheap — each batch pays one fixed-shape insert step and one bitset
        update instead of one dispatch per request."""
        out = []
        ins = [(rq, t) for rq, t in muts if rq.op == "insert"]
        dels = [(rq, t) for rq, t in muts if rq.op == "delete"]
        if ins:
            lab = None
            if self.live.labels is not None:
                nl = 32 * int(self.live.labels.shape[1])
                lab = np.stack([
                    make_mask([] if rq.labels is None else rq.labels, nl)
                    for rq, _ in ins])
            ext = self.live.insert(np.stack([rq.query for rq, _ in ins]),
                                   labels=lab)
            self.stats["inserts"] += len(ins)
            now = self._clock()
            for (rq, arrive), e in zip(ins, ext):
                ids = np.asarray([e], np.int64)
                out.append(self._record(Response(
                    req_id=rq.req_id, ids=ids,
                    dists=np.zeros(1, np.float32), count=1,
                    overflow=False, es_stopped=False,
                    latency_s=now - arrive, op="insert",
                    epoch=self.live.epoch,
                    timings=self._timings(arrive, svc0, now))))
        if dels:
            per_req = [np.atleast_1d(np.asarray(rq.delete_ids, np.int64))
                       for rq, _ in dels]
            self.stats["deletes"] += self.live.delete(np.concatenate(per_req))
            now = self._clock()
            for (rq, arrive), ids in zip(dels, per_req):
                out.append(self._record(Response(
                    req_id=rq.req_id, ids=ids,
                    dists=np.zeros(len(ids), np.float32), count=len(ids),
                    overflow=False, es_stopped=False,
                    latency_s=now - arrive, op="delete",
                    epoch=self.live.epoch,
                    timings=self._timings(arrive, svc0, now))))
        return out

    # -- lockstep execution --------------------------------------------------
    def _execute(self, queries: np.ndarray, radii: np.ndarray,
                 label_filter: Optional[LabelFilter] = None):
        """Dispatch one padded batch; returns ``(RangeResult, DegradedResult
        | None)`` — the second element is populated only on the
        fault-tolerant sharded path (no mesh, or an injector present).
        ``label_filter`` (optional) covers every padded lane; each dispatch
        path evaluates it at its own result stage."""
        es = (self.scfg.es_radius_factor * jnp.asarray(radii)
              if self.scfg.es_radius_factor > 0 else None)
        qs = jnp.asarray(queries)
        rs = jnp.asarray(radii)
        if self.live is not None:
            return self._view.range(qs, rs, cfg=self.cfg, es_radius=es,
                                    filter=label_filter), None
        if self.sharded is not None:
            if (self.mesh is not None and self.injector is None
                    and self.fleet is None
                    and getattr(self.sharded, "tiers", None) is None):
                return sharded_range_search(
                    mesh=self.mesh, corpus=self.sharded, queries=qs, r=rs,
                    cfg=self.cfg, es_radius=es,
                    label_filter=label_filter), None
            d = fault_tolerant_sharded_search(
                corpus=self.sharded, queries=qs, r=rs, cfg=self.cfg,
                es_radius=es, label_filter=label_filter,
                injector=self.injector, retry=self.retry,
                fleet=self.fleet, hedge=self.hedge)
            self.stats["degraded_batches"] += int(not d.complete)
            self.stats["shard_retries"] += int(d.attempts.sum()) - d.shards_total
            self.stats["shards_lost"] += d.shards_total - d.shards_ok
            if self.fleet is not None:
                self.stats.update(self.fleet.stats)  # running fleet totals
            return d.result, d
        return range_search_compacted(
            corpus=self.engine.points, graph=self.engine.graph, queries=qs,
            start_ids=self.engine.start_ids, r=rs, cfg=self.cfg, es_radius=es,
            labels=None if label_filter is None else self.engine.labels,
            label_filter=label_filter), None

    def step(self) -> list[Response]:
        """Serve one micro-batch from the queue.

        Mutations in the batch apply first (in arrival order); the epoch
        snapshot then advances ONCE and every query in the batch is answered
        against that view — a consistent ``(graph, corpus, tombstones,
        epoch)`` even as later batches keep mutating. Requests batch
        regardless of radius: the radius vector rides alongside the query
        matrix (padded identically), and every layer below answers each lane
        at its own radius. In continuous mode a step additionally advances
        the persistent lane pool one tick and retires finished lanes.
        """
        if self._pool is not None:
            return self._step_continuous()
        # host spans (no-ops unless a profiler trace is active): range.step
        # and, inside it and in order, range.batch, the walk's range.phase1 /
        # compact / phase2 / merge / rerank, then range.respond
        with jax.profiler.TraceAnnotation(
                "range.step", batch=self.stats["batches"]) as span:
            return self._step_lockstep(span)

    def _step_lockstep(self, span) -> list[Response]:
        if self.fleet is not None:
            # background recovery sweep: rebuild lost replicas and re-admit
            # them through the breaker's half-open probe
            self.fleet.maintain()
            self.stats.update(self.fleet.stats)
        with jax.profiler.TraceAnnotation("range.batch"):
            batch = self._drain()
            if not batch:
                return []
            svc0 = self._clock()
            out = []
            if self.live is not None:
                muts = [b for b in batch if b[0].op in ("insert", "delete")]
                batch = [b for b in batch if b[0].op in ("range", "count")]
                if muts:
                    out.extend(self._apply_mutations(muts, svc0))
                    if (self.scfg.auto_consolidate
                            and self.live.maybe_consolidate()):
                        self.stats["consolidations"] += 1
                    self._view = self.live.snapshot()
                self.stats["epoch"] = self._view.epoch
                self.stats["batches"] += 1 if (muts and not batch) else 0
            batch, shed = self._shed_expired(batch, svc0)
            out.extend(shed)
            if not batch:
                return out
            reqs = [b[0] for b in batch]
            arrive = [b[1] for b in batch]
            n = len(reqs)
            bucket = next_pow2(n)
            span.set_metadata(n=n, bucket=bucket)
            q = np.stack([rq.query for rq in reqs])
            radii = np.asarray(
                [self.scfg.default_radius if rq.radius is None else rq.radius
                 for rq in reqs], np.float32)
            if bucket > n:  # pad with repeats (masked out of responses)
                q = np.concatenate([q, np.repeat(q[:1], bucket - n, axis=0)])
                radii = np.concatenate(
                    [radii, np.repeat(radii[:1], bucket - n)])
            lf = self._batch_filter(reqs, bucket)
            n_filtered = sum(rq.filter_labels is not None for rq in reqs)
        res, degraded = self._execute(q, radii, lf)
        with jax.profiler.TraceAnnotation("range.respond") as rspan:
            now = self._clock()
            # one batched fetch of everything the responses and counters read
            (ids, dists, counts, over, ess, nrr, ndist, nvis, p2,
             p2_slots, p2_slices) = jax.device_get(
                 (res.ids, res.dists, res.count, res.overflow,
                  res.es_stopped, res.n_rerank, res.n_dist, res.n_visited,
                  res.p2_rounds, res.p2_slot_rounds, res.p2_slices))
            epoch = self._epoch()
            dkw = {}
            if degraded is not None:  # annotate shard health on every response
                dkw = dict(shards_ok=degraded.shards_ok,
                           shards_total=degraded.shards_total,
                           complete=degraded.complete,
                           coverage=degraded.coverage,
                           code=degraded.code)
                if hasattr(degraded, "replica_ok"):  # replicated fan-out
                    dkw.update(replicas_ok=degraded.replicas_ok,
                               replicas_total=degraded.replicas_total)
            for i, rq in enumerate(reqs):
                row = ids[i]
                valid = row != INVALID_ID
                if rq.op == "count":  # certified count only, no payload
                    r_ids = np.zeros(0, row.dtype)
                    r_dists = np.zeros(0, np.float32)
                else:
                    r_ids, r_dists = row[valid], dists[i][valid]
                out.append(self._record(Response(
                    req_id=rq.req_id,
                    op=rq.op,
                    ids=r_ids,
                    dists=r_dists,
                    count=int(counts[i]),
                    overflow=bool(over[i]),
                    es_stopped=bool(ess[i]),
                    latency_s=now - arrive[i],
                    radius=float(radii[i]),
                    epoch=epoch,
                    timings=self._timings(arrive[i], svc0, now),
                    filtered=rq.filter_labels is not None,
                    **dkw,
                )))
            work = self._count_work(n, ndist, nvis, p2, p2_slots,
                                    p2_slices)
            rspan.set_metadata(**work)
            self.stats["served"] += n
            self.stats["count_requests"] += sum(rq.op == "count"
                                                for rq in reqs)
            self.stats["batches"] += 1
            self.stats["filtered_batches"] += int(lf is not None)
            self.stats["filtered_requests"] += n_filtered
            self.stats["es_stopped"] += int(ess[:n].sum())
            self.stats["overflow"] += int(over[:n].sum())
            self.stats["reranked"] += int(nrr[:n].sum())
            self._track_radii(radii[:n])
        return out

    def _count_work(self, n: int, ndist, nvis, p2, p2_slots,
                    p2_slices) -> dict:
        """Add one batch's work to the counters; returns the increments.
        ``p2``, ``p2_slots`` and ``p2_slices`` are the batch's
        ``RangeResult.p2_rounds`` / ``p2_slot_rounds`` / ``p2_slices``
        (None adds nothing to the phase-2 counters)."""
        work = {"n_dist": int(ndist[:n].sum()),
                "n_visited": int(nvis[:n].sum()),
                "p2_lane_rounds": 0 if p2 is None
                else int(p2[:n][p2[:n] >= 0].sum()),
                "p2_slot_rounds": int(p2_slots or 0),
                "p2_slices": int(p2_slices or 0)}
        for k, v in work.items():
            self.stats[k] += v
        return work

    # -- continuous execution ------------------------------------------------
    def _step_continuous(self) -> list[Response]:
        """One continuous-batching step: drain, (mutations), effort-split
        phase-1 dispatches, pool tick, retirements. Point queries answered
        at phase 1 return from the step they were drained in; saturated
        lanes ride the pool across steps."""
        out = []
        batch = self._drain()
        svc0 = self._clock()
        if self.live is not None:
            muts = [b for b in batch if b[0].op in ("insert", "delete")]
            batch = [b for b in batch if b[0].op in ("range", "count")]
            if muts:
                # in-flight checkpoints must not cross an epoch: finish them
                # against the snapshot they were admitted under, THEN mutate
                out.extend(self._finish_pool())
                out.extend(self._apply_mutations(muts, svc0))
                if (self.scfg.auto_consolidate
                        and self.live.maybe_consolidate()):
                    self.stats["consolidations"] += 1
                self._view = self.live.snapshot()
                self._pool.rebind(self._device_corpus(), self._graph())
            self.stats["epoch"] = self._view.epoch
        batch, shed = self._shed_expired(batch, svc0)
        out.extend(shed)
        if batch:
            reqs = [b[0] for b in batch]
            arrive = [b[1] for b in batch]
            q = np.stack([rq.query for rq in reqs])
            radii = np.asarray(
                [self.scfg.default_radius if rq.radius is None else rq.radius
                 for rq in reqs], np.float32)
            heavy = np.zeros(len(reqs), bool)
            if self.effort is not None and len(reqs) > 1:
                pred = self.effort.predict(q, radii)
                heavy = pred >= self.scfg.effort_threshold
            self.stats["bucket_cheap"] += int((~heavy).sum())
            self.stats["bucket_heavy"] += int(heavy.sum())
            # cheap bucket first: point queries keep their relative order
            # and never queue behind the heavy dispatch
            for sel in (np.nonzero(~heavy)[0], np.nonzero(heavy)[0]):
                if len(sel):
                    out.extend(self._dispatch_phase1(
                        [reqs[i] for i in sel], [arrive[i] for i in sel],
                        q[sel], radii[sel], svc0))
            self._track_radii(radii)
            self.stats["batches"] += 1
            nf = sum(rq.filter_labels is not None for rq in reqs)
            self.stats["filtered_batches"] += int(nf > 0)
            self.stats["filtered_requests"] += nf
        # deadline check BEFORE the tick: a lane past its budget is
        # finalized from its current GreedyState checkpoint instead of
        # resumed — a certified partial (truncated, never corrupted) that
        # frees the slot so the pool can never stall on one slow lane
        expired = self._pool.expired(self._clock())
        if len(expired):
            out.extend(self._respond_greedy(*self._pool.retire(expired),
                                            expired=True))
        before = self._pool.occupancy
        finished = self._pool.tick()
        self.stats["pool_ticks"] = self._pool.ticks
        if before > len(finished):
            # at least one lane survived the tick while the server kept
            # serving around it — the scheduler rotated past a straggler
            self.stats["pool_rotations"] += 1
        if len(finished):
            out.extend(self._respond_greedy(*self._pool.retire(finished)))
        return out

    def _dispatch_phase1(self, reqs, arrive, q, radii, svc0) -> list[Response]:
        """Run one pow2-padded phase-1 batch; answer unsaturated lanes now,
        seed saturated ones into the pool (overflow runs one-shot)."""
        n = len(reqs)
        bucket = next_pow2(n)
        if bucket > n:
            q = np.concatenate([q, np.repeat(q[:1], bucket - n, axis=0)])
            radii = np.concatenate([radii, np.repeat(radii[:1], bucket - n)])
        qj = jnp.asarray(q)
        rj = jnp.asarray(radii)
        es = (self.scfg.es_radius_factor * rj
              if self.scfg.es_radius_factor > 0 else None)
        st, res, need = range_phase1(self._device_corpus(), self._graph(), qj,
                                     self._start_ids(), rj, self.cfg,
                                     es_radius=es)
        need_h = np.array(need)
        need_h[n:] = False
        out = []
        # phase 1 walks unfiltered (predicates are result-stage only); the
        # batch predicate applies at both finalize sites — here for direct
        # lanes, and at retirement (_respond_greedy) for pooled lanes
        lf = self._batch_filter(reqs, bucket)
        direct = np.nonzero(~need_h[:n])[0]
        if len(direct):
            fin = self._finalize(qj, rj, res, lf)
            out.extend(self._emit_range(fin, direct, reqs, arrive, radii,
                                        svc0, phase2=False))
        lanes = np.nonzero(need_h)[0]
        if len(lanes):
            seeded = greedy_seed_batch(self._device_corpus(), st, rj,
                                       self.cfg.result_cap, self.cfg.search)
            nv1 = np.asarray(st.n_visited)
            nd1 = np.asarray(st.n_dist)
            es1 = np.asarray(st.es_stopped)
            metas = [dict(req=reqs[i], arrive=arrive[i], svc0=svc0,
                          radius=float(radii[i]),
                          deadline_at=self._deadline_at(reqs[i], arrive[i]),
                          n_visited=int(nv1[i]), n_dist=int(nd1[i]),
                          es=bool(es1[i]))
                     for i in lanes]
            fit = min(len(lanes), len(self._pool.free_slots()))
            if fit:
                self._pool.admit(seeded, lanes[:fit], qj, rj, metas[:fit])
                self.stats["pool_admitted"] += fit
            if fit < len(lanes):
                # pool full: run the overflow lanes to completion in one
                # slice (identical results — the slice width is a latency
                # knob, not a semantic one)
                out.extend(self._oneshot(seeded, lanes[fit:], qj, rj,
                                         metas[fit:]))
        return out

    def _oneshot(self, seeded, sel, qj, rj, metas) -> list[Response]:
        k = len(sel)
        P = next_pow2(k)
        sel_p = np.concatenate([sel, np.repeat(sel[:1], P - k)])
        g, qs, rs = _gather_lanes((seeded, qj, rj), jnp.asarray(sel_p))
        g = greedy_resume_batch(
            self._device_corpus(), self._graph(), qs, rs, g, jnp.ones(P, bool),
            self.cfg.result_cap, self.cfg.frontier_rounds,
            self.cfg.frontier_rounds, self.cfg.search)
        _, over = greedy_lane_done(g, self.cfg.frontier_rounds)
        self.stats["pool_oneshot"] += k
        return self._respond_greedy(g, qs, rs, over, metas)

    def _respond_greedy(self, g, qs, rs, over, metas, *,
                        expired: bool = False) -> list[Response]:
        """Finalize retired greedy lanes (pool or one-shot) into Responses.
        Device arrays are pow2-padded past ``len(metas)``; pad lanes are
        finalized (fixed shapes) but never answered.

        ``expired=True`` marks deadline force-retirements: the lanes'
        checkpoints are finalized as-is (the greedy loop only ever appends
        in-range nodes, and ``finalize_results`` still tombstone-filters
        and exact-reranks), so the partial answer is certified — every
        returned id verifiably within radius — just possibly short.
        ``coverage`` is the visited-frontier fraction from the checkpoint."""
        k = len(metas)
        P = int(np.asarray(g.res_count).shape[0])
        nv = np.zeros(P, np.int32)
        nd = np.zeros(P, np.int32)
        esf = np.zeros(P, bool)
        for i, m in enumerate(metas):
            nv[i], nd[i], esf[i] = m["n_visited"], m["n_dist"], m["es"]
        res = RangeResult(
            ids=g.res_ids, dists=g.res_dists, count=g.res_count,
            overflow=jnp.asarray(over),
            n_visited=jnp.asarray(nv),
            n_dist=jnp.asarray(nd) + g.n_dist,
            es_stopped=jnp.asarray(esf),
            phase2=jnp.ones(P, bool),
            n_rerank=jnp.zeros(P, jnp.int32))
        extras = None
        if expired:
            cov = greedy_coverage(g)
            extras = [dict(complete=False, coverage=float(cov[i]),
                           code=DEADLINE_EXPIRED) for i in range(k)]
            self.stats["deadline_partial"] += k
        lf = None
        if any(m["req"].filter_labels is not None for m in metas):
            # rebuild the retired lanes' predicates (pad lanes all-pass)
            entries = ([m["req"].filter_labels for m in metas]
                       + [None] * (P - k))
            modes = ([m["req"].filter_mode for m in metas]
                     + ["and"] * (P - k))
            lf = make_label_filter(entries, self._num_labels(), modes=modes)
        res = self._finalize(qs, rs, res, lf)
        self.stats["pool_retired"] += k
        reqs = [m["req"] for m in metas]
        arrive = [m["arrive"] for m in metas]
        radii = np.asarray([m["radius"] for m in metas], np.float32)
        return self._emit_range(res, np.arange(k), reqs, arrive, radii,
                                metas[0]["svc0"] if k else 0.0, phase2=True,
                                svc0s=[m["svc0"] for m in metas],
                                extras=extras)

    def _emit_range(self, res: RangeResult, rows, reqs, arrive, radii,
                    svc0, *, phase2: bool, svc0s=None,
                    extras=None) -> list[Response]:
        """Turn result rows into recorded Responses. ``rows`` indexes the
        (padded) result arrays; ``reqs``/``arrive``/``radii`` are indexed
        the same way for phase-1 emission and positionally (row i ->
        meta i) for greedy retirement. ``extras`` (positional, one dict
        per emitted row) merges degradation fields (complete/coverage/
        code) into the Response."""
        now = self._clock()
        ids = self._externalize(np.asarray(res.ids))
        dists = np.asarray(res.dists)
        counts = np.asarray(res.count)
        over = np.asarray(res.overflow)
        ess = np.asarray(res.es_stopped)
        epoch = self._epoch()
        out = []
        for j, i in enumerate(rows):
            row = ids[i]
            valid = row != INVALID_ID
            a = arrive[i] if svc0s is None else arrive[j]
            s0 = svc0 if svc0s is None else svc0s[j]
            rq = reqs[i] if svc0s is None else reqs[j]
            rad = radii[i] if svc0s is None else radii[j]
            if rq.op == "count":  # certified count only, no payload
                r_ids = np.zeros(0, row.dtype)
                r_dists = np.zeros(0, np.float32)
                self.stats["count_requests"] += 1
            else:
                r_ids, r_dists = row[valid], dists[i][valid]
            out.append(self._record(Response(
                req_id=rq.req_id,
                op=rq.op,
                ids=r_ids,
                dists=r_dists,
                count=int(counts[i]),
                overflow=bool(over[i]),
                es_stopped=bool(ess[i]),
                latency_s=now - a,
                radius=float(rad),
                epoch=epoch,
                timings=self._timings(a, s0, now),
                filtered=rq.filter_labels is not None,
                **(extras[j] if extras is not None else {}),
            )))
            self.stats["es_stopped"] += int(ess[i])
            self.stats["overflow"] += int(over[i])
            self.stats["reranked"] += int(np.asarray(res.n_rerank)[i])
        self.stats["served"] += len(out)
        return out

    def _finish_pool(self) -> list[Response]:
        """Tick the pool to empty (epoch barrier / final drain). Deadlines
        stay live during the barrier: expired lanes finalize as certified
        partials between ticks, same as in the steady state."""
        out = []
        while self._pool.occupancy:
            expired = self._pool.expired(self._clock())
            if len(expired):
                out.extend(self._respond_greedy(*self._pool.retire(expired),
                                                expired=True))
                continue
            finished = self._pool.tick()
            self.stats["pool_ticks"] = self._pool.ticks
            if len(finished):
                out.extend(self._respond_greedy(*self._pool.retire(finished)))
        return out

    # -- monitoring / drain --------------------------------------------------
    def radius_dispersion(self) -> dict:
        """Mean/std/min/max of served radii + mixed-batch count (monitoring)."""
        n = max(self.stats["served"], 1)
        mean = self.stats["radius_sum"] / n
        var = max(self.stats["radius_sumsq"] / n - mean * mean, 0.0)
        return dict(mean=mean, std=var ** 0.5,
                    min=self.stats["radius_min"], max=self.stats["radius_max"],
                    mixed_radius_batches=self.stats["mixed_radius_batches"])

    def run_until_drained(self) -> list[Response]:
        out = []
        while self.queue or self.in_flight():
            out.extend(self.step())
        return out
