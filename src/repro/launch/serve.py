"""Serving launcher: build an index over a corpus and serve range queries.

  PYTHONPATH=src python -m repro.launch.serve --profile bigann-like \\
      --n 20000 --queries 512 --mode greedy --early-stop --mixed-radius
  PYTHONPATH=src python -m repro.launch.serve --n 20000 --churn 0.1

Builds the synthetic corpus, selects a radius with the paper's Sec.-3
methodology, builds the Vamana index, starts the RangeServer and drives a
batch of requests through it, reporting QPS / AP / early-stop stats.
``--shards S`` serves through the fault-tolerant host fan-out; add
``--replicas R`` (plus optionally ``--hedge-ms`` and ``--down-replicas``)
to serve an R-way replicated fleet with hedged reads, circuit breakers,
and background replica recovery — coverage stays 1.0 while any replica of
every shard survives.
``--mixed-radius`` spreads per-request radii across the corpus's match
distribution (real traffic mixes duplicate-detection-tight and
recommendation-wide thresholds); the server batches them together and
answers each request at its own radius. ``--churn FRAC`` serves from a
**live** index instead of a frozen one: insert and delete requests for
FRAC of the corpus interleave with the queries in the same admission
queue, the server applies them between micro-batches (epoch snapshots),
and AP is scored against the exact oracle on the FINAL live set.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax.numpy as jnp
import numpy as np

from ..configs.range_engine import EngineDeployConfig
from ..core import (
    BuildConfig, RangeSearchEngine, average_precision, exact_range_search,
    pack_labels,
)
from ..core.beam_search import ES_D_VISITED
from ..core.radius import default_grid, select_radius, sweep
from ..data.synthetic import make_corpus
from ..live import LiveConfig, LiveIndex
from ..serve import RangeServer, Request, ServerConfig
from ..utils import INVALID_ID, enable_compile_cache


def select_serving_radius(points, queries, metric: str):
    """The paper's Sec.-3 radius methodology over a 24-point grid: returns
    ``(radius, grid_index, profile)``."""
    grid = default_grid(points, queries, metric, num=24)
    prof = sweep(jnp.asarray(points), jnp.asarray(queries), grid, metric)
    r, gi = select_radius(prof, robustness_weight=0.2)
    print(f"[serve] selected radius {r:.4g} "
          f"(zero-result frac {prof.zero_frac[gi]:.2f})")
    return r, gi, prof


# A quantized search buffers every candidate whose certified lower bound is
# in range (the keep band), a superset of the answer that the rerank trims
# afterwards. On bigann-like the band held 1.50x (100k rows) and 1.66x
# (300k) the true matches, so an int8 buffer sized like the f32 one filled
# up on the heaviest queries and lost matches f32 keeps.
QUANTIZED_CAP_HEADROOM = 2


def serving_range_cfg(metric: str, *, beam: int = 32, expand_width: int = 4,
                      corpus_dtype: str = "float32", mode: str = "greedy",
                      early_stop: bool = False,
                      use_expand_kernel: bool = False):
    """The ``RangeConfig`` every serving driver runs with: room for 2048
    results per query, times ``QUANTIZED_CAP_HEADROOM`` on an int8
    corpus."""
    cap = 2048 * (QUANTIZED_CAP_HEADROOM if corpus_dtype == "int8" else 1)
    return EngineDeployConfig().overrides(
        metric=metric, beam=beam,
        max_beam=beam * (8 if mode == "doubling" else 1),
        visit_cap=512,
        es_metric=ES_D_VISITED if early_stop else 0, es_visit_limit=20,
        expand_width=expand_width, corpus_dtype=corpus_dtype,
        use_expand_kernel=use_expand_kernel,
        mode=mode, result_cap=cap).range_cfg


def serve_lockstep(srv: RangeServer, points, queries, radii, *,
                   metric: str, filt_of=None, fmode=None,
                   raw_labels=None) -> dict:
    """Serve each query once through ``srv`` (submit, step under
    backpressure, drain) and score AP against ``exact_range_search`` over
    ``points``, the exact f32 corpus.

    ``filt_of[i]``/``fmode[i]`` give request i's label predicate (None for
    unfiltered); filtered lanes score against the post-filtered oracle over
    ``raw_labels``. Returns the numbers: ``seconds`` and ``qps`` of the
    serving loop, ``ap``, ``latency_ms`` (sorted per request), the
    per-query result ids (``ids``, sorted) and the ``responses``."""
    nq = len(queries)
    filt_of = filt_of or [None] * nq
    fmode = fmode or ["and"] * nq
    t0 = time.perf_counter()
    resp = []
    for i in range(nq):
        rq = Request(req_id=i, query=queries[i], radius=float(radii[i]),
                     filter_labels=filt_of[i], filter_mode=fmode[i])
        while srv.submit(rq) is not None:  # queue_full: serve under
            resp.extend(srv.step())        # backpressure, then retry
    resp.extend(srv.run_until_drained())
    dt = time.perf_counter() - t0

    gt_ids, _, gt_counts = exact_range_search(
        jnp.asarray(points), jnp.asarray(queries), jnp.asarray(radii), metric)
    if raw_labels is not None:
        # filtered lanes score against the POST-FILTERED oracle: the exact
        # in-radius set restricted to predicate-matching points
        gt_ids = np.asarray(gt_ids).copy()
        gt_counts = np.asarray(gt_counts).copy()
        lab_sets = [set(l) for l in raw_labels]
        for qi in range(nq):
            if filt_of[qi] is None:
                continue
            pred = set(filt_of[qi])
            keep = [int(x) for x in gt_ids[qi][:gt_counts[qi]]
                    if (pred <= lab_sets[int(x)] if fmode[qi] == "and"
                        else bool(pred & lab_sets[int(x)]))]
            gt_ids[qi] = INVALID_ID
            gt_ids[qi, :len(keep)] = keep
            gt_counts[qi] = len(keep)
    res_ids = np.full((nq, 4096), INVALID_ID, np.int64)
    counts = np.zeros(nq, np.int64)
    ids = [None] * nq
    for rp in resp:
        k = min(len(rp.ids), 4096)
        res_ids[rp.req_id, :k] = rp.ids[:k]
        counts[rp.req_id] = k
        ids[rp.req_id] = np.sort(np.asarray(rp.ids))
    ap = average_precision(np.asarray(gt_ids), np.asarray(gt_counts),
                           res_ids, counts)
    return dict(seconds=dt, qps=nq / dt, ap=float(ap), ids=ids,
                latency_ms=sorted(rp.latency_s * 1e3 for rp in resp),
                responses=resp)


def _replicated_main(args) -> int:
    """Sharded/replicated traffic driver: host fan-out serving with R-way
    replication, hedged reads, and scripted replica loss."""
    from ..core.build import build_vamana, medoid
    from ..dist.sharded_engine import build_sharded
    from ..fault import FaultInjector, HedgePolicy, RetryPolicy

    n_shards = max(args.shards, 1)
    print(f"[serve] SHARDED corpus {args.profile} n={args.n} "
          f"shards={n_shards} replicas={args.replicas}")
    ds = make_corpus(args.profile, n=args.n, n_queries=args.queries)
    pts = np.asarray(ds.points, np.float32)
    qs = ds.queries

    r, _, _ = select_serving_radius(pts, qs, ds.metric)

    bcfg = BuildConfig(max_degree=32, beam=64, metric=ds.metric)
    t0 = time.perf_counter()
    corpus = build_sharded(
        pts, n_shards,
        lambda p: (build_vamana(jnp.asarray(p), bcfg), medoid(p)[None]),
        corpus_dtype=args.corpus_dtype,
        tier=args.tier, resident_mb=args.resident_mb)
    print(f"[serve] {n_shards}-shard index built in "
          f"{time.perf_counter() - t0:.1f}s")
    if args.tier:
        print(f"[serve] tiered shards: "
              f"{[t.budget().as_dict() for t in corpus.tiers]}")

    down = []
    if args.down_replicas:
        down = [tuple(int(x) for x in pair.split(":"))
                for pair in args.down_replicas.split(",")]
        print(f"[serve] scripted replica loss: {down}")
    injector = FaultInjector(seed=0, down_replicas=tuple(down)) if down else None
    hedge = (HedgePolicy(delay_s=args.hedge_ms / 1e3)
             if args.hedge_ms > 0 else None)

    rcfg = serving_range_cfg(ds.metric, beam=args.beam,
                             expand_width=args.expand_width,
                             corpus_dtype=args.corpus_dtype, mode=args.mode)
    srv = RangeServer(None, rcfg, ServerConfig(max_batch=args.max_batch),
                      sharded=corpus, replicas=args.replicas,
                      injector=injector, hedge=hedge,
                      retry=RetryPolicy(backoff_s=0.01))

    out = serve_lockstep(srv, pts, qs, np.full(args.queries, r, np.float32),
                         metric=ds.metric)
    cov = min(rp.coverage for rp in out["responses"])
    codes = {rp.code for rp in out["responses"]}
    print(f"[serve] {args.queries} queries in {out['seconds']:.3f}s = "
          f"{out['qps']:.0f} QPS; AP={out['ap']:.4f}; "
          f"min coverage={cov:.2f} codes={codes}")
    st = srv.stats
    print(f"[serve] replication: hedges_fired={st['hedges_fired']} "
          f"hedge_wins={st['hedge_wins']} breaker_trips={st['breaker_trips']} "
          f"replicas_lost={st['replicas_lost']} "
          f"replicas_recovered={st['replicas_recovered']} "
          f"shards_lost={st['shards_lost']} "
          f"degraded_batches={st['degraded_batches']}")
    if args.tier:
        print(f"[serve] tier fetch path (shard 0): "
              f"{corpus.tiers[0].counters.as_dict()}")
    return 0


def _churn_main(args) -> int:
    """Live-engine traffic driver: interleaved insert/delete/query requests
    through one admission queue, AP scored on the final live set."""
    n, k = args.n, max(int(args.churn * args.n), 1)
    print(f"[serve] LIVE corpus {args.profile} n={n} churn={args.churn} "
          f"({k} inserts + {k} deletes interleaved with {args.queries} queries)")
    ds = make_corpus(args.profile, n=n + k, n_queries=args.queries)
    pts_all = np.asarray(ds.points, np.float32)
    init, stream = pts_all[:n], pts_all[n:]
    qs = ds.queries

    raw_labels = None
    if args.filter_frac > 0:
        # label the full stream (initial corpus + future inserts) up front
        # so inserted points carry predicates the moment they land
        lrng = np.random.default_rng(7)
        raw_labels = [list(lrng.choice(args.num_labels,
                                       size=int(lrng.integers(1, 4)),
                                       replace=False))
                      for _ in range(n + k)]
        print(f"[serve] labeled live corpus: {args.num_labels}-label "
              f"vocabulary, 1-3 labels/point (inserts carry labels)")

    r, _, _ = select_serving_radius(init, qs, ds.metric)

    t0 = time.perf_counter()
    live = LiveIndex.create(
        init, LiveConfig(capacity=n + k, insert_batch=128),
        BuildConfig(max_degree=32, beam=64, metric=ds.metric),
        metric=ds.metric, corpus_dtype=args.corpus_dtype,
        labels=None if raw_labels is None
        else pack_labels(raw_labels[:n], args.num_labels),
        tier=args.tier, resident_mb=args.resident_mb)
    print(f"[serve] live index built in {time.perf_counter() - t0:.1f}s "
          f"{live.stats()}")
    if args.tier:
        print(f"[serve] tiered live corpus: "
              f"{live.points.budget().as_dict()}")

    rcfg = serving_range_cfg(ds.metric, beam=args.beam,
                             expand_width=args.expand_width,
                             corpus_dtype=args.corpus_dtype, mode=args.mode)
    srv = RangeServer(None, rcfg,
                      ServerConfig(max_batch=args.max_batch,
                                   continuous=args.continuous,
                                   lanes=args.lanes,
                                   slice_rounds=args.slice_rounds),
                      live=live)

    rng = np.random.default_rng(0)
    doomed = rng.choice(n, size=k, replace=False)  # initial ids to delete
    filt_of = [None] * args.queries
    fmode = ["and"] * args.queries
    if args.filter_frac > 0:
        # same predicate mix as the static path: mostly single-label AND,
        # every fourth lane a two-label OR
        nf = max(int(args.filter_frac * args.queries), 1)
        for qi in rng.choice(args.queries, nf, replace=False):
            if qi % 4 == 3:
                filt_of[qi] = [int(x) for x in
                               rng.choice(args.num_labels, 2, replace=False)]
                fmode[qi] = "or"
            else:
                filt_of[qi] = [int(rng.integers(args.num_labels))]
        print(f"[serve] filtered traffic: {nf}/{args.queries} requests "
              f"carry label predicates")
    reqs = (
        [Request(req_id=i, query=qs[i], radius=float(r),
                 filter_labels=filt_of[i], filter_mode=fmode[i])
         for i in range(args.queries)]
        + [Request(req_id=args.queries + i, op="insert", query=stream[i],
                   labels=None if raw_labels is None
                   else np.asarray(raw_labels[n + i]))
           for i in range(k)]
        + [Request(req_id=args.queries + k + i, op="delete",
                   delete_ids=np.asarray([doomed[i]]))
           for i in range(k)]
    )
    rng.shuffle(reqs)  # interleave mutations with query traffic
    t0 = time.perf_counter()
    resp = []
    for rq in reqs:
        while srv.submit(rq) is not None:  # queue_full: serve under
            resp.extend(srv.step())        # backpressure, then retry
    resp.extend(srv.run_until_drained())
    dt = time.perf_counter() - t0
    n_req = len(reqs)
    print(f"[serve] {n_req} requests ({args.queries} queries, {k} inserts, "
          f"{k} deletes) in {dt:.3f}s = {n_req / dt:.0f} req/s; "
          f"epoch={srv.stats['epoch']} "
          f"consolidations={srv.stats['consolidations']}")

    # score queries against the exact oracle on the FINAL live set (each
    # query was answered at some intermediate epoch: with shuffled traffic
    # the early/late disagreement shows up as a small AP haircut, which is
    # the honest serving-consistency number)
    ext, vecs = live.live_vectors()
    gt = exact_range_search(jnp.asarray(vecs), jnp.asarray(qs),
                            float(r), ds.metric)
    if raw_labels is not None:
        # filtered lanes score against the POST-FILTERED oracle over the
        # final live set (rows index vecs; labels key off external ids)
        gt_ids_f = np.asarray(gt[0]).copy()
        gt_counts_f = np.asarray(gt[2]).copy()
        lab_sets = [set(raw_labels[int(e)]) for e in ext]
        for qi in range(args.queries):
            if filt_of[qi] is None:
                continue
            pred = set(filt_of[qi])
            keep = [int(x) for x in gt_ids_f[qi][:gt_counts_f[qi]]
                    if (pred <= lab_sets[int(x)] if fmode[qi] == "and"
                        else bool(pred & lab_sets[int(x)]))]
            gt_ids_f[qi] = INVALID_ID
            gt_ids_f[qi, :len(keep)] = keep
            gt_counts_f[qi] = len(keep)
        gt = (gt_ids_f, gt[1], gt_counts_f)
    lut = np.full(live.next_ext_id + 1, INVALID_ID, np.int64)
    lut[ext] = np.arange(len(ext))
    res_ids = np.full((args.queries, 4096), INVALID_ID, np.int64)
    counts = np.zeros(args.queries, np.int64)
    qresp = [rp for rp in resp if rp.op == "range"]
    for rp in qresp:
        rows = lut[np.minimum(rp.ids, live.next_ext_id)][:4096]
        res_ids[rp.req_id, :len(rows)] = rows
        counts[rp.req_id] = len(rows)
    ap = average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                           res_ids, counts)
    lat = sorted(rp.latency_s for rp in qresp)
    print(f"[serve] AP vs final live set = {ap:.4f}; latency "
          f"p50={lat[len(lat) // 2] * 1e3:.1f}ms "
          f"p99={lat[int(len(lat) * 0.99)] * 1e3:.1f}ms")
    print(f"[serve] stats={srv.stats}")
    if args.filter_frac > 0:
        st = srv.stats
        print(f"[serve] filtered: requests={st['filtered_requests']} "
              f"batches={st['filtered_batches']}/{st['batches']} "
              f"(AP above scored vs the post-filtered oracle on the final "
              f"live set)")
    print(f"[serve] final live index: {live.stats()}")
    if args.tier:
        print(f"[serve] tier fetch path: "
              f"{live.points.counters.as_dict()}")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--profile", default="bigann-like")
    p.add_argument("--n", type=int, default=20_000)
    p.add_argument("--queries", type=int, default=512)
    p.add_argument("--mode", default="greedy",
                   choices=["beam", "doubling", "greedy"])
    p.add_argument("--beam", type=int, default=32)
    p.add_argument("--expand-width", type=int, default=4,
                   help="frontier nodes expanded per search iteration")
    p.add_argument("--corpus-dtype", default="float32",
                   choices=["float32", "bfloat16", "int8"],
                   help="corpus storage dtype: int8 runs the quantized "
                        "two-pass pipeline (guard-banded search + exact "
                        "boundary rerank)")
    p.add_argument("--tier", action="store_true",
                   help="tiered corpus: keep only codes+meta device-resident "
                        "and serve the guard-band rerank from a host-RAM "
                        "raw-row store (implies --corpus-dtype int8)")
    p.add_argument("--resident-mb", type=float, default=None,
                   help="device row-cache budget for --tier, in MB "
                        "(default: n/8 rows)")
    p.add_argument("--early-stop", action="store_true")
    p.add_argument("--max-batch", type=int, default=128)
    p.add_argument("--mixed-radius", action="store_true",
                   help="per-request radii spread across the match "
                        "distribution instead of one shared radius")
    p.add_argument("--churn", type=float, default=0.0,
                   help="serve from a live index with this fraction of the "
                        "corpus inserted AND deleted during the run "
                        "(interleaved with the query traffic)")
    p.add_argument("--continuous", action="store_true",
                   help="continuous batching: saturated lanes ride a "
                        "persistent pool instead of lockstepping their "
                        "micro-batch (greedy mode only)")
    p.add_argument("--lanes", type=int, default=32,
                   help="continuous-mode lane pool width (rounded to pow2)")
    p.add_argument("--slice-rounds", type=int, default=8,
                   help="greedy expansions per pooled lane per server step")
    p.add_argument("--effort", action="store_true",
                   help="fit an effort regressor on a workload sample and "
                        "split admissions into cheap/heavy dispatches")
    p.add_argument("--heavy-frac", type=float, default=0.0,
                   help="fraction of requests given a dense-region radius "
                        "(tail-latency workload)")
    p.add_argument("--filter-frac", type=float, default=0.0,
                   help="fraction of range requests carrying a label "
                        "predicate (the corpus gets synthetic per-point "
                        "labels; AP is scored against the post-filtered "
                        "oracle)")
    p.add_argument("--num-labels", type=int, default=16,
                   help="synthetic label vocabulary size for --filter-frac")
    p.add_argument("--shards", type=int, default=0,
                   help="serve through the fault-tolerant host fan-out over "
                        "this many shards (0 = single frozen index)")
    p.add_argument("--replicas", type=int, default=1,
                   help="R-way shard replication (implies --shards serving; "
                        "coverage stays 1.0 under loss of R-1 replicas of "
                        "any shard)")
    p.add_argument("--hedge-ms", type=float, default=0.0,
                   help="hedge delay in ms: fire the next replica when the "
                        "primary is slower than this (0 disables hedging)")
    p.add_argument("--down-replicas", default="",
                   help="scripted replica loss, e.g. '0:0,1:1' downs shard "
                        "0's replica 0 and shard 1's replica 1")
    args = p.parse_args(argv)
    enable_compile_cache()
    if args.tier:
        args.corpus_dtype = "int8"  # tiering exists for the quantized split

    if args.churn > 0:
        return _churn_main(args)
    if args.shards > 0 or args.replicas > 1:
        return _replicated_main(args)

    print(f"[serve] corpus {args.profile} n={args.n}")
    ds = make_corpus(args.profile, n=args.n, n_queries=args.queries)
    pts = jnp.asarray(ds.points)
    qs = ds.queries
    r, gi, prof = select_serving_radius(ds.points, qs, ds.metric)

    raw_labels = None
    labels_packed = None
    if args.filter_frac > 0:
        # synthetic per-point labels: 1-3 ids each from a small vocabulary
        # (the category/attribute tags real filtered-search corpora carry)
        lrng = np.random.default_rng(7)
        raw_labels = [list(lrng.choice(args.num_labels,
                                       size=int(lrng.integers(1, 4)),
                                       replace=False))
                      for _ in range(args.n)]
        labels_packed = pack_labels(raw_labels, args.num_labels)
        print(f"[serve] labeled corpus: {args.num_labels}-label vocabulary, "
              f"1-3 labels/point")

    t0 = time.perf_counter()
    eng = RangeSearchEngine.build(
        pts, BuildConfig(max_degree=32, beam=64, metric=ds.metric),
        metric=ds.metric, corpus_dtype=args.corpus_dtype,
        labels=labels_packed, tier=args.tier, resident_mb=args.resident_mb)
    print(f"[serve] index built in {time.perf_counter() - t0:.1f}s "
          f"{eng.stats()}")
    if args.tier:
        bud = eng.points.budget()
        print(f"[serve] tiered corpus: device={bud.device_total} B "
              f"({bud.device_bytes_per_vector(args.n):.1f} B/vec) "
              f"host={bud.host_total} B; breakdown={bud.as_dict()}")

    rng = np.random.default_rng(0)
    if args.mixed_radius:
        # spread per-request radii across the sweep grid around the selected
        # radius: tight (near-duplicate) through wide (recommendation) lanes
        # interleaved in the same micro-batches
        lo = float(prof.radii[max(gi - 6, 0)])
        hi = float(prof.radii[min(gi + 4, len(prof.radii) - 1)])
        radii = np.linspace(lo, hi, args.queries).astype(np.float32)
        rng.shuffle(radii)  # mix radii *within* batches, not across them
        print(f"[serve] mixed radii in [{lo:.4g}, {hi:.4g}]")
    else:
        radii = np.full(args.queries, r, np.float32)
    if args.heavy_frac > 0:
        # tail-latency workload: a slice of the traffic queries at the top
        # of the sweep grid (dense-region, phase-2-bound) while the rest
        # stay point-like — the regime continuous batching exists for
        hi = float(prof.radii[-1])
        nh = max(int(args.heavy_frac * args.queries), 1)
        radii[rng.choice(args.queries, nh, replace=False)] = hi
        print(f"[serve] heavy traffic: {nh} requests at radius {hi:.4g}")
    filt_of = [None] * args.queries
    fmode = ["and"] * args.queries
    if args.filter_frac > 0:
        # a slice of the traffic filters: mostly single-label AND lanes,
        # every fourth a two-label OR (broader posting list) — filtered and
        # plain requests deliberately share micro-batches
        nf = max(int(args.filter_frac * args.queries), 1)
        for qi in rng.choice(args.queries, nf, replace=False):
            if qi % 4 == 3:
                filt_of[qi] = [int(x) for x in
                               rng.choice(args.num_labels, 2, replace=False)]
                fmode[qi] = "or"
            else:
                filt_of[qi] = [int(rng.integers(args.num_labels))]
        print(f"[serve] filtered traffic: {nf}/{args.queries} requests "
              f"carry label predicates")

    rcfg = serving_range_cfg(
        ds.metric, beam=args.beam, expand_width=args.expand_width,
        corpus_dtype=args.corpus_dtype, mode=args.mode,
        early_stop=args.early_stop)
    effort = None
    if args.effort:
        # calibrate the admission regressor on exact match counts for a
        # sample of the workload (production: observed counts of answered
        # traffic; here the oracle is cheap)
        from ..models.effort import EffortPredictor
        samp = min(256, args.queries)
        _, _, c = exact_range_search(pts, jnp.asarray(qs[:samp]),
                                     jnp.asarray(radii[:samp]), ds.metric)
        effort = EffortPredictor.fit(qs[:samp], radii[:samp], np.asarray(c))
        print(f"[serve] effort regressor fitted on {samp} samples")
    srv = RangeServer(eng, rcfg,
                      ServerConfig(max_batch=args.max_batch,
                                   es_radius_factor=1.5 if args.early_stop else 0.0,
                                   continuous=args.continuous,
                                   lanes=args.lanes,
                                   slice_rounds=args.slice_rounds),
                      effort=effort)
    out = serve_lockstep(srv, pts, qs, radii, metric=ds.metric,
                         filt_of=filt_of, fmode=fmode, raw_labels=raw_labels)
    lat = out["latency_ms"]
    print(f"[serve] {args.queries} queries in {out['seconds']:.3f}s = "
          f"{out['qps']:.0f} QPS (batched); AP={out['ap']:.4f}")
    print(f"[serve] latency p50={lat[len(lat)//2]:.1f}ms "
          f"p99={lat[int(len(lat)*0.99)]:.1f}ms; stats={srv.stats}")
    hs = srv.latency_summary()
    print(f"[serve] histogram p50/p95/p99 (ms): "
          + " ".join(f"{op}={h['p50_ms']:.1f}/{h['p95_ms']:.1f}/{h['p99_ms']:.1f}"
                     for op, h in hs.items() if h["count"]))
    if args.continuous:
        st = srv.stats
        print(f"[serve] pool: admitted={st['pool_admitted']} "
              f"oneshot={st['pool_oneshot']} ticks={st['pool_ticks']} "
              f"rotations={st['pool_rotations']} "
              f"buckets cheap/heavy={st['bucket_cheap']}/{st['bucket_heavy']}")
    if args.filter_frac > 0:
        st = srv.stats
        print(f"[serve] filtered: requests={st['filtered_requests']} "
              f"batches={st['filtered_batches']}/{st['batches']} "
              f"(AP above scored vs the post-filtered oracle)")
    disp = srv.radius_dispersion()
    print(f"[serve] radius dispersion mean={disp['mean']:.4g} "
          f"std={disp['std']:.4g} range=[{disp['min']:.4g}, {disp['max']:.4g}] "
          f"mixed_batches={disp['mixed_radius_batches']}")
    if args.corpus_dtype == "int8":
        served = max(srv.stats["served"], 1)
        print(f"[serve] quantized corpus: "
              f"{eng.stats()['hot_bytes_per_vector']} hot bytes/vector "
              f"(f32: {4 * ds.points.shape[1]}), "
              f"guard-band reranks/query="
              f"{srv.stats['reranked'] / served:.2f}")
    if args.tier:
        print(f"[serve] tier fetch path: {eng.points.counters.as_dict()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
