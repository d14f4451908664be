"""Benchmark driver: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick (3 profiles)
  PYTHONPATH=src python -m benchmarks.run --full     # all 9 profiles
  PYTHONPATH=src python -m benchmarks.run --scale    # + Fig7 densification

Corpora are synthetic with paper-matched range characteristics
(data/synthetic.py); absolute QPS is CPU-scale, the paper's *qualitative*
claims (speedup ordering, early-stop separation, greedy-vs-doubling
crossover) are what each section validates.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# machine-readable perf trajectory, one record per CI run (uploaded as an
# artifact so QPS/AP are comparable across PRs without log scraping)
SMOKE_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_smoke.json")


# mixed-radius gate: max AP a heterogeneous batch may lose vs dispatching
# each radius level as its own homogeneous batch (recorded in floors too)
MAX_MIXED_AP_GAP = 0.005

# quantized-corpus gates. The AP gap bounds what int8 storage + guard-band
# rerank may cost in result quality end to end — it is deterministic on the
# fixed smoke corpus and the real correctness contract (the oracle tests
# additionally prove exact post-rerank sets). The perf gate is the
# *roofline term* the quantization exists for: hot-loop corpus bytes per
# distance must drop >= 3x (int8 codes + 12B metadata vs 4d f32 — the
# binding constraint of the TPU deployment, README "Memory footprint &
# quantization"). Wall-clock QPS ratios (end-to-end and hot-path) are
# RECORDED but not gated: across repeated runs on shared 2-core CI boxes
# they swing ~0.7-1.8x with the cache regime and noisy neighbors (measured;
# see the record's note), which would make any fixed floor flaky. On the
# XLA CPU backend the e2e ratio hovers around 0.9-1.0x — the loop is
# dominated by dtype-independent merge/scatter work and gathers stay
# cache-resident at smoke scale; the e2e payoff belongs to the TPU path
# (Pallas int8 kernels + the HBM cut this gate pins).
MAX_QUANTIZED_AP_GAP = 0.01
MIN_QUANTIZED_BYTES_REDUCTION = 3.0

# tiered-corpus gates. The tier moves the raw f32 rerank rows off device
# (host-RAM row store) while int8 codes + 12B meta stay resident, so the
# two gated claims are (a) STRUCTURAL: device corpus bytes per vector
# (codes + meta + the bounded row cache, from the measured MemoryBudget)
# must drop >= 3x vs f32-resident, with the row cache pinned to <= 25% of
# the raw-row bytes it replaces (else the "tier" is quietly re-residenting
# the corpus); and (b) BITWISE: results must be identical to the resident
# int8 engine on the same graph — ids, dists, count, every bit. Not an AP
# gap of zero, actual array equality: the tiered exact_pairs contract is
# that cache state, fetch bucketing, and eviction history can never change
# a result bit. Fetch-path telemetry (dedup ratio, cache hit rate, rows/
# bytes fetched) is recorded for trajectory tracking, not gated (it shifts
# with REPRO_TIER_CACHE_ROWS, which the CI memcap job deliberately
# shrinks).
MIN_TIER_DEVICE_BYTES_REDUCTION = 3.0
MAX_TIER_CACHE_FRAC_OF_RAW = 0.25

# live-churn gate: after 10% churn (inserts + tombstoned deletes) and a
# consolidation pass, AP on the live set may trail a FRESH static rebuild of
# the same live set by at most this much — the acceptance bound on what
# streaming mutation costs versus batch reindexing. Deterministic on the
# fixed smoke corpus; wall-clock mutation rates are recorded, not gated
# (same CI-noise rationale as the quantized row).
MAX_CHURN_AP_GAP = 0.02

# tail-latency gates: on a mixed point+heavy workload (every lockstep
# micro-batch carries one dense-region straggler), continuous batching must
# cut the POINT queries' p99 to at most this fraction of the lockstep
# baseline's — the lockstep-break claim itself, measured as a ratio so the
# gate survives CI wall-clock noise (both sides run on the same box seconds
# apart). The AP gap gate pins that the latency win is not bought with
# accuracy: sliced pool execution must answer within this of lockstep.
MAX_TAIL_P99_RATIO = 0.5
MAX_TAIL_AP_GAP = 0.005

# degraded-serving gates. Shard loss: permanently losing 1 of 4 shards must
# keep AP at >= this fraction of the healthy run's (the corpus partitions
# ~uniformly, so 3/4 coverage holds ~75% of the matches; 0.70 leaves
# distribution skew headroom), with the degradation honestly annotated
# (coverage 0.75, shards_ok 3/4, code shard_lost). Deadline: lanes that
# COMPLETE under a p50-latency deadline return full (bitwise-identical to
# no-deadline) answers, so their AP must hold this fraction of the healthy
# run's AP over the SAME lanes (bitwise identity makes the true ratio 1.0;
# the floor leaves only float/accounting headroom) — which lanes complete
# varies with CI wall clock, but each complete lane's answer does not, so
# only a certification bug (a corrupted result stamped complete) can trip
# it. Expired lanes return certified partials and are recorded (coverage),
# not gated — their count is wall-clock dependent.
MIN_DEGRADED_AP_FRAC = 0.70
MIN_DEADLINE_COMPLETE_AP_FRAC = 0.90

# filtered-retrieval gate: AP of predicate push-down search (scored against
# the post-filtered brute-force oracle) may trail the unfiltered AP (scored
# against the unfiltered oracle) by at most this much. The filtered walk is
# the unfiltered walk with a result-stage gate — filtering never changes
# routing on the fused path and can only improve it on the compacted path
# (entry reseeding from the posting list) — so any larger gap means the
# predicate is leaking into the traversal. The selective-lane fallback
# speedup is RECORDED, not gated (CI wall-clock noise; the structural fact
# that fallback lanes bypass the graph IS gated via n_visited == 0).
MAX_FILTERED_AP_GAP = 0.01


def smoke(n: int, min_qps: float, min_ap: float) -> int:
    """CI gate: one tiny corpus through ``range_search_compacted``; exits
    nonzero when QPS falls below ``min_qps`` (order-of-magnitude regression
    guard — CI boxes are slow, so the floor is deliberately conservative)
    or AP below ``min_ap``. Runs the multi-node expansion config (E=4)
    against the single-node baseline (E=1) and records both in
    ``BENCH_smoke.json``; the gate applies to the E=4 numbers.

    The radius targets ~128 matches/query (picked off the sweep grid), the
    paper's match-dense regime (SSNPP/Fig. 4): range retrieval's cost there
    is dominated by the greedy result-expansion phase, which is exactly what
    the multi-node/bitset rework accelerates — and what serving traffic pays
    for. (At near-zero match counts the search is gather-bandwidth-bound and
    E barely matters; that regime is covered by qps_precision.py.)"""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        RangeConfig, SearchConfig, average_precision, exact_range_search,
    )

    from .common import ap_of, get_dataset, get_engine, run_range

    # default n_queries so get_engine's internal get_dataset is a cache hit
    # (a different n_queries would rebuild the grid sweep + ground truth)
    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs = qs[:128]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    eng = get_engine("bigann-like", n)

    def measure(expand_width: int):
        cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32,
                                              visit_cap=128, metric=ds.metric,
                                              expand_width=expand_width),
                          mode="greedy", result_cap=1024)
        qps, res = run_range(eng, qs, r, cfg)
        return cfg, dict(
            qps=round(qps, 2),
            ap=round(ap_of(res, gt), 4),
            mean_n_dist=round(float(np.asarray(res.n_dist).mean()), 1),
            mean_n_visited=round(float(np.asarray(res.n_visited).mean()), 1),
        )

    cfg, rec = measure(expand_width=4)
    _, base = measure(expand_width=1)
    speedup = rec["qps"] / max(base["qps"], 1e-9)
    print(f"[smoke] range_search_compacted: n={n} expand_width=4 "
          f"qps={rec['qps']:.1f} ap={rec['ap']:.4f} "
          f"(floors: qps>={min_qps}, ap>={min_ap})")
    print(f"[smoke] expand_width=1 baseline: qps={base['qps']:.1f} "
          f"ap={base['ap']:.4f} -> E=4 speedup {speedup:.2f}x")

    # -- mixed-radius row: heterogeneous batches are the serving regime -----
    # per-query radii log-spaced across the match distribution (from the
    # capture-curve sweep: the span whose mean counts cover ~2..~512
    # matches/query), round-robin across lanes so every micro-batch mixes
    # near-duplicate-tight and recommendation-wide radii
    lo_i = int(np.argmin(np.abs(mean_counts - 2.0)))
    hi_i = int(np.argmin(np.abs(mean_counts - 512.0)))
    n_distinct = 8
    levels = np.geomspace(float(prof.radii[lo_i]), float(prof.radii[hi_i]),
                          n_distinct).astype(np.float32)
    radii = levels[np.arange(qs.shape[0]) % n_distinct]
    gt_mix = exact_range_search(pts, qs, jnp.asarray(radii), ds.metric)
    mix_cfg = cfg  # same E=4 config as the main row: the two stay comparable
    mix_qps, mix_res = run_range(eng, qs, jnp.asarray(radii), mix_cfg)
    mix_ap = ap_of(mix_res, gt_mix)
    # homogeneous-dispatch reference: each radius level served in its own
    # batch (what a radius-bucketing server would do); the mixed batch must
    # match its AP — heterogeneity is free accuracy-wise
    hom_ids = np.zeros_like(np.asarray(mix_res.ids))
    hom_counts = np.zeros_like(np.asarray(mix_res.count))
    for k, lv in enumerate(levels):
        lanes = np.nonzero(np.arange(qs.shape[0]) % n_distinct == k)[0]
        sub = eng.range(qs[lanes], float(lv), cfg=mix_cfg)
        hom_ids[lanes] = np.asarray(sub.ids)
        hom_counts[lanes] = np.asarray(sub.count)
    hom_ap = average_precision(np.asarray(gt_mix[0]), np.asarray(gt_mix[2]),
                               hom_ids, hom_counts)
    ap_gap = abs(mix_ap - hom_ap)
    mixed = dict(
        qps=round(mix_qps, 2), ap=round(mix_ap, 4),
        ap_homogeneous=round(hom_ap, 4), ap_gap=round(ap_gap, 5),
        radius_lo=float(levels[0]), radius_hi=float(levels[-1]),
        n_distinct_radii=n_distinct,
        mean_matches=round(float(np.asarray(gt_mix[2]).mean()), 1),
    )
    print(f"[smoke] mixed-radius batch: qps={mix_qps:.1f} ap={mix_ap:.4f} "
          f"(homogeneous dispatch ap={hom_ap:.4f}, gap={ap_gap:.5f}; "
          f"radii {levels[0]:.3g}..{levels[-1]:.3g})")

    # -- churn row: live mutation vs a fresh static rebuild ------------------
    churn = _churn_row(n)
    print(f"[smoke] churn 10%: live ap={churn['ap_live']:.4f} vs fresh "
          f"rebuild ap={churn['ap_rebuild']:.4f} "
          f"(gap {churn['ap_gap']:+.4f}, floor {MAX_CHURN_AP_GAP}); "
          f"query qps live {churn['qps_live']:.1f} vs static "
          f"{churn['qps_static']:.1f}; "
          f"{churn['inserts_per_s']:.0f} inserts/s, "
          f"{churn['deletes_per_s']:.0f} deletes/s, consolidation "
          f"{churn['consolidate_s']:.2f}s")

    # -- quantized-corpus row: int8 two-pass vs f32, same graph --------------
    # measured on gist-like (d=256): the gather-bound regime the quantized
    # pipeline targets — corpus bytes per distance dominate as d grows
    quantized = _quantized_row(n)
    print(f"[smoke] quantized (gist-like d={quantized['dim']}): "
          f"e2e int8 {quantized['engine']['qps_int8']:.1f} qps vs f32 "
          f"{quantized['engine']['qps_f32']:.1f} "
          f"({quantized['engine']['speedup']:.2f}x), "
          f"ap gap {quantized['engine']['ap_gap']:+.4f}, "
          f"rerank band {quantized['engine']['rerank_per_query']:.1f}/query")
    print(f"[smoke] quantized hot path (bulk gather+distance): int8 "
          f"{quantized['hot_path']['speedup']:.2f}x f32 "
          f"({quantized['hot_path']['bytes_per_dist_f32']:.0f} -> "
          f"{quantized['hot_path']['bytes_per_dist_int8']:.0f} "
          f"bytes/distance)")

    # -- tiered row: host-RAM raw rows, device codes + bounded cache ---------
    tiered = _tiered_row(n)
    tm, tf = tiered["memory"], tiered["fetch"]
    print(f"[smoke] tiered (gist-like d={tiered['dim']}): device "
          f"{tm['device_bytes_per_vector']:.0f} B/vec vs f32-resident "
          f"{tm['f32_resident_bytes'] // n} -> "
          f"{tm['device_bytes_reduction_vs_f32']:.2f}x "
          f"(floor {MIN_TIER_DEVICE_BYTES_REDUCTION}); cache "
          f"{tm['cache_rows']} rows = {tm['cache_frac_of_raw']:.3f} of raw "
          f"(cap {MAX_TIER_CACHE_FRAC_OF_RAW}); bitwise_identical="
          f"{tiered['bitwise_identical']}")
    print(f"[smoke] tiered fetch path: dedup {tf['dedup_ratio']:.2f}x "
          f"({tf['pairs']} pairs -> {tf['unique_rows']} unique), cache hit "
          f"rate {tf['cache_hit_rate']:.3f}, {tf['fetched_rows']} rows / "
          f"{tf['fetch_batches']} buckets fetched; qps ratio vs resident "
          f"int8 {tiered['qps_ratio']:.2f}x")

    # -- heavy-tail row: radius methodology on an adversarial workload -------
    heavy = _heavy_tail_row(min(n, 4_000))
    print(f"[smoke] heavy-tail radius (recorded): zero_frac="
          f"{heavy['zero_frac']:.3f} max_count={heavy['max_count']} "
          f"median_nonzero={heavy['median_nonzero']} top-10% queries hold "
          f"{heavy['top10pct_match_mass']:.2f} of all matches; "
          f"hist={heavy['histogram']}")

    # -- tail-latency row: continuous batching vs lockstep -------------------
    tail = _tail_latency_row(n)
    print(f"[smoke] tail latency (point queries, {tail['n_point']} of "
          f"{tail['n_queries']}): continuous p99 "
          f"{tail['continuous']['point_p99_ms']:.1f}ms vs lockstep "
          f"{tail['lockstep']['point_p99_ms']:.1f}ms -> ratio "
          f"{tail['point_p99_ratio']:.3f} (floor {MAX_TAIL_P99_RATIO}); "
          f"ap {tail['continuous']['ap']:.4f} vs "
          f"{tail['lockstep']['ap']:.4f} (gap {tail['ap_gap']:.5f})")

    # -- degraded row: shard loss + deadline partials ------------------------
    degraded = _degraded_row(n)
    sl, dl = degraded["shard_loss"], degraded["deadline"]
    print(f"[smoke] shard loss (1 of {sl['shards_total']}): degraded "
          f"ap={sl['ap_degraded']:.4f} vs healthy {sl['ap_healthy']:.4f} "
          f"-> frac {sl['ap_frac']:.3f} (floor {MIN_DEGRADED_AP_FRAC}); "
          f"coverage={sl['coverage']} shards_ok={sl['shards_ok']}/"
          f"{sl['shards_total']} code={sl['code']}")
    dl_frac = dl["ap_frac"]
    print(f"[smoke] deadline at p50 ({dl['deadline_s'] * 1e3:.1f}ms): "
          f"{dl['n_complete']}/{dl['n_queries']} lanes complete, "
          f"ap(complete)={dl['ap_complete_lanes']} vs healthy same-lane "
          f"{dl['ap_healthy_same_lanes']} -> frac "
          f"{'n/a' if dl_frac is None else f'{dl_frac:.4f}'} "
          f"(floor {MIN_DEADLINE_COMPLETE_AP_FRAC}); "
          f"{dl['n_partial']} certified partials, mean coverage "
          f"{dl['mean_partial_coverage']}")

    # -- replicated row: R=2 absorbs replica loss; hedging hides slowness ----
    replicated = _replicated_row(n)
    rl, rh = replicated["replica_loss"], replicated["hedged"]
    print(f"[smoke] replicated (R={replicated['replicas']}, one replica of "
          f"each shard down): coverage={rl['coverage']} code={rl['code']} "
          f"bitwise_identical={rl['bitwise_identical']} replicas_ok="
          f"{rl['replicas_ok']}/{rl['replicas_total']}; "
          f"R=1 baseline coverage={replicated['baseline_r1_coverage']}")
    print(f"[smoke] hedged (scripted-slow primaries, delay=0): "
          f"hedges_fired={rh['hedges_fired']} hedge_wins={rh['hedge_wins']} "
          f"bitwise_identical={rh['bitwise_identical']} "
          f"ap_gap={rh['ap_gap']:+.5f}")

    # -- filtered row: predicate push-down vs the post-filtered oracle -------
    filtered = _filtered_row(n)
    print(f"[smoke] filtered (selective AND ~{filtered['selective_frac']:.2f}"
          f" / broad OR ~{filtered['broad_frac']:.2f} of corpus): "
          f"ap={filtered['ap_filtered']:.4f} vs unfiltered "
          f"{filtered['ap_unfiltered']:.4f} "
          f"(gap {filtered['ap_gap']:+.4f}, floor {MAX_FILTERED_AP_GAP}); "
          f"fallback on {filtered['n_fallback_lanes']} selective lanes -> "
          f"{filtered['fallback_speedup']:.2f}x walk qps")

    record = dict(
        bench="smoke", n=n, n_queries=int(qs.shape[0]), radius=float(r),
        mean_matches=round(float(np.asarray(gt[2]).mean()), 1),
        config=dataclasses.asdict(cfg), **rec,
        baseline_expand1=base, speedup_vs_expand1=round(speedup, 3),
        mixed_radius=mixed,
        quantized=quantized,
        tiered=tiered,
        heavy_tail=heavy,
        churn=churn,
        tail_latency=tail,
        degraded=degraded,
        replicated=replicated,
        filtered=filtered,
        floors=dict(min_qps=min_qps, min_ap=min_ap,
                    max_mixed_ap_gap=MAX_MIXED_AP_GAP,
                    max_quantized_ap_gap=MAX_QUANTIZED_AP_GAP,
                    min_quantized_bytes_reduction=MIN_QUANTIZED_BYTES_REDUCTION,
                    min_tier_device_bytes_reduction=MIN_TIER_DEVICE_BYTES_REDUCTION,
                    max_tier_cache_frac_of_raw=MAX_TIER_CACHE_FRAC_OF_RAW,
                    tier_bitwise_identical=True,
                    max_churn_ap_gap=MAX_CHURN_AP_GAP,
                    max_tail_p99_ratio=MAX_TAIL_P99_RATIO,
                    max_tail_ap_gap=MAX_TAIL_AP_GAP,
                    min_degraded_ap_frac=MIN_DEGRADED_AP_FRAC,
                    min_deadline_complete_ap_frac=MIN_DEADLINE_COMPLETE_AP_FRAC,
                    replicated_coverage=1.0, min_hedges_fired=1,
                    max_filtered_ap_gap=MAX_FILTERED_AP_GAP),
        timestamp=time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    )
    with open(SMOKE_JSON, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"[smoke] trajectory record -> {SMOKE_JSON}")

    if rec["qps"] < min_qps or rec["ap"] < min_ap:
        print("[smoke] FAIL: below regression floor")
        return 1
    if ap_gap > MAX_MIXED_AP_GAP:
        print("[smoke] FAIL: mixed-radius batch AP deviates from "
              "homogeneous dispatch")
        return 1
    if quantized["engine"]["ap_gap"] > MAX_QUANTIZED_AP_GAP:
        print("[smoke] FAIL: quantized-corpus AP gap above floor")
        return 1
    hp = quantized["hot_path"]
    if (hp["bytes_per_dist_f32"] / hp["bytes_per_dist_int8"]
            < MIN_QUANTIZED_BYTES_REDUCTION):
        print("[smoke] FAIL: int8 bytes-per-distance reduction below floor")
        return 1
    if not tiered["bitwise_identical"]:
        print("[smoke] FAIL: tiered results deviate from the resident int8 "
              "engine — the exact_pairs bitwise-parity contract is broken")
        return 1
    if (tiered["memory"]["device_bytes_reduction_vs_f32"]
            < MIN_TIER_DEVICE_BYTES_REDUCTION):
        print("[smoke] FAIL: tiered device bytes/vector reduction vs "
              "f32-resident below floor")
        return 1
    if tiered["memory"]["cache_frac_of_raw"] > MAX_TIER_CACHE_FRAC_OF_RAW:
        print("[smoke] FAIL: tiered row cache exceeds the resident-bytes "
              "cap — the tier is re-residenting the corpus")
        return 1
    if churn["ap_gap"] > MAX_CHURN_AP_GAP:
        print("[smoke] FAIL: churned live index trails a fresh rebuild by "
              "more than the AP floor")
        return 1
    if tail["point_p99_ratio"] > MAX_TAIL_P99_RATIO:
        print("[smoke] FAIL: continuous batching did not cut point-query "
              "p99 below the lockstep-ratio floor")
        return 1
    if tail["ap_gap"] > MAX_TAIL_AP_GAP:
        print("[smoke] FAIL: continuous batching AP deviates from lockstep")
        return 1
    if sl["ap_frac"] < MIN_DEGRADED_AP_FRAC:
        print("[smoke] FAIL: 1-of-4 shard loss dropped AP below the "
              "degraded floor")
        return 1
    if sl["shards_ok"] != 3 or sl["coverage"] != 0.75 or \
            sl["code"] != "shard_lost":
        print("[smoke] FAIL: shard-loss degradation not annotated "
              "(coverage/shards_ok/code)")
        return 1
    if dl_frac is not None and dl_frac < MIN_DEADLINE_COMPLETE_AP_FRAC:
        print("[smoke] FAIL: lanes marked complete under a deadline "
              "returned degraded answers (certification bug)")
        return 1
    if rl["coverage"] != 1.0 or rl["code"] != "replica_lost" or \
            not rl["bitwise_identical"]:
        print("[smoke] FAIL: R=2 did not absorb one-replica-per-shard loss "
              "(expected coverage 1.0, code replica_lost, bitwise-identical "
              "results)")
        return 1
    if rh["hedges_fired"] < 1 or not rh["bitwise_identical"]:
        print("[smoke] FAIL: hedge path not exercised or hedged results "
              "deviate from the healthy run")
        return 1
    if filtered["ap_gap"] > MAX_FILTERED_AP_GAP:
        print("[smoke] FAIL: filtered AP (vs post-filtered oracle) trails "
              "unfiltered AP beyond the floor — predicate is leaking into "
              "the traversal")
        return 1
    if filtered["n_fallback_lanes"] == 0:
        print("[smoke] FAIL: selective predicates never engaged the "
              "brute-scan fallback (n_visited stayed nonzero)")
        return 1
    return 0


def _filtered_row(n: int) -> dict:
    """Filtered-retrieval smoke: predicate push-down vs the post-filtered
    brute-force oracle, on the same corpus/graph/radius as the main row.

    Labels are synthetic (1-2 of 16 per point, seeded); lanes alternate a
    selective single-label AND (~9% of the corpus matches) and a broad
    4-label OR (~35%). Filtered AP is scored against the post-filtered
    oracle, unfiltered AP against the plain oracle — the gap is gated at
    MAX_FILTERED_AP_GAP. The selective lanes are then re-run with
    ``filter_threshold`` above their selectivity so the per-lane brute-scan
    fallback engages (proven via n_visited == 0); its speedup over the walk
    path on the same lanes is recorded."""
    import dataclasses as dc

    import numpy as np

    from repro.core import (
        RangeConfig, RangeSearchEngine, SearchConfig, average_precision,
        exact_range_search, label_match_counts, make_label_filter,
        pack_labels,
    )
    from repro.utils import INVALID_ID

    from .common import get_dataset, get_engine, run_range

    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs = qs[:128]
    nq = qs.shape[0]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    base = get_engine("bigann-like", n)
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=1024)

    num_labels = 16
    rng = np.random.default_rng(17)
    raw = [sorted(int(x) for x in
                  rng.choice(num_labels, size=int(rng.integers(1, 3)),
                             replace=False))
           for _ in range(int(pts.shape[0]))]
    eng = RangeSearchEngine(points=base.points, graph=base.graph,
                            start_ids=base.start_ids,
                            labels=pack_labels(raw, num_labels),
                            metric=base.metric)

    entries = [[q % num_labels] if q % 2 == 0
               else [(q + j) % num_labels for j in range(4)]
               for q in range(nq)]
    modes = ["and" if q % 2 == 0 else "or" for q in range(nq)]
    filt = make_label_filter(entries, num_labels, modes=modes)

    # post-filtered oracle: drop non-matching ids from the exact ground truth
    sets = [set(x) for x in raw]
    gt_ids = np.asarray(gt[0])
    gt_f_ids = np.full_like(gt_ids, INVALID_ID)
    gt_f_counts = np.zeros(nq, np.int64)
    for q in range(nq):
        pred = set(entries[q])
        keep = [int(i) for i in gt_ids[q][gt_ids[q] != INVALID_ID]
                if (pred <= sets[int(i)] if modes[q] == "and"
                    else bool(pred & sets[int(i)]))]
        gt_f_ids[q, :len(keep)] = keep
        gt_f_counts[q] = len(keep)

    qps_u, res_u = run_range(eng, qs, r, cfg)
    ap_u = float(average_precision(gt_ids, np.asarray(gt[2]),
                                   np.asarray(res_u.ids),
                                   np.asarray(res_u.count)))
    qps_f, res_f = run_range(eng, qs, r, cfg, filter=filt)
    ap_f = float(average_precision(gt_f_ids, gt_f_counts,
                                   np.asarray(res_f.ids),
                                   np.asarray(res_f.count)))

    # selectivity actually realized (posting-list fraction per lane kind)
    match = np.asarray(label_match_counts(eng.labels, filt)) / pts.shape[0]
    sel_frac = float(match[::2].mean())
    broad_frac = float(match[1::2].mean())

    # fallback speedup: selective lanes only, threshold above their
    # selectivity (x1.5 headroom) so every lane takes the brute scan
    sel = np.arange(0, nq, 2)
    qs_sel = qs[sel]
    filt_sel = make_label_filter([entries[i] for i in sel], num_labels,
                                 modes="and")
    thr = min(0.999, float(match[::2].max()) * 1.5)
    qps_walk, _ = run_range(eng, qs_sel, r, cfg, filter=filt_sel)
    qps_fb, res_fb = run_range(
        eng, qs_sel, r, dc.replace(cfg, filter_threshold=thr),
        filter=filt_sel)
    n_fallback = int((np.asarray(res_fb.n_visited) == 0).sum())

    return dict(
        num_labels=num_labels,
        selective_frac=round(sel_frac, 4), broad_frac=round(broad_frac, 4),
        qps_unfiltered=round(qps_u, 2), qps_filtered=round(qps_f, 2),
        ap_unfiltered=round(ap_u, 4), ap_filtered=round(ap_f, 4),
        ap_gap=round(ap_u - ap_f, 5),
        mean_matches_postfilter=round(float(gt_f_counts.mean()), 1),
        fallback_threshold=round(thr, 4),
        n_fallback_lanes=n_fallback, n_selective_lanes=int(sel.shape[0]),
        qps_selective_walk=round(qps_walk, 2),
        qps_selective_fallback=round(qps_fb, 2),
        fallback_speedup=round(qps_fb / max(qps_walk, 1e-9), 3),
    )


def _degraded_row(n: int) -> dict:
    """Fault-tolerant serving smoke: shard loss + deadline partials.

    Shard loss: 4-shard corpus through ``fault_tolerant_sharded_search``
    healthy, then with shard 1 permanently down (every attempt times out).
    The degraded merge is exact over surviving shards, so its AP tracks
    the surviving corpus fraction — gated at MIN_DEGRADED_AP_FRAC of the
    healthy AP, with the coverage/shards_ok/code annotations pinned.

    Deadline: the continuous server re-serves the smoke workload with each
    request's ``deadline_s`` set to the healthy run's p50 latency. Lanes
    that complete carry full answers (certified complete ⇒ bitwise equal
    to the no-deadline run), so AP restricted to them must hold
    MIN_DEADLINE_COMPLETE_AP_FRAC of the healthy run's AP over the same
    lanes; expired lanes come back as certified partials whose coverage is
    recorded, not gated (how many expire is CI wall-clock dependent, what
    each one contains is not)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        BuildConfig, RangeConfig, SearchConfig, average_precision,
        build_vamana, exact_range_search,
    )
    from repro.core.graph import medoid
    from repro.dist.sharded_engine import build_sharded
    from repro.fault import (
        FaultInjector, RetryPolicy, fault_tolerant_sharded_search,
    )
    from repro.serve import RangeServer, Request, ServerConfig
    from repro.utils import INVALID_ID

    from .common import get_dataset, get_engine

    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs = qs[:128]
    qs_np = np.asarray(qs)
    nq = qs_np.shape[0]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=1024)

    # -- shard loss: healthy vs 1-of-4 permanently down ----------------------
    # per-shard Vamana (not kNN): the smoke corpus is clustered, and a kNN
    # graph over well-separated clusters is disconnected — a medoid entry
    # point would strand most of the shard and crater the healthy baseline
    bcfg = BuildConfig(max_degree=24, beam=48, insert_batch=256,
                       two_pass=True, metric=ds.metric)
    corpus = build_sharded(np.asarray(pts), 4,
                           lambda p: (build_vamana(jnp.asarray(p), bcfg),
                                      medoid(p)[None]))

    def ap_of_res(res):
        return float(average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                                       np.asarray(res.ids),
                                       np.asarray(res.count)))

    fast_retry = RetryPolicy(max_attempts=2, backoff_s=0.0)
    healthy = fault_tolerant_sharded_search(corpus=corpus, queries=qs, r=r,
                                            cfg=cfg, retry=fast_retry)
    lost = fault_tolerant_sharded_search(
        corpus=corpus, queries=qs, r=r, cfg=cfg,
        injector=FaultInjector(seed=0, down_shards=(1,)), retry=fast_retry)
    ap_h, ap_d = ap_of_res(healthy.result), ap_of_res(lost.result)
    shard_loss = dict(
        shards_total=lost.shards_total, down_shards=[1],
        ap_healthy=round(ap_h, 4), ap_degraded=round(ap_d, 4),
        ap_frac=round(ap_d / max(ap_h, 1e-9), 4),
        coverage=round(lost.coverage, 4), shards_ok=lost.shards_ok,
        code=lost.code, attempts=np.asarray(lost.attempts).tolist(),
    )

    # -- deadline at the healthy run's p50 latency ---------------------------
    eng = get_engine("bigann-like", n)
    scfg = ServerConfig(max_batch=16, continuous=True, lanes=16,
                        slice_rounds=8)

    def drive(deadline_s=None):
        srv = RangeServer(eng, cfg, scfg)
        for i in range(nq):
            srv.submit(Request(req_id=i, query=qs_np[i], radius=r,
                               deadline_s=deadline_s))
        return srv.run_until_drained()

    drive()                 # warmup: compile phase1/pool/retire programs
    resp_h = drive()        # healthy pass: measures the p50 the deadline pins
    lat = sorted(rp.latency_s for rp in resp_h)
    p50 = lat[len(lat) // 2]
    resp_d = drive(deadline_s=p50)
    complete = [rp for rp in resp_d if rp.op == "range" and rp.complete]
    partial = [rp for rp in resp_d if not rp.complete]
    cap = cfg.result_cap

    def pack(resps, mask):
        ids = np.full((nq, cap), INVALID_ID, np.int64)
        counts = np.zeros(nq, np.int64)
        for rp in resps:
            if not mask[rp.req_id]:
                continue
            k = min(len(rp.ids), cap)
            ids[rp.req_id, :k] = np.asarray(rp.ids[:k])
            counts[rp.req_id] = k
        return (float(average_precision(np.asarray(gt[0])[mask],
                                        np.asarray(gt[2])[mask],
                                        ids[mask], counts[mask]))
                if mask.any() else None)

    mask = np.zeros(nq, bool)
    for rp in complete:
        mask[rp.req_id] = True
    # complete lanes are bitwise-identical to the no-deadline run, so AP
    # over them must match the healthy run's AP over the SAME lanes — the
    # gate is that ratio, immune to which lanes the wall clock let finish
    ap_complete = pack(resp_d, mask)
    ap_healthy_lanes = pack(resp_h, mask)
    ap_frac = (None if ap_complete is None
               else round(ap_complete / max(ap_healthy_lanes, 1e-9), 4))
    deadline = dict(
        n_queries=nq, deadline_s=round(p50, 5),
        n_complete=len(complete), n_partial=len(partial),
        ap_complete_lanes=(None if ap_complete is None
                           else round(ap_complete, 4)),
        ap_healthy_same_lanes=(None if ap_healthy_lanes is None
                               else round(ap_healthy_lanes, 4)),
        ap_frac=ap_frac,
        mean_partial_coverage=(
            round(float(np.mean([rp.coverage for rp in partial])), 4)
            if partial else None),
        note="ap_frac (complete lanes vs the healthy run on the same "
             "lanes) is the gated claim (deterministic per lane); the "
             "complete/partial split depends on CI wall clock and is "
             "recorded for trajectory tracking only",
    )
    return dict(n=n, radius=r, shard_loss=shard_loss, deadline=deadline)


def _replicated_row(n: int) -> dict:
    """Replicated-serving smoke: R=2 keeps the answer whole where R=1
    degrades, and hedging hides slow primaries at zero answer cost.

    Replica loss: the same 4-shard corpus as the degraded row, replicated
    2-way, searched with one replica of EVERY shard scripted down
    (alternating, so both replica slots are exercised). The surviving
    replica of each shard is bitwise-identical — replica choice is
    unobservable — so the gate is structural, not statistical:
    ``coverage == 1.0``, results bitwise-equal to the healthy
    single-replica run, and the response annotated ``replica_lost``
    (redundancy degraded, answer not). PR 7's shard-loss row stays as the
    R=1 baseline: same loss pattern without replication costs 25% of the
    corpus (coverage 0.75).

    Hedging: a fresh fleet with every shard's primary scripted ``slow``
    and a zero hedge delay — each shard fires exactly one hedge, the
    secondary wins, and the merged result is again bitwise-identical
    (zero AP gap by construction, asserted bitwise rather than via a
    float floor)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        BuildConfig, RangeConfig, SearchConfig, average_precision,
        build_vamana, exact_range_search,
    )
    from repro.core.graph import medoid
    from repro.dist.sharded_engine import build_sharded
    from repro.fault import (
        FaultInjector, HedgePolicy, ReplicaFleet, ReplicatedCorpus,
        RetryPolicy, fault_tolerant_sharded_search,
    )

    from .common import get_dataset

    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs = qs[:128]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=1024)
    bcfg = BuildConfig(max_degree=24, beam=48, insert_batch=256,
                       two_pass=True, metric=ds.metric)
    corpus = build_sharded(np.asarray(pts), 4,
                           lambda p: (build_vamana(jnp.asarray(p), bcfg),
                                      medoid(p)[None]))

    def ap_of(res):
        return float(average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                                       np.asarray(res.ids),
                                       np.asarray(res.count)))

    def bitwise(a, b):
        return bool(np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
                    and np.array_equal(np.asarray(a.dists),
                                       np.asarray(b.dists))
                    and np.array_equal(np.asarray(a.count),
                                       np.asarray(b.count)))

    fast_retry = RetryPolicy(max_attempts=2, backoff_s=0.0)
    healthy = fault_tolerant_sharded_search(corpus=corpus, queries=qs, r=r,
                                            cfg=cfg, retry=fast_retry)
    ap_h = ap_of(healthy.result)
    rep = ReplicatedCorpus.replicate(corpus, 2)

    # -- one replica of every shard down: R=2 keeps coverage at 1.0 ----------
    down = ((0, 0), (1, 1), (2, 0), (3, 1))
    fleet = ReplicaFleet(rep)
    lost = fault_tolerant_sharded_search(
        fleet=fleet, queries=qs, r=r, cfg=cfg,
        injector=FaultInjector(seed=0, down_replicas=down), retry=fast_retry)
    ap_l = ap_of(lost.result)
    replica_loss = dict(
        down_replicas=[list(p) for p in down],
        coverage=round(lost.coverage, 4), shards_ok=lost.shards_ok,
        code=lost.code, bitwise_identical=bitwise(lost.result, healthy.result),
        replicas_ok=lost.replicas_ok, replicas_total=lost.replicas_total,
        ap_healthy=round(ap_h, 4), ap_replicated=round(ap_l, 4),
        served_by=np.asarray(lost.served_by).tolist(),
    )

    # -- scripted-slow primaries + zero hedge delay: hedges win, zero gap ----
    fleet_h = ReplicaFleet(rep)
    hedged = fault_tolerant_sharded_search(
        fleet=fleet_h, queries=qs, r=r, cfg=cfg,
        injector=FaultInjector(
            seed=0, script={(s, 0, 0): "slow" for s in range(4)}),
        retry=fast_retry, hedge=HedgePolicy(delay_s=0.0))
    ap_hg = ap_of(hedged.result)
    hedged_row = dict(
        hedges_fired=int(fleet_h.stats["hedges_fired"]),
        hedge_wins=int(fleet_h.stats["hedge_wins"]),
        bitwise_identical=bitwise(hedged.result, healthy.result),
        ap_gap=round(ap_h - ap_hg, 6), code=hedged.code,
        served_by=np.asarray(hedged.served_by).tolist(),
    )

    return dict(n=n, radius=r, replicas=2,
                baseline_r1_coverage=0.75,
                replica_loss=replica_loss, hedged=hedged_row)


def _tail_latency_row(n: int) -> dict:
    """Continuous batching vs lockstep on a mixed point+heavy workload.

    128 bigann-like queries: 120 point-like (~4 matches) and 8 dense-region
    (~512 matches), one heavy lane leading each micro-batch of 16 — the
    adversarial case for lockstep execution, where every batch's point
    queries wait for the straggler's greedy phase. Both servers run the
    identical engine/config/workload seconds apart; a throwaway pass per
    mode warms the jit caches so the timed pass measures steady-state
    serving, not compilation. Percentiles here are EXACT (np.percentile
    over the retained per-response latencies) — the gate must not inherit
    the serving histogram's bucket quantization; the servers' log-bucket
    summaries are recorded alongside for the dashboard shape."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        RangeConfig, SearchConfig, average_precision, exact_range_search,
    )
    from repro.serve import RangeServer, Request, ServerConfig
    from repro.utils import INVALID_ID

    from .common import get_dataset, get_engine

    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs_np = np.asarray(qs[:128])
    nq = qs_np.shape[0]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r_point = float(prof.radii[int(np.argmin(np.abs(mean_counts - 4.0)))])
    r_heavy = float(prof.radii[int(np.argmin(np.abs(mean_counts - 512.0)))])
    radii = np.full(nq, r_point, np.float32)
    radii[::16] = r_heavy
    point = radii == r_point
    gt = exact_range_search(pts, jnp.asarray(qs_np), jnp.asarray(radii),
                            ds.metric)
    eng = get_engine("bigann-like", n)
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=1024)

    def drive(scfg):
        srv = RangeServer(eng, cfg, scfg)
        for i in range(nq):
            srv.submit(Request(req_id=i, query=qs_np[i],
                               radius=float(radii[i])))
        return srv, srv.run_until_drained()

    def score(srv, resp):
        cap = cfg.result_cap
        ids = np.full((nq, cap), INVALID_ID, np.int64)
        counts = np.zeros(nq, np.int64)
        lat = np.zeros(nq)
        for rp in resp:
            k = min(len(rp.ids), cap)
            ids[rp.req_id, :k] = np.asarray(rp.ids[:k])
            counts[rp.req_id] = k
            lat[rp.req_id] = rp.latency_s
        ap = average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                               ids, counts)
        return dict(
            ap=round(float(ap), 4),
            point_p50_ms=round(float(np.percentile(lat[point], 50)) * 1e3, 2),
            point_p95_ms=round(float(np.percentile(lat[point], 95)) * 1e3, 2),
            point_p99_ms=round(float(np.percentile(lat[point], 99)) * 1e3, 2),
            heavy_p99_ms=round(float(np.percentile(lat[~point], 99)) * 1e3, 2),
            histograms=srv.latency_summary(),
        )

    lock_cfg = ServerConfig(max_batch=16)
    cont_cfg = ServerConfig(max_batch=16, continuous=True, lanes=16,
                            slice_rounds=8)
    drive(lock_cfg)                      # warmup: compile the lockstep path
    drive(cont_cfg)                      # warmup: phase1/pool/retire programs
    srv_l, resp_l = drive(lock_cfg)
    srv_c, resp_c = drive(cont_cfg)
    lock = score(srv_l, resp_l)
    cont = score(srv_c, resp_c)
    cont["pool"] = {k: srv_c.stats[k] for k in
                    ("pool_admitted", "pool_retired", "pool_ticks",
                     "pool_rotations", "pool_oneshot")}
    return dict(
        n=n, n_queries=nq, n_point=int(point.sum()),
        radius_point=r_point, radius_heavy=r_heavy,
        lockstep=lock, continuous=cont,
        point_p99_ratio=round(cont["point_p99_ms"]
                              / max(lock["point_p99_ms"], 1e-9), 4),
        ap_gap=round(abs(lock["ap"] - cont["ap"]), 5),
        note="point_p99_ratio (continuous/lockstep, same box seconds apart) "
             "and ap_gap are the gated claims; heavy-lane p99 rises in "
             "continuous mode by design (stragglers trade their own "
             "latency for everyone else's tail)",
    )


def _churn_row(n: int) -> dict:
    """10% churn against the live index, scored vs a fresh static rebuild.

    Starting from the cached static engine's graph: insert n/10 fresh
    vectors, tombstone n/10 of the originals, consolidate, then compare AP
    on the exact live-set oracle against an engine REBUILT from scratch on
    the same live set — the gap is what streaming mutation costs vs batch
    reindexing (gated at MAX_CHURN_AP_GAP). Mutation rates and query QPS
    under tombstones are recorded alongside."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        RangeConfig, RangeSearchEngine, SearchConfig, average_precision,
        exact_range_search,
    )
    from repro.live import LiveConfig, LiveIndex
    from repro.utils import INVALID_ID, block_until_ready

    from .common import get_dataset, run_range

    ds, pts, qs, _, prof, _ = get_dataset("bigann-like", n)
    qs = qs[:128]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    k = max(n // 10, 1)

    # two-pass builds on BOTH sides: the single-pass batch build leaves
    # ~10% zero-in-degree (unreachable) nodes, and which points end up
    # orphaned is a per-build roll — at ap ~0.87 that seed variance (~0.03)
    # swamps the ~0.01 churn effect this gate exists to measure. The second
    # α pass reattaches orphans (both graphs reach ap ~0.99), so the gap is
    # churn damage, not orphan luck.
    live = LiveIndex.create(pts, LiveConfig(capacity=n + k, insert_batch=128),
                            _churn_build_cfg(ds.metric), metric=ds.metric)
    rng = np.random.default_rng(0)
    fresh = (np.asarray(pts)[rng.integers(0, n, k)]
             + rng.standard_normal((k, pts.shape[1])).astype(np.float32)
             * 0.05 * np.asarray(pts).std())
    t0 = _time.perf_counter()
    live.insert(fresh)
    t_ins = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    live.delete(rng.choice(n, k, replace=False))
    t_del = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    live.consolidate()
    t_cons = _time.perf_counter() - t0

    # exact oracle on the live set; both contenders answer in ext-id space
    ext, vecs = live.live_vectors()
    gt = exact_range_search(jnp.asarray(vecs), qs, r, ds.metric)
    lut = np.full(live.next_ext_id + 1, INVALID_ID, np.int64)
    lut[ext] = np.arange(len(ext))
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=1024)

    def live_qps():
        fn = lambda: live.range(qs, r, cfg=cfg)
        block_until_ready(fn().dists)
        ts = []
        res = None
        for _ in range(2):
            t0 = _time.perf_counter()
            res = fn()
            block_until_ready(res.dists)
            ts.append(_time.perf_counter() - t0)
        return qs.shape[0] / float(np.median(ts)), res

    qps_live, res_live = live_qps()
    ids_live = np.asarray(res_live.ids)
    rows_live = np.where(ids_live != INVALID_ID,
                         lut[np.minimum(ids_live, live.next_ext_id)],
                         np.int64(INVALID_ID))
    ap_live = average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                                rows_live, np.asarray(res_live.count))

    # fresh static rebuild on the same live set (row ids == oracle ids)
    t0 = _time.perf_counter()
    eng_fresh = RangeSearchEngine.build(jnp.asarray(vecs),
                                        _churn_build_cfg(ds.metric),
                                        metric=ds.metric)
    t_rebuild = _time.perf_counter() - t0
    qps_static, res_fresh = run_range(eng_fresh, qs, r, cfg)
    ap_rebuild = average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                                   np.asarray(res_fresh.ids),
                                   np.asarray(res_fresh.count))
    return dict(
        n=n, churn_frac=round(k / n, 3), radius=r,
        ap_live=round(ap_live, 4), ap_rebuild=round(ap_rebuild, 4),
        ap_gap=round(ap_rebuild - ap_live, 5),
        qps_live=round(qps_live, 2), qps_static=round(qps_static, 2),
        inserts_per_s=round(k / max(t_ins, 1e-9), 1),
        deletes_per_s=round(k / max(t_del, 1e-9), 1),
        consolidate_s=round(t_cons, 3),
        rebuild_s=round(t_rebuild, 3),
        epochs=live.epoch,
        note="ap_gap (live vs fresh rebuild on the identical live set) is "
             "the gated claim; mutation rates and the QPS pair are "
             "recorded for trajectory tracking, not gated (CI wall-clock "
             "noise)",
    )


def _churn_build_cfg(metric: str):
    """Build config shared by the churn row's initial live graph AND its
    fresh-rebuild contender (the comparison must hold everything but the
    mutation path fixed). two_pass: see the note in _churn_row."""
    from repro.core import BuildConfig
    return BuildConfig(max_degree=24, beam=48, insert_batch=512,
                       metric=metric, two_pass=True)


def _quantized_row(n: int) -> dict:
    """Int8-corpus two-pass vs f32 on the same graph: e2e QPS + AP gap +
    rerank-band rate, plus the bulk gather+distance hot-path ratio and the
    bytes-per-distance table (see the MIN_QUANTIZED_BYTES_REDUCTION note
    for why the byte cut, not a wall-clock ratio, is the gated claim)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.roofline import corpus_bytes_per_distance
    from repro.core import (
        RangeConfig, RangeSearchEngine, SearchConfig, exact_range_search,
    )
    from repro.kernels import gatherdist_ref
    from repro.utils import block_until_ready

    from .common import ap_of, get_dataset, get_engine, run_range

    profile = "gist-like"
    ds, pts, qs, _, prof, _ = get_dataset(profile, n)
    qs = qs[:128]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    eng = get_engine(profile, n)
    # same graph and entry points; only the corpus storage differs
    eng_i8 = _dc.replace(
        RangeSearchEngine.from_graph(pts, eng.graph, metric=ds.metric,
                                     corpus_dtype="int8"),
        start_ids=eng.start_ids)
    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=2048)
    qps_f, res_f = run_range(eng, qs, r, cfg)
    qps_q, res_q = run_range(eng_i8, qs, r, cfg)
    ap_f, ap_q = ap_of(res_f, gt), ap_of(res_q, gt)

    # hot path: the in-loop bulk gather+distance op (tile shapes of the
    # fused expand: Q lanes x E*R candidates each), f32 rows vs int8
    # codes+metadata — the corpus-bytes roofline term itself
    t_tile = 128
    ids = jax.random.randint(jax.random.PRNGKey(0), (qs.shape[0], t_tile),
                             0, pts.shape[0], jnp.int32)
    f_f32 = jax.jit(lambda i, q: gatherdist_ref(pts, i, q, metric=ds.metric))
    f_i8 = jax.jit(lambda i, q: gatherdist_ref(eng_i8.points, i, q,
                                               metric=ds.metric))
    def wall(fn):
        block_until_ready(fn())
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))
    t_f = wall(lambda: f_f32(ids, qs))
    t_q = wall(lambda: f_i8(ids, qs))
    d = int(pts.shape[1])
    return dict(
        profile=profile, dim=d, radius=r,
        engine=dict(
            qps_f32=round(qps_f, 2), qps_int8=round(qps_q, 2),
            speedup=round(qps_q / max(qps_f, 1e-9), 3),
            ap_f32=round(ap_f, 4), ap_int8=round(ap_q, 4),
            ap_gap=round(ap_f - ap_q, 5),
            rerank_per_query=round(
                float(np.asarray(res_q.n_rerank).mean()), 1),
            mean_count=round(float(np.asarray(res_q.count).mean()), 1),
        ),
        hot_path=dict(
            tile=f"{qs.shape[0]}x{t_tile}x{d}",
            ms_f32=round(t_f * 1e3, 3), ms_int8=round(t_q * 1e3, 3),
            speedup=round(t_f / max(t_q, 1e-9), 3),
            bytes_per_dist_f32=corpus_bytes_per_distance(d, "float32"),
            bytes_per_dist_int8=corpus_bytes_per_distance(d, "int8"),
            note="wall ratios are cache-regime/noise dependent on CPU CI "
                 "(measured swing ~0.7-1.8x run to run) and are recorded, "
                 "not gated; the gated perf claim is the bytes/distance "
                 "roofline cut, which the Pallas int8 kernels realize on "
                 "TPU HBM",
        ),
    )


def _tiered_row(n: int) -> dict:
    """Tiered corpus vs resident int8 on the same graph: the device-bytes
    cut the tier exists for, proven at BITWISE result identity (see the
    MIN_TIER_DEVICE_BYTES_REDUCTION note). Same gist-like profile and
    config as _quantized_row so the f32 -> int8 -> tiered progression
    reads off one table."""
    import dataclasses as _dc

    import numpy as np

    from repro.core import (
        RangeConfig, RangeSearchEngine, SearchConfig, exact_range_search,
    )
    from repro.tier import tiered_corpus

    from .common import ap_of, get_dataset, get_engine, run_range

    profile = "gist-like"
    ds, pts, qs, _, prof, _ = get_dataset(profile, n)
    qs = qs[:128]
    mean_counts = np.asarray(prof.counts).mean(axis=0)
    r = float(prof.radii[int(np.argmin(np.abs(mean_counts - 128.0)))])
    gt = exact_range_search(pts, qs, r, ds.metric)
    eng = get_engine(profile, n)
    # resident int8 reference: same graph/entries, raw rows on device
    eng_i8 = _dc.replace(
        RangeSearchEngine.from_graph(pts, eng.graph, metric=ds.metric,
                                     corpus_dtype="int8"),
        start_ids=eng.start_ids)
    # tiered contender: identical codes (split from the SAME QuantizedCorpus,
    # raw rows move to the host store). Cache default n/32 rows (~3% of raw
    # bytes); the CI memcap env may shrink it further — parity must survive.
    cache_rows = int(os.environ.get("REPRO_TIER_CACHE_ROWS",
                                    max(1, n // 32)))
    tier = tiered_corpus(eng_i8.points, cache_rows=cache_rows)
    eng_tier = _dc.replace(eng_i8, points=tier)

    cfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32, visit_cap=128,
                                          metric=ds.metric, expand_width=4),
                      mode="greedy", result_cap=2048)
    qps_i8, res_i8 = run_range(eng_i8, qs, r, cfg)
    qps_t, res_t = run_range(eng_tier, qs, r, cfg)
    bitwise = bool(
        np.array_equal(np.asarray(res_t.ids), np.asarray(res_i8.ids)) and
        np.array_equal(np.asarray(res_t.dists), np.asarray(res_i8.dists)) and
        np.array_equal(np.asarray(res_t.count), np.asarray(res_i8.count)))

    d = int(pts.shape[1])
    budget = tier.budget()
    f32_resident = 4 * d * n  # the raw rows a resident f32 corpus parks in HBM
    reduction = f32_resident / max(1, budget.device_total)
    cache_frac = budget.device["row_cache"] / max(1, budget.host["row_store"])
    return dict(
        profile=profile, dim=d, radius=r,
        qps_int8=round(qps_i8, 2), qps_tiered=round(qps_t, 2),
        qps_ratio=round(qps_t / max(qps_i8, 1e-9), 3),
        ap_tiered=round(ap_of(res_t, gt), 4),
        bitwise_identical=bitwise,
        rerank_per_query=round(float(np.asarray(res_t.n_rerank).mean()), 1),
        memory=dict(
            **budget.as_dict(),
            device_bytes_per_vector=round(budget.device_bytes_per_vector(n), 1),
            f32_resident_bytes=f32_resident,
            device_bytes_reduction_vs_f32=round(reduction, 3),
            cache_rows=int(tier.cache.capacity),
            cache_frac_of_raw=round(cache_frac, 4),
        ),
        fetch=tier.counters.as_dict(),
        note="bitwise identity to resident int8 and the measured device-"
             "bytes cut are the gated claims; QPS ratio and fetch telemetry "
             "(dedup ratio, cache hit rate) are recorded for trajectory "
             "tracking, not gated",
    )


def _heavy_tail_row(n: int) -> dict:
    """RECORDED, not gated: the radius methodology (core/radius.py) on a
    lognormal planted-cluster corpus whose match counts are far heavier-
    tailed than the quantile-matched profiles — most queries zero matches,
    a few queries matching entire giant clusters. Exercises sweep /
    select_radius / match_histogram end to end and records the Fig. 4
    bucket table; wall-clock-free and deterministic, kept ungated because
    it validates the *methodology's* behavior on an adversarial input, not
    a perf or quality floor of the engine."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.radius import (
        default_grid, match_histogram, select_radius, sweep,
    )

    from .common import make_heavy_tailed

    pts, qs = make_heavy_tailed(n, d=32, n_queries=128, seed=0)
    grid = default_grid(pts, qs, "l2", num=32)
    prof = sweep(jnp.asarray(pts), jnp.asarray(qs), grid, "l2")
    r, gi = select_radius(prof, target_zero_frac=0.85, robustness_weight=0.2)
    counts = np.asarray(prof.counts)[:, gi]
    nz = np.sort(counts[counts > 0])
    # tail mass: fraction of ALL matches held by the top 10% of queries —
    # ~1.0 for a true heavy tail, ~0.1 for a uniform workload
    k = max(1, counts.size // 10)
    tail_mass = float(np.sort(counts)[-k:].sum() / max(1, counts.sum()))
    return dict(
        n=n, dim=32, radius=float(r),
        zero_frac=round(float(prof.zero_frac[gi]), 4),
        histogram=match_histogram(counts),
        mean_count=round(float(counts.mean()), 1),
        max_count=int(counts.max()),
        median_nonzero=0 if nz.size == 0 else int(np.median(nz)),
        top10pct_match_mass=round(tail_mass, 4),
        note="recorded only — validates radius selection + Fig. 4 "
             "bucketing on a heavy-tailed workload",
    )


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--full", action="store_true", help="all 9 dataset profiles")
    p.add_argument("--scale", action="store_true", help="include Fig7 scaling")
    p.add_argument("--n", type=int, default=10_000)
    p.add_argument("--smoke", action="store_true",
                   help="tiny-corpus QPS/AP regression gate (CI)")
    p.add_argument("--min-qps", type=float, default=5.0)
    p.add_argument("--min-ap", type=float, default=0.6)
    args = p.parse_args(argv)
    quick = not args.full
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        return smoke(min(args.n, 4_000), args.min_qps, args.min_ap)

    from . import (
        early_stop_metrics, early_stop_qps, kernel_bench, match_distribution,
        qps_precision, radius_capture, topk_compare,
    )

    t0 = time.time()
    print("== repro benchmarks (paper: Range Retrieval with Graph-Based "
          "Indices) ==")
    radius_capture.run(n=args.n, quick=quick)
    match_distribution.run(n=args.n, quick=quick)
    qps_precision.run(n=args.n, quick=quick)
    early_stop_metrics.run(n=args.n, quick=quick)
    early_stop_qps.run(n=args.n, quick=quick)
    topk_compare.run(n=args.n)
    kernel_bench.run()
    if args.scale:
        qps_precision.run_scaling(n=max(args.n // 2, 4000))
    print(f"\n== done in {time.time() - t0:.0f}s ==")
    return 0


if __name__ == "__main__":
    sys.exit(main())
