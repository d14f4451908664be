#!/usr/bin/env python3
"""Bring-up smoke of the served range path on TPU.

One chip (no arguments): one shard of the range-engine deployment
(``EngineDeployConfig``: 1,000,000 rows, d=128, R=32, l2, bigann-like
profile, made from ``--seed``). The index is built once with
``RangeSearchEngine.build`` and served through ``RangeServer`` by the
serving CLI's lockstep driver (``repro.launch.serve.serve_lockstep``),
``--queries`` requests per phase, each phase served twice (cold, then warm):

  a   f32 corpus, XLA expand path         gate: AP >= 0.6
  b   int8 corpus (production setting)    gate: |AP(b) - AP(a)| <= 0.01
  c   Pallas expand kernel, f32 and int8  gates: f32 ids == (a) per query,
                                                  |AP(c int8) - AP(b)| <= 0.01

``--four-chip`` runs only the sharded path: four shards of the same profile
on a (1, 4) ("data", "model") mesh, one shard per chip, served through
``RangeServer(mesh=..., sharded=...)``, and compared per query with the
host fan-out (``fault_tolerant_sharded_search``) over the same corpus.

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse --n 3000 --queries 64

Each phase prints one ``[chip_smoke]`` JSON line (requests/s there is a
smoke timing of one cold and one warm pass, not a benchmark). The last
line of stdout is ``{"ok": true, "device": {...}}``, printed only when JAX
found a TPU and every gate held. Without a TPU the script exits 1 before
any work, unless ``--rehearse`` runs the phases on the CPU (then phase c
is skipped, since the kernels compile for TPU only) and exits 1 after.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MIN_AP = 0.6          # BENCH_smoke.json floors.min_ap
MAX_AP_GAP = 0.01     # BENCH_smoke.json floors.max_quantized_ap_gap


def _say(**kw):
    print("[chip_smoke] " + json.dumps(kw), flush=True)


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _dataset(args, n):
    from repro.data.synthetic import make_corpus
    from repro.launch.serve import select_serving_radius
    t0 = time.perf_counter()
    ds = make_corpus(args.profile, n=n, n_queries=args.queries,
                     seed=args.seed)
    r, gi, prof = select_serving_radius(ds.points, ds.queries, ds.metric)
    counts = prof.counts[:, gi]
    matches = dict(zero_frac=float(prof.zero_frac[gi]),
                   mean=float(counts.mean()), max=int(counts.max()))
    return ds, r, matches, time.perf_counter() - t0


def _serve_twice(make_server, pts, ds, r):
    """A cold pass (it compiles), then a warm pass, each through a fresh
    server; returns both results."""
    import numpy as np

    from repro.launch.serve import serve_lockstep
    radii = np.full(len(ds.queries), r, np.float32)
    return [serve_lockstep(make_server(), pts, ds.queries, radii,
                           metric=ds.metric) for _ in range(2)]


def one_chip(args, dev) -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.configs.range_engine import EngineDeployConfig
    from repro.core import BuildConfig, RangeSearchEngine
    from repro.launch.serve import serving_range_cfg
    from repro.serve import RangeServer, ServerConfig

    deploy = EngineDeployConfig()
    n = args.n or deploy.shard_corpus
    ds, r, matches, data_s = _dataset(args, n)
    pts = jnp.asarray(ds.points)
    t0 = time.perf_counter()
    eng = RangeSearchEngine.build(
        pts, BuildConfig(max_degree=deploy.max_degree, beam=64,
                         metric=ds.metric), metric=ds.metric)
    jax.block_until_ready(eng.graph.neighbors)
    build_s = time.perf_counter() - t0
    _say(step="build", device_kind=dev.device_kind, n=n, d=ds.points.shape[1],
         R=deploy.max_degree, data_and_radius_s=data_s, radius=r,
         matches_per_query=matches,
         build_s=build_s, build_rows_per_s=n / build_s)
    eng8 = RangeSearchEngine.from_graph(pts, eng.graph, metric=ds.metric,
                                        corpus_dtype="int8")
    failures, out = [], {}
    phases = [("a", eng, "float32", False), ("b", eng8, "int8", False)]
    if dev.platform == "tpu":
        phases += [("c-f32", eng, "float32", True),
                   ("c-int8", eng8, "int8", True)]
    else:
        _say(phase="c", skipped="Pallas TPU kernels compile for TPU only")
    for name, e, dtype, kernel in phases:
        rcfg = serving_range_cfg(ds.metric, corpus_dtype=dtype,
                                 use_expand_kernel=kernel)
        cold, warm = _serve_twice(
            lambda: RangeServer(e, rcfg, ServerConfig(max_batch=128)),
            pts, ds, r)
        out[name] = warm
        lat = warm["latency_ms"]
        _say(phase=name, device_kind=dev.device_kind, n=n,
             corpus_dtype=dtype, expand_kernel=kernel,
             queries=len(ds.queries), build_s=build_s,
             first_call_s=cold["seconds"] - warm["seconds"],
             cold_pass_s=cold["seconds"], warm_pass_s=warm["seconds"],
             smoke_requests_per_s=warm["qps"], ap=warm["ap"],
             latency_p50_ms=lat[len(lat) // 2],
             peak_bytes_in_use=_peak_bytes(dev))

    ap = {k: v["ap"] for k, v in out.items()}
    if ap["a"] < MIN_AP:
        failures.append(f"a: AP {ap['a']:.4f} < {MIN_AP}")
    if abs(ap["b"] - ap["a"]) > MAX_AP_GAP:
        failures.append(f"b: |AP(b) - AP(a)| = {abs(ap['b'] - ap['a']):.4f}"
                        f" > {MAX_AP_GAP}")
    if "c-f32" in out:
        bad = [i for i, (x, y) in enumerate(zip(out["a"]["ids"],
                                                out["c-f32"]["ids"]))
               if x.shape != y.shape or (x != y).any()]
        if bad:
            failures.append(f"c-f32: ids differ from (a) on {len(bad)} "
                            f"queries, first {bad[:5]}")
        gap = abs(ap["c-int8"] - ap["b"])
        if gap > MAX_AP_GAP:
            failures.append(f"c-int8: |AP(c) - AP(b)| = {gap:.4f} > "
                            f"{MAX_AP_GAP}")
    return failures


def four_chip(args, devs) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BuildConfig
    from repro.core.build import build_vamana
    from repro.core.graph import medoid
    from repro.dist.sharded_engine import build_sharded
    from repro.fault import fault_tolerant_sharded_search
    from repro.launch.serve import serving_range_cfg
    from repro.serve import RangeServer, ServerConfig

    if len(devs) < 4:
        return [f"--four-chip needs 4 devices, JAX found {len(devs)}"]
    n = args.n or 4 * 250_000
    ds, r, matches, data_s = _dataset(args, n)
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devs[:4],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    bcfg = BuildConfig(max_degree=32, beam=64, metric=ds.metric)
    t0 = time.perf_counter()
    corpus = build_sharded(
        ds.points, 4, lambda p: (build_vamana(p, bcfg), medoid(p)[None]),
        mesh=mesh)
    jax.block_until_ready(corpus.neighbors)
    build_s = time.perf_counter() - t0
    placed = [sorted(d.id for d in corpus.points.sharding.device_set),
              [s.device.id for s in corpus.points.addressable_shards]]
    _say(step="build", device_kind=devs[0].device_kind, n=n, shards=4,
         shard_rows=corpus.shard_size, data_and_radius_s=data_s, radius=r,
         matches_per_query=matches,
         build_s=build_s, shard_devices=placed[1],
         bytes_in_use=[d.memory_stats()["bytes_in_use"]
                       if d.memory_stats() else None for d in devs[:4]])

    rcfg = serving_range_cfg(ds.metric)
    cold, warm = _serve_twice(
        lambda: RangeServer(None, rcfg, ServerConfig(max_batch=128),
                            mesh=mesh, sharded=corpus),
        jnp.asarray(ds.points), ds, r)
    radii = np.full(len(ds.queries), r, np.float32)
    ref = fault_tolerant_sharded_search(corpus=corpus,
                                        queries=jnp.asarray(ds.queries),
                                        r=jnp.asarray(radii), cfg=rcfg)
    ref_ids = np.asarray(ref.result.ids)
    ref_cnt = np.asarray(ref.result.count)
    bad = [i for i in range(len(ds.queries))
           if not np.array_equal(np.sort(ref_ids[i, :ref_cnt[i]]),
                                 warm["ids"][i])]
    _say(phase="four-chip", device_kind=devs[0].device_kind, n=n, shards=4,
         queries=len(ds.queries),
         first_call_s=cold["seconds"] - warm["seconds"],
         smoke_requests_per_s=warm["qps"], ap=warm["ap"],
         queries_equal_to_host_fan_out=len(ds.queries) - len(bad),
         peak_bytes_in_use=[_peak_bytes(d) for d in devs[:4]],
         bytes_in_use=[d.memory_stats()["bytes_in_use"]
                       if d.memory_stats() else None for d in devs[:4]])
    failures = []
    if bad:
        failures.append(f"four-chip: mesh ids differ from the host fan-out "
                        f"on {len(bad)} queries, first {bad[:5]}")
    if placed[0] != sorted(d.id for d in devs[:4]) or \
            sorted(placed[1]) != placed[0]:
        failures.append(f"four-chip: shards not one per device: {placed}")
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--four-chip", action="store_true",
                   help="run only the sharded path on a 4-chip mesh")
    p.add_argument("--n", type=int, default=0,
                   help="corpus rows (default: the deployment's 1,000,000 "
                        "for one chip, 4 x 250,000 for --four-chip)")
    p.add_argument("--queries", type=int, default=256)
    p.add_argument("--profile", default="bigann-like")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on whatever JAX finds (CPU included); never ok")
    args = p.parse_args(argv)

    import jax

    from repro.utils import enable_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing run", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    _say(step="start", platform=devs[0].platform,
         device_kind=devs[0].device_kind, device_count=len(devs),
         compile_cache=cache)
    t0 = time.perf_counter()
    failures = (four_chip(args, devs) if args.four_chip
                else one_chip(args, devs[0]))
    _say(step="end", seconds=time.perf_counter() - t0, failures=failures)
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    if devs[0].platform != "tpu":
        print("chip_smoke: rehearsal passed, but JAX found no TPU",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
