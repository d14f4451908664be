"""One run of one cell: set-up, the measured window, the comparison with the
reference, and the metrics.

Everything particular to a configuration, a traffic mix or a metric is read
from its own file by the name that ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py`` (or ``<metric before its first dot>.py``).
From the program the harness takes only ``RangeSearchEngine``,
``RangeServer`` and the deploy config's public ``overrides``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from . import corpus, reference, trace, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(HERE, "out", "trace")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def reader(metric: str):
    """The ``read(ctx)`` function of a metric, found by its name."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {metric!r}")


def cell_metrics(manifest: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in manifest[kind]
            if cell in m.get("workloads", [cell])]


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1


def range_cfg(cfg: dict, **extra):
    """The configuration's whole ``search`` dict, its metric and storage,
    with ``extra`` on top; ``overrides`` refuses an unknown key."""
    from repro.configs.range_engine import EngineDeployConfig
    return EngineDeployConfig().overrides(**{
        **cfg["search"], "metric": cfg["profile"]["metric"],
        "corpus_dtype": cfg["corpus_dtype"], **extra}).range_cfg


def warm_up(server, queries, radius, max_batch) -> int:
    """Serve every shape the window will use, and no other; returns the
    number of batches served. The closed loop's batches are the pool's
    consecutive blocks of ``max_batch``, so one pass over the pool serves
    each of them once."""
    from repro.serve import Request
    steps = 0

    def serve(idx):
        nonlocal steps
        for k, qi in enumerate(idx):
            server.submit(Request(req_id=-1 - k, query=queries[qi],
                                  radius=radius))
        server.step()
        steps += 1

    for s in range(0, len(queries), max_batch):
        serve(range(s, min(s + max_batch, len(queries))))
    return steps


@contextlib.contextmanager
def profiled(on: bool):
    if not on:
        yield None
        return
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        yield TRACE_DIR
    finally:
        jax.profiler.stop_trace()


def span_fn(on: bool):
    if not on:
        return traffic._null_span
    import jax
    return jax.profiler.TraceAnnotation


def window(server, queries, radius, mix, seconds, traced):
    from repro.serve import Request
    span = span_fn(traced)
    with span("bench.window"):
        return traffic.run_closed(server, Request, queries, radius, mix,
                                  seconds, span=span)


def check(cfg, points, queries, radius, log: traffic.Log,
          t_close: float) -> dict:
    """The comparison that decides ``correct``, over every response; and
    ``ap_window``, the AP of the responses that came in the window."""
    rids = list(log.ids)
    answered = [(log.pool_idx[rid], log.ids[rid]) for rid in rids]
    t = time.perf_counter()
    cmp = reference.compare(points, queries, radius, answered,
                            cfg["profile"]["metric"])
    cmp["reference_s"] = time.perf_counter() - t
    inw = [k for k, rid in enumerate(rids) if log.done[rid] <= t_close]
    size = sum(cmp["sizes"][k] for k in inw)
    cmp["ap_window"] = (sum(cmp["hits"][k] for k in inw) / size
                        if size else 1.0)
    cmp["unanswered"] = len(log.pool_idx) - len(log.done)
    cmp["recall_loss"] = 1.0 - cmp["ap"]
    lim = cfg["limits"]
    cmp["checks"] = {k: {"value": cmp[k], "limit": lim[k]} for k in lim}
    cmp["correct"] = all(c["value"] <= c["limit"]
                         for c in cmp["checks"].values())
    return cmp


def build(cfg: dict, points: np.ndarray):
    """The index over ``points``; returns ``(device points, graph, build
    seconds)``."""
    import jax
    import jax.numpy as jnp
    from repro.core import BuildConfig, RangeSearchEngine
    t = time.perf_counter()
    pts = jnp.asarray(points)
    metric = cfg["profile"]["metric"]
    eng = RangeSearchEngine.build(
        pts, BuildConfig(max_degree=cfg["max_degree"], beam=cfg["build_beam"],
                         metric=metric), metric=metric)
    jax.block_until_ready(eng.graph.neighbors)
    return pts, eng.graph, time.perf_counter() - t


def serving(cfg, pts, graph, control: bool):
    """Engine, range config and server over ``graph``: as the configuration
    states, or with its ``control`` (the lower-precision path) in place."""
    from repro.core import RangeSearchEngine
    from repro.serve import RangeServer, ServerConfig
    extra = dict(cfg["control"]) if control else {}
    dtype = extra.pop("corpus_dtype", cfg["corpus_dtype"])
    metric = cfg["profile"]["metric"]
    eng = RangeSearchEngine.from_graph(
        pts, graph, metric=metric,
        corpus_dtype=None if dtype == "float32" else dtype)
    rcfg = range_cfg(dict(cfg, corpus_dtype=dtype), **extra)
    return eng, rcfg, RangeServer(eng, rcfg, ServerConfig(**cfg["server"]))


def run_cell(cell_name: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, n: int = 0, pool: int = 0, radius: float = 0.0,
             control: bool = False) -> dict:
    """One run; returns the result line's fields (and more). ``n``,
    ``pool`` and ``radius`` shrink the deployment for a rehearsal on the
    CPU. With ``control`` the server runs the configuration's ``control``,
    the program's lower-precision path, which the comparison has to
    refuse; the measurement runs never use it."""
    import jax
    from repro.utils import enable_compile_cache

    manifest = load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == cell_name)
    cfg = load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    dev = jax.devices()[0]

    t = time.perf_counter()
    points, queries = corpus.deployment(cfg, seed, n, pool)
    steps_s = {"data": time.perf_counter() - t}
    pts, graph, build_s = build(cfg, points)
    steps_s["build"] = build_s
    radius = radius or cfg["radius"]
    t = time.perf_counter()
    eng, rcfg, server = serving(cfg, pts, graph, control)
    max_batch = cfg["server"]["max_batch"]
    if mix["loop"] != "closed" or len(queries) % max_batch:
        raise ValueError("the closed loop needs a pool of whole batches")
    n_warm = warm_up(server, queries, radius, max_batch)
    steps_s["warm_up"] = time.perf_counter() - t
    say(f"set-up {json.dumps(steps_s)} warm-up batches {n_warm} "
        f"{'control ' + json.dumps(cfg['control']) + ' ' if control else ''}"
        f"radius {radius} rows {points.shape[0]} pool {queries.shape[0]}")

    gc.collect()
    counter.on = True
    with profiled(traced) as tdir:
        log, t0, t_close = window(server, queries, radius, mix, seconds,
                                  traced)
    counter.on = False
    setup_s = t0 - t_start
    traffic.drain(server, log)
    stats = dict(server.stats)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    del server, eng
    gc.collect()

    red = {}
    if traced:
        red = trace.reduce(trace.load(tdir), trace.layers())
    cmp = check(cfg, points, queries, radius, log, t_close)
    ctx = dict(
        setup_s=setup_s, build_s=build_s, rows=points.shape[0],
        window_s=t_close - t0,
        answered_in_window=sum(1 for v in log.done.values() if v <= t_close),
        ap=cmp["ap_window"], trace=red)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, cell_name, kind):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(cmp["correct"]),
           "attempted": len(log.due),
           "failed": cmp["unanswered"] + log.errors,
           "metrics": metrics, "device": device}
    if traced and red:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["window_compiles"] = counter.count
    out["checks"] = cmp["checks"]
    say(f"window {ctx['window_s']:.3f}s answered {ctx['answered_in_window']}"
        f" attempted {len(log.due)} compiles in "
        f"window {counter.count} ap {cmp['ap_window']:.6f} (all responses "
        f"{cmp['ap']:.6f}) false_positives "
        f"{cmp['false_positives']} matches {cmp['matches']} reference "
        f"{cmp['reference_s']:.2f}s server {json.dumps(stats)}")
    if traced:
        say(f"trace {json.dumps({k: red.get(k) for k in ('layer_s', 'span_idle_s', 'span_count')})}")
    return out
