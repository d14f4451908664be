"""The harness hands a configuration's ``search`` and ``server`` settings to
the program whole: for ``bigann-int8`` it builds what it always built, and an
inner-product configuration with early stopping on runs through a whole
rehearsal with no further edit."""
import copy
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import corpus, harness, radius

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
N, POOL = 1500, 256


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def _fault_run():
    spec = importlib.util.spec_from_file_location(
        "fault_run", os.path.join(HERE, "fault_run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_int8_settings_are_what_the_seven_keys_built():
    from repro.configs.range_engine import EngineDeployConfig
    from repro.core import build_knn_graph
    from repro.serve import ServerConfig
    cfg = _cfg("bigann-int8")

    def seven_keys(**extra):
        return EngineDeployConfig().overrides(
            metric="l2", corpus_dtype="int8", beam=32, max_beam=32,
            visit_cap=512, result_cap=4096, mode="greedy",
            frontier_rounds=2048, lam=1.0, **extra).range_cfg

    assert harness.range_cfg(cfg) == seven_keys()
    pts = jnp.asarray(np.random.default_rng(0).standard_normal(
        (256, 8)).astype(np.float32))
    graph = build_knn_graph(pts, k=8)
    for control, want in ((False, seven_keys()),
                          (True, seven_keys(rerank=False))):
        _, rcfg, server = harness.serving(cfg, pts, graph, control)
        assert rcfg == want
        assert server.scfg == ServerConfig(max_batch=128, max_queue=8192)


def test_unknown_search_key_is_refused():
    cfg = copy.deepcopy(_cfg("bigann-int8"))
    cfg["search"]["visit_kap"] = 512
    with pytest.raises(TypeError, match="visit_kap"):
        harness.range_cfg(cfg)


@pytest.fixture
def ip_early_stop(monkeypatch):
    """A manifest with one cell over an inner-product configuration with
    early stopping on, served by the loaders in place of the files; the
    radius is the rule's on the rehearsal's own draw."""
    from repro.core import ES_D_VISITED
    cfg = copy.deepcopy(_cfg("bigann-int8"))
    cfg.update(name="tiny-ip", corpus_dtype="float32",
               control={"corpus_dtype": "bfloat16"})
    cfg["profile"]["metric"] = "ip"
    cfg["search"].update(es_metric=ES_D_VISITED, es_visit_limit=20)
    cfg["server"]["es_radius_factor"] = 1.0
    manifest = {"workloads": [{"name": "tiny-ip.sat", "config": "tiny-ip",
                               "traffic": "sat", "chips": 1}],
                "end_to_end": [], "per_layer": []}
    monkeypatch.setattr(harness, "load_manifest", lambda: manifest)
    monkeypatch.setattr(harness, "load_config",
                        lambda name: {"tiny-ip": cfg}[name])
    # keep the process's compile cache settings as the other tests have them
    import repro.utils
    monkeypatch.setattr(repro.utils, "enable_compile_cache", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    points, queries = corpus.deployment(cfg, 0, N, POOL)
    r = radius.select_radius(points, queries, "ip")["radius"]
    seen = []
    serving = harness.serving
    monkeypatch.setattr(harness, "serving",
                        lambda *a: seen.append(serving(*a)) or seen[-1])
    yield cfg, r, seen
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def test_ip_early_stop_config_runs_through_the_harness(ip_early_stop,
                                                       monkeypatch):
    from repro.core import ES_D_VISITED
    from repro.serve import RangeServer
    cfg, r, seen = ip_early_stop
    assert r < 0

    def run(seed):
        return harness.run_cell("tiny-ip.sat", seed, 1.0, False,
                                t_start=time.perf_counter(), n=N, pool=POOL,
                                radius=r)

    sound = run(5)
    _, rcfg, server = seen[-1]
    assert rcfg.search.metric == "ip" and server.engine.metric == "ip"
    assert rcfg.search.es_metric == ES_D_VISITED
    assert rcfg.search.es_visit_limit == 20
    assert server.scfg.es_radius_factor == 1.0
    assert server.stats["es_stopped"] > 0
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["max_excess"]["value"] == 0.0

    step = RangeServer.step
    alter = _fault_run().FAULTS["alter"]
    monkeypatch.setattr(RangeServer, "step", lambda self: alter(step(self)))
    broken = run(6)
    assert not broken["correct"]
    c = broken["checks"]
    assert (c["max_excess"]["value"] > c["max_excess"]["limit"]
            or c["bad_ids"]["value"] > 0)
