"""Compile the served path for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with jaxlib, so these tests lower and compile
the expand kernels and the search programs at the deployment's widths
(N=1M rows, d=128, R=32 and the lane-padded R=128, E=4, Q=128) without a
chip. They catch what interpret mode cannot: block shapes off the (8, 128)
tiling, DMA slices that split a tile, programs that do not fit HBM.
Nothing runs, so they say nothing about results or speed.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.beam_search import beam_search_batch
from repro.core.corpus import QuantizedCorpus
from repro.core.graph import Graph
from repro.core.range_search import (
    _retire_lanes, greedy_resume_batch, greedy_seed_batch, range_phase1,
)
from repro.kernels.expand import expand_frontier
from repro.launch.serve import serving_range_cfg

N, D, Q, E = 1_000_000, 128, 128, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def _corpus(sharding, dtype):
    if dtype == "int8":
        return QuantizedCorpus(codes=_spec(sharding, (N, D), jnp.int8),
                               meta=_spec(sharding, (N, 3), jnp.float32),
                               raw=_spec(sharding, (N, D), jnp.float32))
    return _spec(sharding, (N, D), jnp.float32)


@pytest.mark.parametrize("r", [32, 128])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_expand_kernel_lowers_to_tpu_custom_call(one_chip, dtype, r):
    fn = jax.jit(lambda pts, nbrs, fr, qs: expand_frontier(
        pts, nbrs, fr, qs, use_pallas=True))
    compiled = fn.lower(_corpus(one_chip, dtype),
                        _spec(one_chip, (N, r), jnp.int32),
                        _spec(one_chip, (Q, E), jnp.int32),
                        _spec(one_chip, (Q, D), jnp.float32)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_range_phase1_compiles(one_chip, use_kernel):
    cfg = serving_range_cfg("l2", expand_width=E, use_expand_kernel=use_kernel)
    fn = jax.jit(range_phase1, static_argnames=("cfg",))
    compiled = fn.lower(_corpus(one_chip, "float32"),
                        Graph(neighbors=_spec(one_chip, (N, 32), jnp.int32)),
                        _spec(one_chip, (Q, D), jnp.float32),
                        _spec(one_chip, (4,), jnp.int32),
                        _spec(one_chip, (Q,), jnp.float32), cfg=cfg).compile()
    assert _has_kernel(compiled) == use_kernel


def _seed_state(one_chip, cfg, corpus, graph, qs, radii):
    """The greedy seed state's shapes, from phase 1 traced abstractly."""
    st = jax.eval_shape(
        lambda c, g, q, s, r: beam_search_batch(c, g, q, s, r, cfg.search),
        corpus, graph, qs, _spec(one_chip, (4,), jnp.int32), radii)
    gs = jax.eval_shape(
        lambda c, st_, r: greedy_seed_batch(c, st_, r, cap=cfg.result_cap,
                                            scfg=cfg.search),
        corpus, st, radii)
    return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), gs)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_greedy_resume_compiles(one_chip, use_kernel):
    cfg = serving_range_cfg("l2", expand_width=E, corpus_dtype="int8",
                            use_expand_kernel=use_kernel)
    corpus = _corpus(one_chip, "int8")
    graph = Graph(neighbors=_spec(one_chip, (N, 32), jnp.int32))
    qs = _spec(one_chip, (Q, D), jnp.float32)
    radii = _spec(one_chip, (Q,), jnp.float32)
    gs = _seed_state(one_chip, cfg, corpus, graph, qs, radii)
    compiled = greedy_resume_batch.lower(
        corpus, graph, qs, radii, gs, _spec(one_chip, (Q,), jnp.bool_),
        cap=cfg.result_cap, rounds=cfg.frontier_rounds, slice_rounds=8,
        scfg=cfg.search).compile()
    assert _has_kernel(compiled) == use_kernel


def test_retire_lanes_compiles(one_chip):
    """The phase-2 slice boundary: a full 128-lane bucket written into the
    batch-sized output, its live lanes gathered into a 32-lane bucket."""
    cfg = serving_range_cfg("l2", expand_width=E, corpus_dtype="int8")
    corpus = _corpus(one_chip, "int8")
    graph = Graph(neighbors=_spec(one_chip, (N, 32), jnp.int32))
    qs = _spec(one_chip, (Q, D), jnp.float32)
    radii = _spec(one_chip, (Q,), jnp.float32)
    gs = _seed_state(one_chip, cfg, corpus, graph, qs, radii)
    out = (gs.res_ids, gs.res_dists, gs.res_count, gs.overflow, gs.n_dist,
           gs.rounds)
    rows = _spec(one_chip, (Q,), jnp.int32)
    _retire_lanes.lower(out, gs, rows, _spec(one_chip, (Q // 4,), jnp.int32),
                        (qs, radii)).compile()
