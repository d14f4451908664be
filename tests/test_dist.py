"""Multi-device distribution tests.

These need >1 device, which requires XLA_FLAGS before jax's first import —
forbidden in conftest (smoke tests must see 1 device, per brief). Each test
therefore runs a short script in a subprocess with the flag set.
"""
import os
import subprocess
import sys
import textwrap


ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_sub(body: str):
    script = textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_compressed_psum_and_collective_matmul():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.dist.compression import compressed_psum_mean
        from repro.dist.collective_matmul import allgather_matmul, matmul_reducescatter
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000), jnp.float32)
        f = shard_map(partial(compressed_psum_mean, axis_name="model", n=4),
                      mesh=mesh, in_specs=P(None, "model"),
                      out_specs=P(None, "model"), check_vma=False)
        got = np.asarray(f(x)).reshape(8, 4, 250)
        want = np.asarray(x).reshape(8, 4, 250).mean(axis=1)
        for s in range(4):
            np.testing.assert_allclose(got[:, s], want, rtol=0.05, atol=0.02)
        xx = jax.random.normal(jax.random.PRNGKey(1), (16, 12), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(2), (12, 6), jnp.float32)
        f2 = shard_map(partial(allgather_matmul, axis_name="model", n=4),
                       mesh=mesh, in_specs=(P("model", None), P(None, None)),
                       out_specs=P(None, None), check_vma=False)
        np.testing.assert_allclose(np.asarray(f2(xx, w)), np.asarray(xx @ w),
                                   rtol=1e-5, atol=1e-5)
        x3 = jax.random.normal(jax.random.PRNGKey(3), (16, 20), jnp.float32)
        w3 = jax.random.normal(jax.random.PRNGKey(4), (20, 6), jnp.float32)
        f3 = shard_map(partial(matmul_reducescatter, axis_name="model", n=4),
                       mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
                       out_specs=P("model", None), check_vma=False)
        np.testing.assert_allclose(np.asarray(f3(x3, w3)), np.asarray(x3 @ w3),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)


def test_sharded_embedding_and_engine():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.dist.embedding import sharded_lookup
        from repro.dist.sharded_engine import build_sharded, sharded_range_search
        from repro.core import (RangeConfig, SearchConfig, build_knn_graph,
                                exact_range_search, average_precision)
        from repro.core.graph import medoid
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        tables = jax.random.normal(jax.random.PRNGKey(5), (3, 64, 8), jnp.float32)
        idx = jax.random.randint(jax.random.PRNGKey(6), (10, 3), 0, 64)
        got = sharded_lookup(mesh, tables, idx, axis=("data", "model"))
        want = jax.vmap(lambda t, i: jnp.take(t, i, axis=0), in_axes=(0, 1),
                        out_axes=1)(tables, idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

        pts = jnp.asarray(np.random.default_rng(0).standard_normal((2000, 16)),
                          jnp.float32)
        qs = np.asarray(pts[:32]) + 0.01
        rcfg = RangeConfig(search=SearchConfig(beam=32, max_beam=32,
                                               visit_cap=128,
                                               expand_width=4),
                           mode="greedy", result_cap=256)
        corpus = build_sharded(np.asarray(pts), 4,
                               lambda p: (build_knn_graph(p, k=12), medoid(p)[None]))
        res = sharded_range_search(mesh=mesh, corpus=corpus, queries=jnp.asarray(qs), r=4.0, cfg=rcfg)
        gt = exact_range_search(pts, jnp.asarray(qs), 4.0)
        ap = average_precision(np.asarray(gt[0]), np.asarray(gt[2]),
                               np.asarray(res.ids), np.asarray(res.count))
        assert ap > 0.8, ap
        print("OK")
    """)


def test_sharded_trainer_elastic_restore():
    run_sub("""
        import functools, shutil
        import numpy as np, jax, jax.numpy as jnp
        from repro.models import TransformerConfig, init_transformer, loss_fn
        from repro.optim import AdamWConfig
        from repro.train import Trainer, TrainerConfig
        from repro.data.lm import LMDataConfig, lm_batches
        from repro.dist.sharding import LM_RULES
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                                n_kv=4, d_head=16, d_ff=64, vocab=64,
                                dtype=jnp.float32, loss_chunk=16, remat=False)
        dcfg = LMDataConfig(vocab=64, seq_len=16, batch=4)
        loss = functools.partial(loss_fn, cfg=cfg)
        shutil.rmtree("/tmp/elastic_t", ignore_errors=True)
        # phase 1: unsharded (single-device) training -> checkpoint
        tr1 = Trainer(loss, init_transformer(jax.random.PRNGKey(0), cfg),
                      AdamWConfig(lr=1e-2, warmup_steps=2),
                      TrainerConfig(total_steps=10, ckpt_every=5, log_every=5,
                                    ckpt_dir="/tmp/elastic_t"))
        tr1.fit(lm_batches(dcfg))
        # phase 2: restore onto an 8-device mesh (elastic reshard)
        tr2 = Trainer(loss, init_transformer(jax.random.PRNGKey(1), cfg),
                      AdamWConfig(lr=1e-2, warmup_steps=2),
                      TrainerConfig(total_steps=14, ckpt_every=50, log_every=2,
                                    ckpt_dir="/tmp/elastic_t"),
                      mesh=mesh, param_rules=LM_RULES)
        assert tr2.maybe_restore() and tr2.step == 10
        out = tr2.fit(lm_batches(dcfg, start_step=10))
        assert out["final_step"] == 14
        assert np.isfinite(out["history"][-1]["loss"])
        print("OK")
    """)


def test_sharded_matches_host_union_exactly():
    """Parity beyond AP: sharded_range_search must equal running the same
    per-shard searches on the host and union-merging — same ids, counts."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RangeConfig, SearchConfig, build_knn_graph
        from repro.core.graph import Graph, medoid
        from repro.core.range_search import range_search_fused
        from repro.dist.sharded_engine import build_sharded, sharded_range_search
        from repro.utils import INVALID_ID
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        pts = jnp.asarray(np.random.default_rng(1).standard_normal((1600, 8)),
                          jnp.float32)
        qs = jnp.asarray(np.asarray(pts[:16]) + 0.02)
        rcfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                               visit_cap=64,
                                               expand_width=2),
                           mode="greedy", result_cap=128)
        corpus = build_sharded(np.asarray(pts), 4,
                               lambda p: (build_knn_graph(p, k=8), medoid(p)[None]))
        res = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=2.5, cfg=rcfg)

        # host reference: same per-shard fused searches, numpy union-merge
        all_ids, all_dists, total = [], [], 0
        for s in range(4):
            r = range_search_fused(corpus=corpus.points[s],
                                   graph=Graph(neighbors=corpus.neighbors[s]),
                                   queries=qs, start_ids=corpus.start_ids[s],
                                   r=2.5, cfg=rcfg)
            gids = np.where(np.asarray(r.ids) == INVALID_ID, INVALID_ID,
                            np.asarray(r.ids) + int(corpus.offsets[s]))
            all_ids.append(gids); all_dists.append(np.asarray(r.dists))
            total = total + np.asarray(r.count)
        ids = np.concatenate(all_ids, axis=1)
        dists = np.concatenate(all_dists, axis=1)
        order = np.argsort(dists, axis=1, kind="stable")
        ids = np.take_along_axis(ids, order, axis=1)[:, :rcfg.result_cap]
        want_count = np.minimum(total, rcfg.result_cap)

        np.testing.assert_array_equal(np.asarray(res.count), want_count)
        got_ids = np.asarray(res.ids)
        for q in range(ids.shape[0]):
            k = want_count[q]
            assert set(got_ids[q, :k]) == set(ids[q, :k]), q
            assert (got_ids[q, k:] == INVALID_ID).all()
        assert int(want_count.sum()) > 0  # the check is not vacuous
        print("OK")
    """)


def test_build_sharded_places_one_shard_per_device():
    """build_sharded(mesh=) leaves every device holding only its own shard
    (replicated along the data axis), and both fan-outs over that placed
    corpus agree per query: the shard_map program behind
    RangeServer(mesh=, sharded=) and the host fan-out, whose per-shard
    searches run on the shard's own device."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RangeConfig, SearchConfig, build_knn_graph
        from repro.core.graph import medoid
        from repro.dist.sharded_engine import build_sharded, shard_view
        from repro.fault import fault_tolerant_sharded_search
        from repro.serve import RangeServer, Request, ServerConfig
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pts = np.random.default_rng(3).standard_normal((1600, 8)).astype(np.float32)
        qs = pts[:16] + 0.02
        rcfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                               visit_cap=64, expand_width=2),
                           mode="greedy", result_cap=128)
        corpus = build_sharded(pts, 4, lambda p: (build_knn_graph(p, k=8),
                                                  medoid(p)[None]), mesh=mesh)
        for leaf in (corpus.points, corpus.neighbors, corpus.start_ids):
            for piece in leaf.addressable_shards:
                m = int(np.argwhere(mesh.devices == piece.device)[0, 1])
                assert piece.data.shape[0] == 1
                assert range(4)[piece.index[0]] == range(m, m + 1), (m, piece.index)
        for s in range(4):  # the host fan-out reads each shard in place
            assert shard_view(corpus.points, s).devices() == \
                {mesh.devices[0, s]}
        srv = RangeServer(None, rcfg, ServerConfig(max_batch=16), mesh=mesh,
                          sharded=corpus)
        for i in range(16):
            srv.submit(Request(req_id=i, query=qs[i], radius=2.5))
        got = {rp.req_id: np.sort(rp.ids) for rp in srv.run_until_drained()}
        ref = fault_tolerant_sharded_search(corpus=corpus, queries=qs, r=2.5,
                                            cfg=rcfg).result
        total = 0
        for i in range(16):
            want = np.sort(np.asarray(ref.ids[i][:ref.count[i]]))
            np.testing.assert_array_equal(got[i], want)
            total += len(want)
        assert total > 0  # the check is not vacuous
        print("OK")
    """)


def test_sharded_mixed_radius_per_lane():
    """Per-query radii through the shard_map program: a mixed-radius batch
    must answer each lane exactly as a homogeneous batch at that lane's
    radius does, and an all-equal radius vector must be bitwise-identical
    to the scalar call (the radius vector shards along data with its
    queries and broadcasts to every model-axis shard)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RangeConfig, SearchConfig, build_knn_graph
        from repro.core.graph import medoid
        from repro.dist.sharded_engine import build_sharded, sharded_range_search
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        pts = jnp.asarray(np.random.default_rng(2).standard_normal((1600, 8)),
                          jnp.float32)
        qs = jnp.asarray(np.asarray(pts[:16]) + 0.02)
        rcfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                               visit_cap=64, expand_width=2),
                           mode="greedy", result_cap=128)
        corpus = build_sharded(np.asarray(pts), 4,
                               lambda p: (build_knn_graph(p, k=8), medoid(p)[None]))
        r_a, r_b = 1.5, 3.5
        radii = jnp.asarray(np.where(np.arange(16) % 2, r_b, r_a), jnp.float32)
        mixed = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=radii, cfg=rcfg)
        hom_a = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=r_a, cfg=rcfg)
        hom_b = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=r_b, cfg=rcfg)
        for name in ("ids", "dists", "count", "overflow"):
            got = np.asarray(getattr(mixed, name))
            wa = np.asarray(getattr(hom_a, name))
            wb = np.asarray(getattr(hom_b, name))
            for q in range(16):
                want = wb[q] if q % 2 else wa[q]
                np.testing.assert_array_equal(got[q], want, err_msg=f"{name}[{q}]")
        assert int(np.asarray(mixed.count).sum()) > 0  # not vacuous
        # all-equal vector == scalar, bitwise, across every result field
        vec = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=jnp.full((16,), r_a), cfg=rcfg)
        for name in ("ids", "dists", "count", "overflow", "n_visited",
                     "n_dist", "es_stopped", "phase2"):
            np.testing.assert_array_equal(np.asarray(getattr(vec, name)),
                                          np.asarray(getattr(hom_a, name)),
                                          err_msg=name)
        print("OK")
    """)


def test_sharded_quantized_two_pass():
    """Locally-quantized int8 shards through the shard_map program: the
    union result must contain only exactly-in-range ids (post-rerank, per
    the brute-force oracle) and must equal running the same per-shard
    quantized two-pass searches on the host (tree-sliced shards) with a
    numpy union-merge — including the summed rerank-band counters."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RangeConfig, SearchConfig, build_knn_graph
        from repro.core.graph import Graph, medoid
        from repro.core.range_search import range_search_fused
        from repro.dist.sharded_engine import build_sharded, sharded_range_search
        from repro.utils import INVALID_ID
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        pts = jnp.asarray(np.random.default_rng(3).standard_normal((1600, 8)),
                          jnp.float32)
        qs = jnp.asarray(np.asarray(pts[:16]) + 0.02)
        rcfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                               visit_cap=64, expand_width=2),
                           mode="greedy", result_cap=128)
        corpus = build_sharded(np.asarray(pts), 4,
                               lambda p: (build_knn_graph(p, k=8), medoid(p)[None]),
                               corpus_dtype="int8")
        r = 2.5
        res = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs, r=r, cfg=rcfg)
        ids = np.asarray(res.ids); cnt = np.asarray(res.count)
        d2 = np.sum((np.asarray(pts)[None, :, :]
                     - np.asarray(qs)[:, None, :]) ** 2, axis=-1)
        for q in range(16):  # zero false positives after the in-shard rerank
            got = ids[q][ids[q] != INVALID_ID]
            assert np.all(d2[q, got] <= r + 1e-5), q
        assert int(cnt.sum()) > 0
        assert int(np.asarray(res.n_rerank).sum()) >= 0

        # host reference: per-shard fused searches on tree-sliced shards
        all_ids, all_dists, total, nrr = [], [], 0, 0
        for s in range(4):
            shard = jax.tree.map(lambda x: x[s], corpus.points)
            rr = range_search_fused(corpus=shard,
                                    graph=Graph(neighbors=corpus.neighbors[s]),
                                    queries=qs, start_ids=corpus.start_ids[s],
                                    r=r, cfg=rcfg)
            gids = np.where(np.asarray(rr.ids) == INVALID_ID, INVALID_ID,
                            np.asarray(rr.ids) + int(corpus.offsets[s]))
            all_ids.append(gids); all_dists.append(np.asarray(rr.dists))
            total = total + np.asarray(rr.count)
            nrr = nrr + np.asarray(rr.n_rerank)
        hids = np.concatenate(all_ids, axis=1)
        hdists = np.concatenate(all_dists, axis=1)
        order = np.argsort(hdists, axis=1, kind="stable")
        hids = np.take_along_axis(hids, order, axis=1)[:, :rcfg.result_cap]
        want_count = np.minimum(total, rcfg.result_cap)
        np.testing.assert_array_equal(cnt, want_count)
        np.testing.assert_array_equal(np.asarray(res.n_rerank), nrr)
        for q in range(16):
            k = want_count[q]
            assert set(ids[q, :k]) == set(hids[q, :k]), q
            assert (ids[q, k:] == INVALID_ID).all()
        print("OK")
    """)


def test_spec_tree_divisibility_fallback():
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.dist.sharding import LM_RULES, spec_tree, DP, TP
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        params = {"layers": {"attn": {"wk": jnp.zeros((6, 32, 3, 16))}},
                  "b3": jnp.zeros((1,))}
        specs = spec_tree(params, LM_RULES, mesh)
        # 3 kv heads don't divide model=4 -> TP dropped (KV replication)
        assert specs["layers"]["attn"]["wk"][2] is None, specs
        assert specs["layers"]["attn"]["wk"][1] == DP
        print("OK")
    """)


def test_sharded_filtered_matches_postfiltered_oracle():
    """Filtered sharded range search: every shard evaluates the per-query
    predicate locally before its rows join the union merge, so the merged
    result equals the post-filtered brute-force oracle. Also: the all-pass
    filter is bitwise-identical to running without one."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import RangeConfig, SearchConfig, build_knn_graph
        from repro.core import all_pass_filter, make_label_filter, pack_labels
        from repro.core.graph import medoid
        from repro.dist.sharded_engine import build_sharded, sharded_range_search
        from repro.utils import INVALID_ID
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        NL = 8
        rng = np.random.default_rng(5)
        pts = jnp.asarray(rng.standard_normal((1600, 8)), jnp.float32)
        raw = [sorted(int(x) for x in
                      rng.choice(NL, size=int(rng.integers(1, 3)),
                                 replace=False))
               for _ in range(1600)]
        qs = jnp.asarray(np.asarray(pts[:16]) + 0.02)
        rcfg = RangeConfig(search=SearchConfig(beam=16, max_beam=16,
                                               visit_cap=64, expand_width=2),
                           mode="greedy", result_cap=128)
        corpus = build_sharded(
            np.asarray(pts), 4,
            lambda p: (build_knn_graph(p, k=8), medoid(p)[None]),
            labels=pack_labels(raw, NL))
        entries = [[q % NL] if q % 2 == 0 else [q % NL, (q + 3) % NL]
                   for q in range(16)]
        modes = ["and" if q % 2 == 0 else "or" for q in range(16)]
        filt = make_label_filter(entries, NL, modes=modes)
        plain = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs,
                                     r=2.5, cfg=rcfg)
        res = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs,
                                   r=2.5, cfg=rcfg, label_filter=filt)

        # oracle: post-filter the unfiltered sharded result. The filtered
        # traversal is identical to the unfiltered one on the collective
        # path (no entry reseeding under shard_map), so set equality holds.
        ids_p = np.asarray(plain.ids)
        ids_f = np.asarray(res.ids)
        sets = [set(r) for r in raw]
        nonempty = 0
        for q in range(16):
            pred = set(entries[q])
            keep = (lambda i: pred <= sets[i]) if modes[q] == "and" \\
                else (lambda i: bool(pred & sets[i]))
            want = {int(i) for i in ids_p[q][ids_p[q] != INVALID_ID]
                    if keep(int(i))}
            got = {int(i) for i in ids_f[q][ids_f[q] != INVALID_ID]}
            assert got == want, (q, sorted(got ^ want)[:5])
            assert int(np.asarray(res.count)[q]) == len(want)
            nonempty += bool(want)
        assert nonempty >= 8  # the check is not vacuous

        # all-pass filter: bitwise identity with the unfiltered run
        ap = sharded_range_search(mesh=mesh, corpus=corpus, queries=qs,
                                  r=2.5, cfg=rcfg,
                                  label_filter=all_pass_filter(16, NL))
        for f in ("ids", "dists", "count", "overflow", "n_visited", "n_dist",
                  "es_stopped", "phase2", "n_rerank"):
            np.testing.assert_array_equal(np.asarray(getattr(ap, f)),
                                          np.asarray(getattr(plain, f)), f)
        print("OK")
    """)
