"""Multi-shard range retrieval: the production layout of the paper's engine.

A corpus bigger than one device's HBM splits into contiguous shards, each
with its *own* sub-index (graph + entry points) — the standard multi-shard
decomposition of graph-ANN systems. Range search then fans out as one
``shard_map`` program:

* shards lay along the **model** axis (one or more sub-indices per device),
  query batches along the **data** axis;
* each device runs the fused single-program search
  (``core.range_search_fused``) of its query block against its local
  shard(s) and remaps shard-local ids to global ids via the shard offset;
* an all-gather along the model axis followed by a distance-sort
  **union-merge** produces the global ``RangeResult``: ids/dists are the
  ``result_cap`` closest in-range points across all shards, counts sum, and
  overflow flags OR (plus a union-level overflow when the merged count
  exceeds the cap).

Because the shards partition the corpus, per-shard result sets are disjoint
and the union needs no dedup — only the merge sort.
"""
from __future__ import annotations

import contextlib
import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.beam_search import broadcast_radius
from ..core.corpus import corpus_cast, pad_corpus_rows
from ..core.graph import Graph
from ..core.labels import LabelFilter
from ..core.range_search import RangeConfig, RangeResult, range_search_fused
from ..utils import INVALID_ID, cdiv
from jax import shard_map
from .sharding import _axis_size


def _points_leaf(points):
    """Representative array leaf of a corpus (works for stacked
    QuantizedCorpus pytrees and plain arrays alike)."""
    return jax.tree.leaves(points)[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedCorpus:
    """Stacked per-shard sub-indices (leading axis = shard).

    ``points`` is either a stacked (S, n, d) array or a stacked
    ``QuantizedCorpus`` whose every leaf carries the shard axis in front
    (codes (S, n, d), meta (S, n, 3), raw (S, n, d)) — each shard
    quantizes *locally*, so its guard band is as tight as its own
    per-vector errors allow."""

    points: Any     # (S, n, d) — shard blocks (pad rows edge-free/unreachable)
    neighbors: Any  # (S, n, R) int32 — per-shard graph adjacency
    start_ids: Any  # (S, k) int32 — per-shard entry points (shard-local ids)
    offsets: Any    # (S,) int32 — global id of each shard's row 0
    # true corpus size: required so pad-row ids (>= n_total) are droppable
    n_total: int = dataclasses.field(metadata=dict(static=True))
    # (S, n, W) uint32 — per-shard packed label rows (core.labels), or None
    # for an unlabeled corpus. Pad rows of a short last shard carry all-zero
    # label rows: they are unreachable anyway, and a zero row matches no
    # non-trivial AND/OR predicate.
    labels: Any = None
    # Tuple of per-shard ``repro.tier.TieredCorpus`` views (device=None —
    # the stacked ``points`` above IS the device arm; each tier contributes
    # its host row store + cache), or None for a fully-resident corpus.
    # Static: a TieredCorpus is identity-hashed and never enters jit; only
    # the host fan-out path (fault.fault_tolerant_sharded_search) composes
    # ``tiers[s].with_device(points[s])`` per shard.
    tiers: Any = dataclasses.field(default=None, metadata=dict(static=True))

    @property
    def n_shards(self) -> int:
        return _points_leaf(self.points).shape[0]

    @property
    def shard_size(self) -> int:
        return _points_leaf(self.points).shape[1]


# Sentinel coordinates for rows padding a short last shard. The value never
# decides correctness: pad rows are appended AFTER the sub-index is built on
# the real rows, so no graph edge and no entry point reaches them under any
# metric — they are unreachable, not merely distant. (Kept large so even a
# hypothetical brute-force pass over shard rows ranks them last under l2.)
_FAR = 1e30


def build_sharded(
    points,
    n_shards: int,
    build_fn: Callable,   # (shard_points (n, d)) -> (Graph, start_ids (k,))
    lane_pad: int = 0,
    corpus_dtype: str = "float32",
    labels=None,
    tier: bool = False,
    resident_mb: float = None,
    mesh: Optional[Mesh] = None,
) -> ShardedCorpus:
    """Partition ``points`` into ``n_shards`` contiguous blocks and build one
    sub-index per block with ``build_fn``. A short last block is padded to
    the common shard size only *after* its graph is built, so the pad rows
    have no incoming edges (search can never visit them, under any metric)
    and the stacked arrays stay rectangular.

    ``lane_pad > 0`` pads every sub-index's degree axis to that multiple
    (``Graph.lane_padded``), done once here rather than per search
    dispatch.

    ``corpus_dtype`` controls per-shard storage: graphs always build on the
    exact f32 block; "int8" then quantizes each shard *locally* (per-shard
    scales and guard-band maxima, computed before any pad rows are appended
    so sentinel values cannot widen the band).

    ``labels`` (optional) is the corpus-wide (N, W) uint32 packed label
    matrix (``core.labels.pack_labels``); it splits into the same contiguous
    blocks as the points, zero-padded to the common shard size (zero rows
    match no non-trivial predicate and are unreachable regardless).

    ``tier=True`` builds each shard as a tiered corpus: the stacked
    ``points`` keep only the device arm (int8 codes + meta for "int8";
    the cast block for float dtypes), while each shard's raw f32 rerank
    rows move into its own host row store (``ShardedCorpus.tiers``).
    ``resident_mb`` caps each shard's device row cache. Tiered sharded
    corpora are served by the host fan-out path only.

    ``mesh`` places each shard on the device(s) of its slot along the
    ``"model"`` axis: the shard is built there (the builds of different
    devices run at once, one thread each) and the stacked
    leaves are sharded ``P("model", ...)`` over the mesh, so no device
    holds another device's shard. Without it every shard is built and
    stacked on the default device."""
    pts = np.asarray(points)
    n_total, d = pts.shape
    n = cdiv(n_total, n_shards)
    if labels is not None:
        labels = np.asarray(labels, np.uint32)
        if labels.shape[0] != n_total:
            raise ValueError(
                f"labels rows ({labels.shape[0]}) != corpus size ({n_total})")
    homes = [None] * n_shards
    if mesh is not None:
        if n_shards % mesh.shape["model"]:
            raise ValueError(f"{n_shards} shards do not lay out on model "
                             f"axis of size {mesh.shape['model']}")
        cols = np.moveaxis(mesh.devices, mesh.axis_names.index("model"), 0)
        per = n_shards // mesh.shape["model"]
        homes = [cols[s // per].flat[0] for s in range(n_shards)]

    def on(s):
        return (contextlib.nullcontext() if homes[s] is None
                else jax.default_device(homes[s]))

    def build(s):
        with on(s):
            return build_fn(jnp.asarray(pts[s * n:(s + 1) * n]))

    if mesh is None:
        built = [build(s) for s in range(n_shards)]
    else:  # a thread per shard: a device whose dispatch queue is full
        # must not hold back the builds of the others
        with ThreadPoolExecutor(n_shards) as pool:
            built = list(pool.map(build, range(n_shards)))
    blocks, nbrs, starts, labs, tiers = [], [], [], [], []
    for s in range(n_shards):
        with on(s):  # every array of shard s lives on its home device
            block = pts[s * n:(s + 1) * n]
            graph, start_ids = built[s]
            if lane_pad:
                graph = graph.lane_padded(lane_pad)
            neighbors = np.asarray(graph.neighbors)
            n_pad = n - block.shape[0]
            stored = corpus_cast(jnp.asarray(block), corpus_dtype)
            if n_pad:  # pad points AND adjacency (INVALID = no edge)
                if corpus_dtype == "int8":
                    stored = pad_corpus_rows(stored, n_pad, _FAR)
                else:
                    stored = jnp.concatenate(
                        [stored,
                         jnp.full((n_pad, d), _FAR, dtype=stored.dtype)], axis=0)
                neighbors = np.concatenate(
                    [neighbors,
                     np.full((n_pad, neighbors.shape[1]), INVALID_ID, np.int32)],
                    axis=0)
            if tier:
                # split the (padded) shard: raw rows -> this shard's host store,
                # device arm -> the stacked points. The tier keeps device=None —
                # the stacked arm is sliced back in per search (with_device).
                from ..tier import tiered_corpus
                t = tiered_corpus(stored, corpus_dtype=corpus_dtype,
                                  resident_mb=resident_mb)
                tiers.append(t.with_device(None))
                stored = t.device
            blocks.append(stored)
            nbrs.append(jnp.asarray(neighbors))
            starts.append(jnp.asarray(start_ids, jnp.int32).reshape(-1))
            if labels is not None:
                lab = labels[s * n:(s + 1) * n]
                if n_pad:
                    lab = np.concatenate(
                        [lab, np.zeros((n_pad, lab.shape[1]), np.uint32)], axis=0)
                labs.append(jnp.asarray(lab))
    if mesh is None:
        stack = lambda *xs: jnp.stack(xs)
    else:
        stack = lambda *xs: _stack_placed(mesh, xs)
    return ShardedCorpus(
        points=jax.tree.map(stack, *blocks),
        neighbors=stack(*nbrs),
        start_ids=stack(*starts),
        offsets=jnp.arange(n_shards, dtype=jnp.int32) * n,
        n_total=n_total,
        labels=None if labels is None else stack(*labs),
        tiers=tuple(tiers) if tier else None,
    )


def _stack_placed(mesh: Mesh, shards) -> jax.Array:
    """Stack per-shard arrays into one array sharded ``P("model")`` on its
    leading axis, assembled from pieces on their own devices."""
    sharding = NamedSharding(mesh, P("model", *([None] * shards[0].ndim)))
    shape = (len(shards),) + shards[0].shape
    pieces = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        rows = range(len(shards))[idx[0]]
        pieces.append(jax.device_put(
            jnp.stack([shards[i] for i in rows]) if len(rows) > 1
            else shards[rows[0]][None], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, pieces)


def shard_view(x, s: int):
    """Shard ``s`` of a stacked leaf. Where the leaf is laid out one shard
    per device (``build_sharded(mesh=)``), this is that device's own piece,
    with no transfer; otherwise ``x[s]``."""
    if isinstance(x, jax.Array) and len(x.sharding.device_set) > 1:
        for piece in x.addressable_shards:
            rows = range(x.shape[0])[piece.index[0]]
            if len(rows) == 1 and rows[0] == s:
                return piece.data[0]
    return x[s]


def _remap_global(ids, offset, n_total: int):
    """Shard-local ids -> global ids. INVALID padding stays INVALID, and so
    does anything past ``n_total`` — defense in depth against pad rows of a
    short last shard (unreachable by construction in build_sharded)."""
    gids = jnp.where(ids == INVALID_ID, INVALID_ID, ids + offset)
    return jnp.where(gids < n_total, gids, INVALID_ID)


def union_merge(ids, dists, cap: int):
    """(Q, M) candidate ids/dists (INVALID/inf padded, disjoint across
    sources) -> the ``cap`` closest per query, distance-sorted."""
    dists, ids = jax.lax.sort((dists, ids), num_keys=1, is_stable=True)
    return ids[:, :cap], dists[:, :cap]


def sharded_range_search(
    *,
    mesh: Mesh,
    corpus: ShardedCorpus,
    queries,
    r,
    cfg: RangeConfig,
    es_radius: Optional[float] = None,
    tombstones=None,
    label_filter: Optional[LabelFilter] = None,
    model_axis="model",
    data_axis="data",
) -> RangeResult:
    """Union range search over every shard of ``corpus``; returns a global
    ``RangeResult`` (ids are corpus-global, counts summed across shards).

    Keyword-only: the parameter order matches the ``core.range_search``
    entry points with the mesh prepended —
    ``(mesh, corpus, queries, r, cfg, es_radius, tombstones,
    label_filter)``.

    ``r``/``es_radius`` are a shared scalar or per-query ``(Q,)`` vectors;
    radii shard along the data axis with their queries and broadcast to
    every shard along the model axis (each shard answers every query at
    that query's own radius).

    ``tombstones`` (optional) is a stacked ``(S, W)`` uint32 dead-slot
    bitset, one exact bitset per shard in shard-local slot space (the live
    subsystem's per-shard tombstones). Each shard's fused search filters its
    own dead slots at the result stage — deleted points still route the
    per-shard walk but never reach the union merge, so counts and the
    merged top-``result_cap`` are live-only.

    ``label_filter`` (optional) is a per-query
    :class:`~repro.core.labels.LabelFilter` over the corpus's attached
    ``labels`` (build_sharded(..., labels=)). Its mask rows shard along the
    data axis with their queries and broadcast to every shard; each shard
    evaluates the predicate locally at the result stage of its fused search
    (filtered-out points route the per-shard walk but never reach the union
    merge), so the merged result equals the post-filtered union."""
    if corpus.n_total <= 0:
        raise ValueError("ShardedCorpus.n_total must be the true corpus size")
    if getattr(corpus, "tiers", None) is not None:
        raise ValueError(
            "a tiered ShardedCorpus cannot run the collective shard_map "
            "program (host row fetches inside a collective would deadlock "
            "the mesh); use fault.fault_tolerant_sharded_search")
    if label_filter is not None and corpus.labels is None:
        raise ValueError(
            "corpus has no labels attached; build_sharded(..., labels=) to "
            "use filtered range search")
    s_total = corpus.n_shards
    n_model = mesh.shape[model_axis]
    if s_total % n_model:
        raise ValueError(
            f"{s_total} shards do not lay out on model axis of size {n_model}")
    s_loc = s_total // n_model
    cap = cfg.result_cap

    queries = jnp.asarray(queries)
    n_q = queries.shape[0]
    # normalize radii to (Q,) vectors so one shard_map signature serves both
    # forms (es None -> +inf, which never triggers early stopping)
    radii = broadcast_radius(r, n_q)
    es_vec = broadcast_radius(es_radius, n_q)
    has_filter = label_filter is not None
    masks = is_and = None
    if has_filter:
        masks = jnp.asarray(label_filter.masks, jnp.uint32)
        is_and = jnp.asarray(label_filter.is_and, bool)
        if masks.shape[0] != n_q:
            raise ValueError(
                f"label_filter covers {masks.shape[0]} lanes for {n_q} queries")
    dp_size = _axis_size(mesh, data_axis)
    q_pad = cdiv(n_q, dp_size) * dp_size
    if q_pad != n_q:  # replicate-pad the batch to the data-axis multiple
        queries = jnp.concatenate(
            [queries, jnp.broadcast_to(queries[:1],
                                       (q_pad - n_q,) + queries.shape[1:])])
        radii = jnp.concatenate(
            [radii, jnp.broadcast_to(radii[:1], (q_pad - n_q,))])
        es_vec = jnp.concatenate(
            [es_vec, jnp.broadcast_to(es_vec[:1], (q_pad - n_q,))])
        if has_filter:  # pad lanes ride with their replicated query
            masks = jnp.concatenate(
                [masks, jnp.broadcast_to(masks[:1],
                                         (q_pad - n_q, masks.shape[1]))])
            is_and = jnp.concatenate(
                [is_and, jnp.broadcast_to(is_and[:1], (q_pad - n_q,))])

    def local_fn(points, neighbors, start_ids, offsets, qs, rs, es,
                 *extra):
        # optional trailing args, ordered (tombs?, labs, mq, aq?) by the
        # closure flags — shard_map positional args cannot be keywords
        it = iter(extra)
        tombs = next(it) if tombstones is not None else None
        labs, mq, aq = (next(it), next(it), next(it)) if has_filter \
            else (None, None, None)
        filt = None if not has_filter else LabelFilter(masks=mq, is_and=aq)
        # points (s_loc, n, d) (or a stacked QuantizedCorpus), qs (q_loc, d),
        # rs/es (q_loc,): search every local shard at each query's own
        # radius. A quantized shard carries its own scales/guard maxima, so
        # the per-shard search guard-bands rs locally and reranks its own
        # boundary — the union merge then sees exact per-shard results.
        # tombs (s_loc, W): each shard filters its own dead slots inside the
        # fused search (result stage only), so the merge below is live-only.
        ids, dists, cnts, overs, nvis, ndis, ess, ph2, nrr = ([] for _ in range(9))
        for s in range(s_loc):
            shard_pts = jax.tree.map(lambda x: x[s], points)
            res = range_search_fused(
                corpus=shard_pts, graph=Graph(neighbors=neighbors[s]),
                queries=qs, start_ids=start_ids[s], r=rs, cfg=cfg,
                es_radius=es, tombstones=None if tombs is None else tombs[s],
                labels=None if labs is None else labs[s], label_filter=filt)
            gids = _remap_global(res.ids, offsets[s], corpus.n_total)
            ids.append(gids)
            dists.append(jnp.where(gids == INVALID_ID, jnp.inf, res.dists))
            # recount after the remap drop (result slots are exactly the
            # valid ids, so the surviving-id count IS the shard count)
            cnts.append(jnp.sum(gids != INVALID_ID, axis=1).astype(jnp.int32))
            overs.append(res.overflow)
            nvis.append(res.n_visited)
            ndis.append(res.n_dist)
            ess.append(res.es_stopped)
            ph2.append(res.phase2)
            nrr.append(res.n_rerank)
        ids = jnp.concatenate(ids, axis=1)      # (q_loc, s_loc*K)
        dists = jnp.concatenate(dists, axis=1)

        # union across the model axis: gather every shard's candidates
        ids = jax.lax.all_gather(ids, model_axis, axis=0)     # (n_model, q, M)
        dists = jax.lax.all_gather(dists, model_axis, axis=0)
        ids = jnp.moveaxis(ids, 0, 1).reshape(ids.shape[1], -1)
        dists = jnp.moveaxis(dists, 0, 1).reshape(dists.shape[1], -1)
        ids, dists = union_merge(ids, dists, cap)

        total = jax.lax.psum(sum(cnts), model_axis)           # (q_loc,)
        over = jax.lax.psum(sum(o.astype(jnp.int32) for o in overs),
                            model_axis) > 0
        return RangeResult(
            ids=ids,
            dists=dists,
            count=jnp.minimum(total, cap).astype(jnp.int32),
            overflow=over | (total > cap),
            n_visited=jax.lax.psum(sum(nvis), model_axis),
            n_dist=jax.lax.psum(sum(ndis), model_axis),
            es_stopped=jax.lax.psum(
                sum(e.astype(jnp.int32) for e in ess), model_axis) > 0,
            phase2=jax.lax.psum(
                sum(p.astype(jnp.int32) for p in ph2), model_axis) > 0,
            n_rerank=jax.lax.psum(sum(nrr), model_axis),
        )

    row = P(data_axis)
    mat = P(data_axis, None)
    # the corpus spec shards every leaf's leading (shard) axis along the
    # model axis — a tree of specs so a stacked QuantizedCorpus (leaves of
    # differing rank, incl. per-shard () guard maxima) lays out the same
    # way as a plain (S, n, d) array
    pts_spec = jax.tree.map(
        lambda leaf: P(model_axis, *([None] * (leaf.ndim - 1))),
        corpus.points)
    out_spec = RangeResult(ids=mat, dists=mat, count=row, overflow=row,
                           n_visited=row, n_dist=row, es_stopped=row,
                           phase2=row, n_rerank=row)
    base_specs = (pts_spec, P(model_axis, None, None),
                  P(model_axis, None), P(model_axis), mat, row, row)
    args = (corpus.points, corpus.neighbors, corpus.start_ids,
            corpus.offsets, queries, radii, es_vec)
    extra_specs, extra_args = [], []
    if tombstones is not None:
        extra_specs.append(P(model_axis, None))
        extra_args.append(jnp.asarray(tombstones, jnp.uint32))
    if has_filter:  # labels shard with the model axis, masks with queries
        extra_specs += [P(model_axis, None, None), mat, row]
        extra_args += [corpus.labels, masks, is_and]
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=base_specs + tuple(extra_specs),
                   out_specs=out_spec, check_vma=False)
    out = fn(*args, *extra_args)
    if q_pad != n_q:
        out = jax.tree.map(lambda x: x[:n_q], out)
    return out
