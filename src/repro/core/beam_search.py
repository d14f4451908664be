"""Batched graph beam search with range-retrieval extensions.

This implements the paper's Algorithms 1 (BeamSearch), 3/4 (EarlyStopping) and
5 (DoublingSearch) as a single fixed-shape ``jax.lax.while_loop``:

* The beam is a distance-sorted triple ``(ids, dists, expanded)`` of length
  ``max_beam`` (the hardware allocation), of which only the first
  ``active_width`` entries are *eligible for expansion* — ``active_width`` is
  the paper's beam size ``b``.
* **Multi-node expansion**: every loop iteration expands the closest
  ``expand_width`` unexpanded beam entries at once through the fused expand
  path (adjacency gather + vector gather + distance + one-pass tile dedup —
  ``kernels.expand``). This cuts the iteration count ~``expand_width``-fold,
  which is what makes the traversal accelerator-friendly: per-iteration
  fixed costs (sort, control flow, the vmapped-batch straggler effect)
  amortize over E expansions, and the E*R distance tile is one pass
  instead of E skinny ones.
* **Bitset visited filtering**: every node is marked in a packed per-query
  ``(W,) uint32`` bitset (``core.bitset``) when it first *enters the beam*
  (start points included), so the duplicate tests against the beam and
  against the visited log are one O(1) bit probe per candidate instead of
  O(max_beam + visit_cap) broadcasts. Above ``SearchConfig.bitset_cap_bits``
  the filter hash-buckets, keeping memory bounded at billion scale.
* **Rank-gather merge**: the candidate tile is merged into the
  already-sorted beam by broadcast rank counts over int-keyed distances and
  a one-hot gather — replacing the full float-keyed ``lax.sort`` over
  ``max_beam + E*R`` entries every iteration (see ``_merge_sorted`` for the
  profiling that drove this shape: vmapped scatters and float sort
  comparators are the expensive primitives, vectorized compares are not).
* **Doubling** (Alg. 5) is performed *in place*: when the active prefix is
  fully expanded and at least ``lam * b`` of it is in-range, ``b`` doubles
  (up to ``max_beam``) and the same loop continues. This is our TPU adaptation
  A1 (see DESIGN.md §2): it visits a superset of the restart-based variant's
  candidates with strictly fewer re-expansions.
* **Early stopping** (Algs. 3/4) is evaluated before each expansion using one
  of the paper's four metrics (``d_visited`` — the recommended one —
  ``d_top1``, ``d_top10``, or ``d_top10 / d_start``), on the *closest*
  candidate of the batch. A search that has already found an in-range
  candidate never early-stops (paper Sec. 4.3).
* Every expansion is appended to a visited log (capacity ``visit_cap``); the
  log is what Vamana's RobustPrune consumes at build time and what greedy
  range search seeds from. ``visit_cap`` remains a strict expansion budget:
  the last iteration expands only the remaining budget even if that is less
  than ``expand_width``.

Single-query semantics are written once and batched with ``jax.vmap``; the
vmapped while-loop steps all queries until every lane is done (lanes that
finish early are frozen by the batching rule — the query-compaction machinery
in ``range_search.py`` exists precisely to bound that straggler effect).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.expand import expand_frontier, expand_frontier_1, pack_int8_rows
from ..utils import INVALID_ID
from .bitset import (
    DEFAULT_BITSET_CAP_BITS,
    bitset_add,
    bitset_contains,
    bitset_exact,
    bitset_init,
    bitset_num_words,
    first_slot_occurrence,
)
from .corpus import CORPUS_DTYPES, corpus_size
from .distances import gather_dist
from .graph import Graph

# Early-stop metric selector (paper Sec. 4.3). Static ints so jit specializes.
ES_NONE = 0
ES_D_VISITED = 1   # distance to the node being visited (paper's best)
ES_D_TOP1 = 2      # distance to closest known neighbor
ES_D_TOP10 = 3     # distance to 10th closest known neighbor
ES_RATIO_TOP10 = 4 # d_top10 / d_start


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Static search hyper-parameters (hashable; a jit static argument)."""

    beam: int = 64            # initial beam width b (paper's B)
    max_beam: int = 64        # allocation; > beam enables in-place doubling
    visit_cap: int = 256      # max expansions == visited-log capacity
    lam: float = 1.0          # λ: in-range fraction of beam that triggers widening
    es_metric: int = ES_NONE  # early-stopping metric (ES_*)
    es_visit_limit: int = 20  # vl: expansions before early stop may trigger
    metric: str = "l2"
    # E: frontier nodes expanded per iteration. E >= 2 takes the fused
    # multi-node path (expand kernel + bitset + sorted merge); E == 1 runs
    # the paper-faithful single-node reference step (pre-fusion dataflow,
    # kept as the correctness/perf baseline — see _step_reference).
    expand_width: int = 4
    bitset_cap_bits: int = DEFAULT_BITSET_CAP_BITS  # seen-filter memory bound
    use_expand_kernel: bool = False  # Pallas expand kernel (compiles for TPU only)
    # declared corpus storage dtype: "float32" | "bfloat16" | "int8". The
    # search itself dispatches on the corpus *value* (array vs
    # QuantizedCorpus); this knob is what deploy configs / builders consult
    # when materializing the corpus (engine.build, build_sharded, serve CLI).
    corpus_dtype: str = "float32"

    def __post_init__(self):
        if self.beam < 1 or self.max_beam < self.beam:
            raise ValueError("need 1 <= beam <= max_beam")
        if self.visit_cap < 1:
            raise ValueError("visit_cap must be >= 1")
        if self.expand_width < 1:
            raise ValueError("expand_width must be >= 1")
        if self.bitset_cap_bits < 32:
            raise ValueError("bitset_cap_bits must be >= 32")
        if self.corpus_dtype not in CORPUS_DTYPES:
            raise ValueError(
                f"corpus_dtype must be one of {CORPUS_DTYPES}")

    @property
    def eff_expand_width(self) -> int:
        """E clamped to the beam allocation (never more slots than exist)."""
        return min(self.expand_width, self.max_beam)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BeamState:
    """Per-query search state (batched by vmap on the leading axis)."""

    ids: jnp.ndarray        # (L,) int32, distance-sorted, INVALID_ID padded
    dists: jnp.ndarray      # (L,) float32, +inf padded
    expanded: jnp.ndarray   # (L,) bool
    active_width: jnp.ndarray  # () int32 — the paper's b
    n_visited: jnp.ndarray  # () int32
    d_visited: jnp.ndarray  # () float32 — farthest node expanded last step
    d_start: jnp.ndarray    # () float32 — distance to the search entry point
    visited_ids: jnp.ndarray    # (V,) int32 log of expanded nodes
    visited_dists: jnp.ndarray  # (V,) float32
    visited_bits: jnp.ndarray   # (W,) uint32 — discovered-node bitset
    n_dist: jnp.ndarray     # () int32 distance-computation counter
    es_stopped: jnp.ndarray # () bool — terminated by early stopping
    done: jnp.ndarray       # () bool


def _sorted_trunc(ids, dists, expanded, length: int):
    """Sort (dists, ids, expanded) ascending by distance; keep first `length`."""
    dists, ids, expanded = jax.lax.sort(
        (dists, ids, expanded.astype(jnp.int32)), num_keys=1, is_stable=True
    )
    return ids[:length], dists[:length], expanded[:length].astype(bool)


def _f32_ascending_key(x: jnp.ndarray) -> jnp.ndarray:
    """Monotone uint32 re-encoding of f32 (sign-flip trick; handles +-inf).

    XLA sorts integer keys several times faster than float keys (no
    NaN-aware total-order comparator), and the beam merge sits inside the
    traversal's hot loop — distances are finite-or-+inf, never NaN.
    """
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return u ^ (jnp.uint32(0x80000000) + (u >> 31) * jnp.uint32(0x7FFFFFFF))


def _f32_from_key(k: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``_f32_ascending_key``."""
    u = k ^ jnp.where(k >= jnp.uint32(0x80000000), jnp.uint32(0x80000000),
                      jnp.uint32(0xFFFFFFFF))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _merge_sorted(b_ids, b_dists, b_exp, c_ids, c_dists, length: int):
    """Merge the candidate tile into the sorted beam; keep the closest
    ``length``. Returns ``(ids, dists, expanded, entrant)`` where
    ``entrant`` marks output slots filled from the candidate tile.

    No ``lax.sort`` over ``max_beam + E*R`` and no scatter: each element's
    merged *rank* is a broadcast count over the int-keyed distances
    (index-tiebreak makes it an exact permutation; the beam, being first in
    concat order, wins ties), and the output beam gathers from a rank
    one-hot. Profiling drove this shape: XLA lowers vmapped scatters to
    per-update loops and float sort comparators cost ~5x integer compares,
    so the O(M^2) vectorized compare matrix (M = max_beam + E*R, a few
    hundred) beats both a sort-based merge and a scatter placement on CPU,
    and maps onto plain VPU ops on TPU.
    """
    m = b_ids.shape[0] + c_ids.shape[0]
    keys = jnp.concatenate([_f32_ascending_key(b_dists),
                            _f32_ascending_key(c_dists)])
    ids = jnp.concatenate([b_ids, c_ids])
    idx = jnp.arange(m)
    rank = jnp.sum((keys[None, :] < keys[:, None])
                   | ((keys[None, :] == keys[:, None])
                      & (idx[None, :] < idx[:, None])), axis=1)
    hit = rank[None, :] == jnp.arange(length)[:, None]   # (length, M)
    src = jnp.argmax(hit, axis=1)                        # exact: rank is a perm
    out_ids = ids[src]
    out_dists = _f32_from_key(keys[src])
    from_beam = src < b_ids.shape[0]
    out_exp = jnp.where(from_beam, b_exp[jnp.minimum(src, b_ids.shape[0] - 1)],
                        False)
    return out_ids, out_dists, out_exp, ~from_beam


def init_state(
    points: jnp.ndarray,
    q: jnp.ndarray,
    start_ids: jnp.ndarray,
    cfg: SearchConfig,
) -> BeamState:
    """Seed the beam with the start points (usually the medoid)."""
    L, V = cfg.max_beam, cfg.visit_cap
    W = bitset_num_words(corpus_size(points), cfg.bitset_cap_bits)
    s = start_ids.astype(jnp.int32)
    sd = gather_dist(points, s, q, cfg.metric)
    # de-duplicate identical start slots (keep first). Slot-level equality ==
    # id-level equality in the exact-bitset regime; in the hashed regime it
    # additionally collapses colliding buckets, keeping bitset_add exact.
    slot = s % jnp.int32(W * 32)
    order = jnp.arange(s.shape[0])
    dup = (slot[:, None] == slot[None, :]) & (order[:, None] > order[None, :])
    is_dup = jnp.any(dup, axis=1)
    sd = jnp.where(is_dup, jnp.inf, sd)
    s = jnp.where(is_dup, INVALID_ID, s)
    bits = bitset_add(bitset_init(W), s, s != INVALID_ID)

    ids = jnp.full((L,), INVALID_ID, dtype=jnp.int32).at[: s.shape[0]].set(s)
    dists = jnp.full((L,), jnp.inf, dtype=jnp.float32).at[: s.shape[0]].set(sd)
    expanded = jnp.zeros((L,), dtype=bool)
    ids, dists, expanded = _sorted_trunc(ids, dists, expanded, L)
    return BeamState(
        ids=ids,
        dists=dists,
        expanded=expanded,
        active_width=jnp.asarray(cfg.beam, jnp.int32),
        n_visited=jnp.asarray(0, jnp.int32),
        d_visited=jnp.asarray(0.0, jnp.float32),
        d_start=jnp.min(sd),
        visited_ids=jnp.full((V,), INVALID_ID, dtype=jnp.int32),
        visited_dists=jnp.full((V,), jnp.inf, dtype=jnp.float32),
        visited_bits=bits,
        # charge only the distinct starts: duplicate slots were zeroed out
        # above, so a start list padded by repetition (per-lane entry-point
        # selection pads broad lanes with copies of the defaults) costs
        # exactly what the unpadded list does — bitwise-identical states
        n_dist=jnp.sum(s != INVALID_ID).astype(jnp.int32),
        es_stopped=jnp.asarray(False),
        done=jnp.asarray(False),
    )


def _es_value(st: BeamState, cand_dist, cfg: SearchConfig):
    if cfg.es_metric == ES_D_VISITED:
        return cand_dist
    if cfg.es_metric == ES_D_TOP1:
        return st.dists[0]
    if cfg.es_metric == ES_D_TOP10:
        return st.dists[jnp.minimum(9, st.active_width - 1)]
    if cfg.es_metric == ES_RATIO_TOP10:
        return st.dists[jnp.minimum(9, st.active_width - 1)] / jnp.maximum(st.d_start, 1e-30)
    return jnp.asarray(jnp.inf, jnp.float32)


def in_range_count(st: BeamState, r, width: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Number of in-range entries within the first `width` beam slots."""
    w = st.active_width if width is None else width
    pos_ok = jnp.arange(st.ids.shape[0]) < w
    return jnp.sum((st.dists <= r) & (st.ids != INVALID_ID) & pos_ok)


def _expand_tile(points, graph: Graph, frontier, q, cfg: SearchConfig,
                 packed=None):
    """Fused expansion of an (E,) frontier: (E*R,) ids/dists + n_dist.

    The Pallas kernel path is opt-in and compiles for TPU only; the XLA
    reference is the same dataflow and is what runs by default.
    ``packed`` is the kernel's int8 row layout from ``_kernel_operand``.
    """
    if cfg.use_expand_kernel:
        ids, dists, nd = expand_frontier(
            points, graph.neighbors, frontier[None], q[None],
            metric=cfg.metric, use_pallas=True, packed=packed)
        return ids[0], dists[0], nd[0]
    return expand_frontier_1(points, graph.neighbors, frontier, q, cfg.metric)


def _kernel_operand(points, cfg: SearchConfig):
    """Per-dispatch operand of the expand kernel, built before the search
    loop so it is not rebuilt per iteration: the int8 codes repacked for
    row DMA (``kernels.expand.pack_int8_rows``), or None."""
    if cfg.use_expand_kernel and getattr(points, "codes", None) is not None:
        return pack_int8_rows(points.codes)
    return None


def _step_reference(points, graph: Graph, q, r, es_radius, cfg: SearchConfig,
                    st: BeamState) -> BeamState:
    """The paper-faithful single-node step (``expand_width=1``).

    This is the pre-fusion dataflow kept verbatim as the correctness and
    performance baseline the fused multi-node path is measured against (the
    smoke gate A/Bs the two): one expansion per iteration, unfused
    ``out_neighbors`` + ``gather_dist``, duplicate suppression by three
    broadcast scans (intra-row, beam, visited log), and a full
    ``lax.sort`` over ``max_beam + R`` entries. The discovery bitset is
    carried through untouched.
    """
    L = cfg.max_beam
    pos = jnp.arange(L)
    eligible = (st.ids != INVALID_ID) & (~st.expanded) & (pos < st.active_width)
    has_frontier = jnp.any(eligible)

    saturated = in_range_count(st, r) >= jnp.ceil(cfg.lam * st.active_width.astype(jnp.float32)).astype(jnp.int32)
    can_widen = (st.active_width < cfg.max_beam) & saturated
    new_width = jnp.where(
        ~has_frontier & can_widen,
        jnp.minimum(st.active_width * 2, cfg.max_beam),
        st.active_width,
    )
    finished = ~has_frontier & ~can_widen

    idx = jnp.argmax(eligible)  # first eligible slot == closest unexpanded
    cand_id = st.ids[idx]
    cand_dist = st.dists[idx]
    found_any = st.dists[0] <= r
    es_on = cfg.es_metric != ES_NONE
    es_trigger = (
        es_on
        & has_frontier
        & (~found_any)
        & (st.n_visited >= cfg.es_visit_limit)
        & (_es_value(st, cand_dist, cfg) > es_radius)
    )

    do_expand = has_frontier & (~es_trigger)

    nbrs = graph.out_neighbors(cand_id)  # (R,)
    nd = gather_dist(points, nbrs, q, cfg.metric)  # (R,) +inf for invalid
    rr = jnp.arange(nbrs.shape[0])
    dup_in_row = jnp.any((nbrs[:, None] == nbrs[None, :]) & (rr[None, :] < rr[:, None]) & (nbrs[:, None] != INVALID_ID), axis=1)
    in_beam = jnp.any((nbrs[:, None] == st.ids[None, :]) & (nbrs[:, None] != INVALID_ID), axis=1)
    in_visited = jnp.any((nbrs[:, None] == st.visited_ids[None, :]) & (nbrs[:, None] != INVALID_ID), axis=1)
    fresh = (~dup_in_row) & (~in_beam) & (~in_visited)
    nd = jnp.where(fresh, nd, jnp.inf)
    nbr_ids = jnp.where(fresh, nbrs, INVALID_ID)

    expanded = st.expanded.at[idx].set(True)
    merged_ids = jnp.concatenate([st.ids, nbr_ids])
    merged_dists = jnp.concatenate([st.dists, nd])
    merged_exp = jnp.concatenate([expanded, jnp.zeros_like(fresh)])
    m_ids, m_dists, m_exp = _sorted_trunc(merged_ids, merged_dists, merged_exp, L)

    v_idx = jnp.minimum(st.n_visited, cfg.visit_cap - 1)
    visited_ids = st.visited_ids.at[v_idx].set(cand_id)
    visited_dists = st.visited_dists.at[v_idx].set(cand_dist)

    exp_state = BeamState(
        ids=m_ids,
        dists=m_dists,
        expanded=m_exp,
        active_width=new_width,
        n_visited=st.n_visited + 1,
        d_visited=cand_dist,
        d_start=st.d_start,
        visited_ids=visited_ids,
        visited_dists=visited_dists,
        visited_bits=st.visited_bits,
        n_dist=st.n_dist + jnp.sum(nbrs != INVALID_ID).astype(jnp.int32),
        es_stopped=st.es_stopped,
        done=(st.n_visited + 1) >= cfg.visit_cap,
    )

    keep_state = dataclasses.replace(
        st,
        active_width=new_width,
        es_stopped=st.es_stopped | es_trigger,
        done=finished | es_trigger,
    )

    return jax.tree.map(
        lambda a, b: jnp.where(do_expand, a, b), exp_state, keep_state
    )


def _step(points, graph: Graph, q, r, es_radius, cfg: SearchConfig, st: BeamState,
          packed=None) -> BeamState:
    if cfg.eff_expand_width == 1:
        return _step_reference(points, graph, q, r, es_radius, cfg, st)
    L = cfg.max_beam
    E = cfg.eff_expand_width
    pos = jnp.arange(L)
    eligible = (st.ids != INVALID_ID) & (~st.expanded) & (pos < st.active_width)
    num_elig = jnp.sum(eligible.astype(jnp.int32))
    has_frontier = num_elig > 0

    # -- frontier exhausted at current width: widen (Alg. 5) or finish -------
    saturated = in_range_count(st, r) >= jnp.ceil(cfg.lam * st.active_width.astype(jnp.float32)).astype(jnp.int32)
    can_widen = (st.active_width < cfg.max_beam) & saturated
    new_width = jnp.where(
        ~has_frontier & can_widen,
        jnp.minimum(st.active_width * 2, cfg.max_beam),
        st.active_width,
    )
    finished = ~has_frontier & ~can_widen

    # -- early stopping (Algs. 3/4), evaluated on the closest candidate ------
    idx = jnp.argmax(eligible)  # first eligible slot == closest unexpanded
    cand0_dist = st.dists[idx]
    found_any = st.dists[0] <= r  # never stop once an in-range candidate is known
    es_on = cfg.es_metric != ES_NONE
    es_trigger = (
        es_on
        & has_frontier
        & (~found_any)
        & (st.n_visited >= cfg.es_visit_limit)
        & (_es_value(st, cand0_dist, cfg) > es_radius)
    )

    do_expand = has_frontier & (~es_trigger)

    # -- select the closest E unexpanded slots (beam is sorted) --------------
    # (broadcast one-hots instead of scatters/argsorts throughout this path:
    # XLA lowers vmapped scatters to sequential per-update loops and sort
    # comparators cost ~5x a vectorized compare — both profiled hot spots)
    budget = jnp.asarray(cfg.visit_cap, jnp.int32) - st.n_visited
    e_cnt = jnp.minimum(jnp.minimum(num_elig, E), budget)
    lane = jnp.arange(E)
    lane_ok = lane < e_cnt
    ecum = jnp.cumsum(eligible.astype(jnp.int32))
    sel_hit = (eligible[:, None] & (ecum[:, None] == (lane + 1)[None, :])
               & lane_ok[None, :])                               # (L, E)
    sel = jnp.argmax(sel_hit, axis=0)  # position of the (e+1)-th eligible
    cand_ids = jnp.where(lane_ok, st.ids[sel], INVALID_ID)
    cand_dists = jnp.where(lane_ok, st.dists[sel], jnp.inf)

    # -- fused expansion + bitset seen filter --------------------------------
    nbr_ids, nd, nd_inc = _expand_tile(points, graph, cand_ids, q, cfg,
                                       packed)
    valid = nbr_ids != INVALID_ID
    seen = bitset_contains(st.visited_bits, jnp.where(valid, nbr_ids, 0)) & valid
    fresh = valid & ~seen
    nbr_ids = jnp.where(fresh, nbr_ids, INVALID_ID)
    nd = jnp.where(fresh, nd, jnp.inf)

    # -- merge the candidate tile into the sorted beam (rank gather) ---------
    expanded = st.expanded | jnp.any(sel_hit, axis=1)
    m_ids, m_dists, m_exp, entrant = _merge_sorted(
        st.ids, st.dists, expanded, nbr_ids, nd, L)

    # -- mark beam entrants in the seen bitset -------------------------------
    # A node is "seen" once it has ever held a beam slot (start points are
    # marked in init_state); expanded nodes stay marked forever, so no node
    # is expanded twice. Candidates truncated straight off the merge stay
    # unmarked and may be rediscovered — the unfused reference's semantics.
    mark = entrant & (m_ids != INVALID_ID)
    if not bitset_exact(corpus_size(points), st.visited_bits.shape[0]):
        # hashed regime: distinct ids may share a bucket; keep one per slot
        mark = first_slot_occurrence(st.visited_bits, m_ids, mark)
    bits = bitset_add(st.visited_bits, m_ids, mark)

    # -- visited log: one append per expanded node ---------------------------
    v_idx = jnp.where(lane_ok, st.n_visited + lane, cfg.visit_cap)
    v_hit = jnp.arange(cfg.visit_cap)[:, None] == v_idx[None, :]    # (V, E)
    v_any = jnp.any(v_hit, axis=1)
    v_lane = jnp.argmax(v_hit, axis=1)
    visited_ids = jnp.where(v_any, cand_ids[v_lane], st.visited_ids)
    visited_dists = jnp.where(v_any, cand_dists[v_lane], st.visited_dists)

    exp_state = BeamState(
        ids=m_ids,
        dists=m_dists,
        expanded=m_exp,
        active_width=new_width,
        n_visited=st.n_visited + e_cnt,
        d_visited=jnp.max(jnp.where(lane_ok, cand_dists, -jnp.inf)),
        d_start=st.d_start,
        visited_ids=visited_ids,
        visited_dists=visited_dists,
        visited_bits=bits,
        n_dist=st.n_dist + nd_inc,
        es_stopped=st.es_stopped,
        done=(st.n_visited + e_cnt) >= cfg.visit_cap,
    )

    keep_state = dataclasses.replace(
        st,
        active_width=new_width,
        es_stopped=st.es_stopped | es_trigger,
        done=finished | es_trigger,
    )

    return jax.tree.map(
        lambda a, b: jnp.where(do_expand, a, b), exp_state, keep_state
    )


def broadcast_radius(r, n: int, default: float = jnp.inf) -> jnp.ndarray:
    """Normalize a radius argument to a per-query ``(n,)`` float32 vector.

    Accepts ``None`` (-> ``default``, broadcast), a python/np scalar, a 0-d
    array (broadcast to every lane), or an ``(n,)`` vector (returned as-is).
    Every layer of the query path normalizes through here, so scalar call
    sites keep working and all-equal vectors are *the same program* as the
    scalar broadcast — the backbone of the oracle harness's bitwise
    scalar/vector equivalence check.
    """
    if r is None:
        r = default
    r = jnp.asarray(r, jnp.float32)
    if r.ndim == 0:
        return jnp.broadcast_to(r, (n,))
    if r.shape != (n,):
        raise ValueError(f"radius vector has shape {r.shape}, expected ({n},)")
    return r


@partial(jax.jit, static_argnames=("cfg",))
def beam_search(
    points: jnp.ndarray,
    graph: Graph,
    q: jnp.ndarray,
    start_ids: jnp.ndarray,
    r: jnp.ndarray,
    cfg: SearchConfig,
    es_radius: Optional[jnp.ndarray] = None,
) -> BeamState:
    """Run the search loop for one query (``r``/``es_radius`` are scalars;
    the batch entry point below carries them per-lane)."""
    esr = jnp.asarray(jnp.inf, jnp.float32) if es_radius is None else jnp.asarray(es_radius, jnp.float32)
    r = jnp.asarray(r, jnp.float32)
    packed = _kernel_operand(points, cfg)
    st = init_state(points, q, start_ids, cfg)
    st = jax.lax.while_loop(
        lambda s: ~s.done,
        lambda s: _step(points, graph, q, r, esr, cfg, s, packed),
        st,
    )
    return st


@partial(jax.jit, static_argnames=("cfg",))
def beam_search_batch(
    points: jnp.ndarray,
    graph: Graph,
    queries: jnp.ndarray,  # (Q, d)
    start_ids: jnp.ndarray,
    r: jnp.ndarray,        # scalar or (Q,) per-query radii
    cfg: SearchConfig,
    es_radius: Optional[jnp.ndarray] = None,  # scalar or (Q,)
) -> BeamState:
    """Batched search; ``r`` and ``es_radius`` are per-lane vmap axes, so a
    single micro-batch may mix radii freely (scalars broadcast).

    ``start_ids`` is shared ``(S,)`` or per-lane ``(Q, S)`` — the filtered
    compacted path seeds selective lanes with posting-list members while
    broad lanes pad the shared defaults by repetition (duplicates collapse
    in ``init_state``, so padding never perturbs the walk)."""
    n = queries.shape[0]
    rv = broadcast_radius(r, n)
    esv = broadcast_radius(es_radius, n)
    with jax.named_scope("range.phase1"):
        if start_ids.ndim == 2:
            fn = lambda q, s_, r_, es_: beam_search(points, graph, q, s_, r_,
                                                    cfg, es_)
            return jax.vmap(fn)(queries, start_ids, rv, esv)
        fn = lambda q, r_, es_: beam_search(points, graph, q, start_ids, r_,
                                            cfg, es_)
        return jax.vmap(fn)(queries, rv, esv)


def topk_from_state(st: BeamState, k: int):
    """Top-k (ids, dists) from a finished search (standard ANNS answer)."""
    return st.ids[..., :k], st.dists[..., :k]
