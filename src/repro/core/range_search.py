"""Range-retrieval algorithms on top of the beam search (paper Algs. 2/5/6).

Three modes, matching the paper:

* ``"beam"``     — the naive baseline: one beam search, filter the beam by r.
* ``"doubling"`` — Alg. 5 via in-place beam widening (``max_beam > beam``).
* ``"greedy"``   — Alg. 6: initial beam search; queries whose beam is
  saturated with in-range results continue with Alg. 2 (expand only in-range
  nodes, unbounded queue -> fixed-capacity result buffer + overflow counter).

Batched execution is two-phase with **query compaction** (DESIGN.md §2): the
uniform phase 1 runs over the whole batch; the irregular phase 2 runs only on
the compacted subset of queries that need it (bucketed to powers of two so jit
compiles O(log Q) variants). ``range_search_fused`` keeps everything in one
XLA program (no host sync) for dry-run lowering and single-dispatch serving.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import INVALID_ID, next_pow2
from .beam_search import (
    BeamState,
    SearchConfig,
    _expand_tile,
    _f32_ascending_key,
    _f32_from_key,
    _kernel_operand,
    beam_search_batch,
    broadcast_radius,
    in_range_count,
)
from .bitset import (
    bitset_add,
    bitset_contains,
    bitset_exact,
    bitset_init,
    bitset_num_words,
    first_slot_occurrence,
)
from .corpus import QuantizedCorpus, corpus_size, upper_bound_dists
from .distances import gather_dist, point_dist
from .graph import Graph
from .labels import LabelFilter, label_match_matrix, labels_match


@dataclasses.dataclass(frozen=True)
class RangeConfig:
    """Static configuration for a range query batch."""

    search: SearchConfig = dataclasses.field(default_factory=SearchConfig)
    mode: str = "greedy"          # beam | doubling | greedy
    result_cap: int = 1024        # K_cap: per-query result buffer
    frontier_rounds: int = 4096   # greedy expansion budget (expansions/query)
    lam: float = 1.0              # λ threshold for entering phase 2
    # quantized-corpus two-pass: exact-rerank the guard-band boundary after
    # the approximate search (requires the corpus to carry raw vectors).
    # False keeps the guard-banded superset (keep band d_hat <= r + eps) —
    # the pre-rerank membership the oracle superset test pins down.
    rerank: bool = True
    # filtered retrieval: when a lane's predicate matches fewer than this
    # fraction of the corpus, the compacted path answers it by brute-
    # scanning the posting list with the exact kernel instead of walking
    # the graph (FilterGraph's _threshold dispatch). 0 disables the
    # fallback; the fused single-program path always walks (it has no host
    # sync to split lanes across programs).
    filter_threshold: float = 0.0

    def __post_init__(self):
        if self.mode not in ("beam", "doubling", "greedy"):
            raise ValueError(f"bad mode {self.mode!r}")
        if self.mode == "doubling" and self.search.max_beam <= self.search.beam:
            raise ValueError("doubling mode needs search.max_beam > search.beam")
        if not 0.0 <= self.filter_threshold <= 1.0:
            raise ValueError("filter_threshold must be in [0, 1]")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RangeResult:
    """Batched range-query output (all arrays INVALID/inf padded)."""

    ids: jnp.ndarray       # (Q, K) int32
    dists: jnp.ndarray     # (Q, K) float32
    count: jnp.ndarray     # (Q,) int32 — number of valid entries
    overflow: jnp.ndarray  # (Q,) bool — K_cap or budget exceeded
    n_visited: jnp.ndarray # (Q,) int32 — phase-1 expansions
    n_dist: jnp.ndarray    # (Q,) int32 — total distance computations
    es_stopped: jnp.ndarray  # (Q,) bool
    phase2: jnp.ndarray    # (Q,) bool — query took the second phase
    n_rerank: jnp.ndarray  # (Q,) int32 — guard-band candidates exact-reranked
    # (Q,) int32 host array — greedy phase-2 expansions per lane, -1 where
    # the lane stopped at phase 1; None on paths that do not track them
    p2_rounds: Optional[np.ndarray] = None
    # host ints of the sliced greedy phase 2 (same paths as p2_rounds):
    # lane-rounds dispatched (over its slices, bucket x the slowest lane's
    # advance) and the number of slices
    p2_slot_rounds: Optional[int] = None
    p2_slices: Optional[int] = None


# ---------------------------------------------------------------------------
# Greedy continuation (paper Alg. 2), fixed-shape form.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GreedyState:
    res_ids: jnp.ndarray    # (K,) int32 — every id here is in-range
    res_dists: jnp.ndarray  # (K,) float32
    res_count: jnp.ndarray  # () int32
    expand_ptr: jnp.ndarray # () int32
    rounds: jnp.ndarray     # () int32
    overflow: jnp.ndarray   # () bool
    n_dist: jnp.ndarray     # () int32
    seen_bits: jnp.ndarray  # (W,) uint32 — result-membership bitset


def _greedy_init(st: BeamState, r, cap: int, num_words: int,
                 exact_bits: bool) -> GreedyState:
    """Seed the result buffer with every in-range node whose exact distance is
    already known: the visited log plus unexpanded in-range beam entries
    (disjoint by construction — expanded beam nodes are in the log). The
    result membership is mirrored into a bitset so the per-expansion "already
    a result?" test is an O(1) probe, not an O(result_cap) broadcast."""
    v_ok = st.visited_dists <= r
    b_ok = (st.dists <= r) & (~st.expanded) & (st.ids != INVALID_ID)
    ids = jnp.concatenate([jnp.where(v_ok, st.visited_ids, INVALID_ID),
                           jnp.where(b_ok, st.ids, INVALID_ID)])
    dists = jnp.concatenate([jnp.where(v_ok, st.visited_dists, jnp.inf),
                             jnp.where(b_ok, st.dists, jnp.inf)])
    # pack in-range entries to the front, closest first (paper pops
    # closest-first; our FIFO expansion then visits in that order)
    _, ids, dists = jax.lax.sort((_f32_ascending_key(dists), ids, dists),
                                 num_keys=1, is_stable=True)
    k = min(cap, ids.shape[0])
    res_ids = jnp.full((cap,), INVALID_ID, jnp.int32).at[:k].set(ids[:k])
    res_dists = jnp.full((cap,), jnp.inf, jnp.float32).at[:k].set(dists[:k])
    total = jnp.sum(jnp.isfinite(dists))
    count = jnp.minimum(total, cap)
    bits = bitset_init(num_words)
    seed_ok = res_ids != INVALID_ID  # unique ids by construction
    if not exact_bits:  # hashed regime: collapse colliding buckets first
        seed_ok = first_slot_occurrence(bits, res_ids, seed_ok)
    bits = bitset_add(bits, res_ids, seed_ok)
    return GreedyState(
        res_ids=res_ids,
        res_dists=res_dists,
        res_count=count.astype(jnp.int32),
        expand_ptr=jnp.asarray(0, jnp.int32),
        rounds=jnp.asarray(0, jnp.int32),
        overflow=(total > cap),
        n_dist=jnp.asarray(0, jnp.int32),
        seen_bits=bits,
    )


def _greedy_step_reference(points, graph: Graph, q, r, cap: int,
                           scfg: SearchConfig, gs: GreedyState,
                           exact_bits: bool = False) -> GreedyState:
    """Single-node greedy step (``expand_width=1``): the pre-fusion dataflow,
    kept as the baseline the fused path is measured against.

    Membership testing has a fast path: when the discovery bitset is
    *exact* (one bit per corpus node — ``bitset_exact``), probing
    ``seen_bits`` is semantically identical to the original O(R * cap)
    broadcast against the result buffer, because ``_greedy_init`` seeds the
    bitset with exactly the buffer's members and this step mirrors every
    append into it. (Cap-dropped neighbors are marked too; re-encountering
    one under the broadcast would re-count it as "new" and re-drop it —
    same buffer, count, and overflow flag either way, since the buffer only
    grows. Verified by the E=1-vs-fused parity test in tests/test_oracle.py,
    which pins the two dataflows to identical result sets on both f32 and
    quantized corpora.) In the *hashed* regime distinct ids share buckets,
    where a probe could report false membership — there the reference keeps
    the paper-faithful broadcast, so ``expand_width=1`` stays a valid
    baseline at every corpus scale."""
    node = gs.res_ids[gs.expand_ptr]
    nbrs = graph.out_neighbors(node)  # (R,)
    nd = gather_dist(points, nbrs, q, scfg.metric)
    rr = jnp.arange(nbrs.shape[0])
    dup_in_row = jnp.any(
        (nbrs[:, None] == nbrs[None, :]) & (rr[None, :] < rr[:, None]) & (nbrs[:, None] != INVALID_ID),
        axis=1,
    )
    if exact_bits:
        seen = bitset_contains(gs.seen_bits,
                               jnp.where(nbrs != INVALID_ID, nbrs, 0))
    else:
        seen = jnp.any((nbrs[:, None] == gs.res_ids[None, :]) & (nbrs[:, None] != INVALID_ID), axis=1)
    new = (nd <= r) & (~dup_in_row) & (~seen) & (nbrs != INVALID_ID)
    pos = gs.res_count + jnp.cumsum(new.astype(jnp.int32)) - 1
    write_pos = jnp.where(new & (pos < cap), pos, cap)  # cap == OOB -> dropped
    res_ids = gs.res_ids.at[write_pos].set(nbrs, mode="drop")
    res_dists = gs.res_dists.at[write_pos].set(nd, mode="drop")
    n_new = jnp.sum(new.astype(jnp.int32))
    return GreedyState(
        res_ids=res_ids,
        res_dists=res_dists,
        res_count=jnp.minimum(gs.res_count + n_new, cap),
        expand_ptr=gs.expand_ptr + 1,
        rounds=gs.rounds + 1,
        overflow=gs.overflow | (gs.res_count + n_new > cap),
        n_dist=gs.n_dist + jnp.sum(nbrs != INVALID_ID).astype(jnp.int32),
        seen_bits=bitset_add(gs.seen_bits, nbrs, new) if exact_bits
        else gs.seen_bits,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _PackedGreedyState:
    """Loop carry of the fused (E >= 2) greedy phase. ``res`` packs
    ``[id, uint32-distance-key]`` per row so the append is ONE bounded
    scatter instead of two (XLA scatter cost is per-update overhead, not
    bytes — the two-buffer form profiled as ~40% of the greedy loop; a
    batched ``dynamic_update_slice`` window write was also tried and lost,
    since a per-lane start index turns DUS into a whole-buffer scatter
    under vmap). Unpacked into ``GreedyState`` after the loop."""

    res: jnp.ndarray        # (K, 2) int32 — [id, dist key (bitcast)]
    res_count: jnp.ndarray  # () int32
    expand_ptr: jnp.ndarray # () int32
    rounds: jnp.ndarray     # () int32
    overflow: jnp.ndarray   # () bool
    n_dist: jnp.ndarray     # () int32
    seen_bits: jnp.ndarray  # (W,) uint32


def _pack_greedy(gs: GreedyState) -> _PackedGreedyState:
    key = jax.lax.bitcast_convert_type(_f32_ascending_key(gs.res_dists),
                                       jnp.int32)
    return _PackedGreedyState(
        res=jnp.stack([gs.res_ids, key], axis=1),
        res_count=gs.res_count, expand_ptr=gs.expand_ptr, rounds=gs.rounds,
        overflow=gs.overflow, n_dist=gs.n_dist, seen_bits=gs.seen_bits)


def _unpack_greedy(ps: _PackedGreedyState) -> GreedyState:
    return GreedyState(
        res_ids=ps.res[:, 0],
        res_dists=_f32_from_key(
            jax.lax.bitcast_convert_type(ps.res[:, 1], jnp.uint32)),
        res_count=ps.res_count, expand_ptr=ps.expand_ptr, rounds=ps.rounds,
        overflow=ps.overflow, n_dist=ps.n_dist, seen_bits=ps.seen_bits)


def _greedy_step(points, graph: Graph, q, r, cap: int, scfg: SearchConfig,
                 gs: _PackedGreedyState, packed=None) -> _PackedGreedyState:
    """Expand the next E result-buffer entries through the fused expand path
    (same kernel as phase 1), appending fresh in-range neighbors.

    The membership probe is the bitset — the reference path's O(R * cap)
    result-buffer broadcast is the dominant cost this replaces."""
    E = scfg.eff_expand_width
    lane = jnp.arange(E)
    e_cnt = jnp.minimum(jnp.asarray(E, jnp.int32), gs.res_count - gs.expand_ptr)
    lane_ok = lane < e_cnt
    ridx = jnp.minimum(gs.expand_ptr + lane, cap - 1)
    nodes = jnp.where(lane_ok, jnp.take(gs.res[:, 0], ridx), INVALID_ID)

    nbr_ids, nd, nd_inc = _expand_tile(points, graph, nodes, q, scfg,
                                       packed)
    valid = nbr_ids != INVALID_ID
    seen = bitset_contains(gs.seen_bits, jnp.where(valid, nbr_ids, 0)) & valid
    new = valid & ~seen & (nd <= r)
    if not bitset_exact(corpus_size(points), gs.seen_bits.shape[0]):
        new = first_slot_occurrence(gs.seen_bits, nbr_ids, new)

    pos = gs.res_count + jnp.cumsum(new.astype(jnp.int32)) - 1
    write_pos = jnp.where(new & (pos < cap), pos, cap)  # cap == OOB -> dropped
    key = jax.lax.bitcast_convert_type(_f32_ascending_key(nd), jnp.int32)
    rows = jnp.stack([nbr_ids, key], axis=1)             # (T, 2)
    res = gs.res.at[write_pos].set(rows, mode="drop")
    n_new = jnp.sum(new.astype(jnp.int32))
    return _PackedGreedyState(
        res=res,
        res_count=jnp.minimum(gs.res_count + n_new, cap),
        expand_ptr=gs.expand_ptr + e_cnt,
        rounds=gs.rounds + e_cnt,
        overflow=gs.overflow | (gs.res_count + n_new > cap),
        n_dist=gs.n_dist + nd_inc,
        # mark every fresh in-range neighbor, including cap-dropped ones (the
        # buffer only ever grows, so a dropped node could never land later)
        seen_bits=bitset_add(gs.seen_bits, nbr_ids, new),
    )


def _greedy_run(points, graph: Graph, q, r, gs: GreedyState, cap: int,
                stop_at, scfg: SearchConfig, active) -> GreedyState:
    """Advance one lane's greedy continuation until its frontier is empty or
    ``gs.rounds`` reaches ``stop_at`` (a traced per-lane value). This is the
    loop shared by the run-to-completion path (``greedy_search``) and the
    checkpoint/resume path (``greedy_resume_batch``): the carry is the full
    ``GreedyState``, so stopping at round s and re-entering later replays
    exactly the same expansion sequence as one uninterrupted run."""
    n_corpus = corpus_size(points)
    num_words = bitset_num_words(n_corpus, scfg.bitset_cap_bits)
    exact_bits = bitset_exact(n_corpus, num_words)
    if not isinstance(active, jnp.ndarray):
        active = jnp.asarray(active)
    stop_at = jnp.asarray(stop_at, jnp.int32)

    def cond(g):
        return active & (g.expand_ptr < g.res_count) & (g.rounds < stop_at)

    if scfg.eff_expand_width == 1:  # paper-faithful single-node reference
        return jax.lax.while_loop(
            cond,
            lambda g: _greedy_step_reference(points, graph, q, r, cap, scfg, g,
                                             exact_bits),
            gs)
    packed = _kernel_operand(points, scfg)
    ps = jax.lax.while_loop(
        cond,
        lambda g: _greedy_step(points, graph, q, r, cap, scfg, g, packed),
        _pack_greedy(gs))
    return _unpack_greedy(ps)


@partial(jax.jit, static_argnames=("cap", "rounds", "scfg"))
def greedy_search(
    points, graph: Graph, q, r, st: BeamState,
    cap: int, rounds: int, scfg: SearchConfig, active: bool | jnp.ndarray = True,
) -> GreedyState:
    """Paper Alg. 2 from a finished beam state. ``active=False`` lanes no-op.

    ``r`` is this query's own radius — a python scalar or a () float array
    (the batched callers vmap a (Q,) radius vector down to one scalar per
    lane; nothing here assumes the batch shares a radius).

    ``rounds`` stays an *expansion* budget: each iteration advances
    ``expand_ptr`` by up to ``scfg.expand_width`` and charges that many
    rounds (the last iteration may overshoot by at most E - 1).
    """
    with jax.named_scope("range.phase2"):
        r = jnp.asarray(r, jnp.float32)
        n_corpus = corpus_size(points)
        num_words = bitset_num_words(n_corpus, scfg.bitset_cap_bits)
        exact_bits = bitset_exact(n_corpus, num_words)
        gs = _greedy_init(st, r, cap, num_words, exact_bits)
        gs = _greedy_run(points, graph, q, r, gs, cap, rounds, scfg, active)
        return dataclasses.replace(
            gs, overflow=gs.overflow | (gs.expand_ptr < gs.res_count))


# ---------------------------------------------------------------------------
# Checkpoint/resume greedy API (continuous-batching serving)
# ---------------------------------------------------------------------------
#
# ``GreedyState`` is a complete checkpoint of a lane's phase-2 search: the
# result buffer, expansion pointer, round counter, and discovery bitset
# together determine every future expansion. The pair below exposes that as
# a batched seed/advance surface so a serving scheduler can run phase 2 in
# bounded ``slice_rounds`` increments, rotating finished lanes out of the
# device batch while stragglers keep their state — the lane compaction of
# ``range_search_compacted`` generalized from one-shot to persistent.

@partial(jax.jit, static_argnames=("cap", "scfg"))
def greedy_seed_batch(corpus, st: BeamState, r, cap: int,
                      scfg: SearchConfig) -> GreedyState:
    """Checkpointable phase-2 seeds for a batch of finished beam states.

    Returns a batched ``GreedyState`` (one lane per query) identical to what
    ``greedy_search`` starts from; advance it with ``greedy_resume_batch``.
    """
    n_corpus = corpus_size(corpus)
    num_words = bitset_num_words(n_corpus, scfg.bitset_cap_bits)
    exact_bits = bitset_exact(n_corpus, num_words)
    rj = broadcast_radius(r, st.ids.shape[0])
    return jax.vmap(
        lambda st_, r_: _greedy_init(st_, r_, cap, num_words, exact_bits)
    )(st, rj)


@partial(jax.jit, static_argnames=("cap", "rounds", "slice_rounds", "scfg"))
def greedy_resume_batch(
    corpus, graph: Graph, queries: jnp.ndarray, r, gs: GreedyState,
    active: jnp.ndarray, cap: int, rounds: int, slice_rounds: int,
    scfg: SearchConfig,
) -> GreedyState:
    """Advance checkpointed greedy lanes by up to ``slice_rounds`` expansions.

    Each lane stops early when its frontier empties (``expand_ptr`` catches
    ``res_count``) or its lifetime budget ``rounds`` is spent; ``active``
    masks free scheduler slots to no-ops. Because the carry is the complete
    lane checkpoint, N resume calls compose to exactly one long
    ``greedy_search`` — slicing changes latency, never results. The final
    budget-exhausted overflow bit is NOT set here (a paused lane is not an
    overflowed one); callers apply it at retirement, see
    ``greedy_lane_done``."""
    rj = broadcast_radius(r, queries.shape[0])

    def one(q_, r_, g_, a_):
        stop_at = jnp.minimum(g_.rounds + slice_rounds, rounds)
        return _greedy_run(corpus, graph, q_, r_, g_, cap, stop_at, scfg, a_)

    with jax.named_scope("range.phase2"):
        return jax.vmap(one)(queries, rj, gs, active)


def greedy_lane_done(gs: GreedyState, rounds: int):
    """Host-side retirement test for resumed lanes.

    Returns ``(done, overflow)`` bool arrays: a lane is done when its
    frontier is exhausted or its lifetime expansion budget is spent; the
    overflow term matches ``greedy_search``'s end-of-run
    ``expand_ptr < res_count`` bit so sliced execution retires with the
    same flags as the one-shot path."""
    ptr = np.asarray(gs.expand_ptr)
    cnt = np.asarray(gs.res_count)
    done = _lane_done(ptr, cnt, np.asarray(gs.rounds), rounds)
    return done, np.asarray(gs.overflow) | (done & (ptr < cnt))


def _lane_done(ptr, cnt, rds, rounds: int) -> np.ndarray:
    """Frontier exhausted or lifetime expansion budget spent."""
    return (ptr >= cnt) | (rds >= rounds)


def greedy_coverage(gs: GreedyState) -> np.ndarray:
    """Visited-frontier fraction per lane: ``expand_ptr / res_count``,
    clamped to [0, 1]. A deadline-truncated lane reports how much of its
    *discovered* result frontier it had expanded when finalized — the
    coverage estimate a certified-partial ``Response`` carries. A lane
    with an empty result set (or one never truncated) reports 1.0."""
    ptr = np.asarray(gs.expand_ptr, np.float64)
    cnt = np.asarray(gs.res_count, np.float64)
    return np.where(cnt > 0, np.minimum(ptr / np.maximum(cnt, 1.0), 1.0), 1.0)


# ---------------------------------------------------------------------------
# Result extraction
# ---------------------------------------------------------------------------

def _beam_results(st: BeamState, r, cap: int):
    """Paper baseline/doubling answer: in-range entries of the active beam."""
    pos = jnp.arange(st.ids.shape[0])
    ok = (st.dists <= r) & (st.ids != INVALID_ID) & (pos < st.active_width)
    dists = jnp.where(ok, st.dists, jnp.inf)
    ids = jnp.where(ok, st.ids, INVALID_ID)
    dists, ids = jax.lax.sort((dists, ids), num_keys=1, is_stable=True)
    k = min(cap, ids.shape[0])
    out_ids = jnp.full((cap,), INVALID_ID, jnp.int32).at[:k].set(ids[:k])
    out_dists = jnp.full((cap,), jnp.inf, jnp.float32).at[:k].set(dists[:k])
    count = jnp.minimum(jnp.sum(ok), cap).astype(jnp.int32)
    return out_ids, out_dists, count, jnp.sum(ok) > cap


def _needs_phase2(st: BeamState, r, lam: float) -> jnp.ndarray:
    """Paper Alg. 6 trigger: the size-b beam is λ-saturated with results."""
    thresh = jnp.ceil(lam * st.active_width.astype(jnp.float32)).astype(jnp.int32)
    return in_range_count(st, r) >= jnp.maximum(thresh, 1)


# ---------------------------------------------------------------------------
# Tombstone filtering (live indices — repro.live)
# ---------------------------------------------------------------------------
#
# Lazy deletes are a packed bitset over corpus slots. Deleted nodes keep
# their vectors and their edges, so the traversal routes THROUGH them
# exactly as before (phase-1 beam, widening triggers, and the greedy
# expansion frontier are all computed on the unfiltered sets — a tombstone
# never perturbs the walk); only at the result stage are dead candidates
# dropped. Applied BEFORE the quantized rerank so the exact pass never
# wastes gathers on dead candidates.

def _drop_dead_lane(tombstones: jnp.ndarray, ids: jnp.ndarray,
                    dists: jnp.ndarray):
    """Drop tombstoned ids from one query's result buffer (stable
    left-compaction, one bounded scatter — same shape as ``_rerank_lane``)."""
    k = ids.shape[0]
    valid = ids != INVALID_ID
    dead = bitset_contains(tombstones, jnp.where(valid, ids, 0)) & valid
    keep = valid & ~dead
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    wp = jnp.where(keep, pos, k)                                  # k == dropped
    out_ids = jnp.full((k,), INVALID_ID, jnp.int32).at[wp].set(ids, mode="drop")
    out_d = jnp.full((k,), jnp.inf, jnp.float32).at[wp].set(dists, mode="drop")
    return out_ids, out_d, jnp.sum(keep.astype(jnp.int32))


@jax.jit
def filter_tombstoned(tombstones: jnp.ndarray, res: RangeResult) -> RangeResult:
    """Remove tombstoned ids from a batched ``RangeResult`` and recount.

    ``tombstones`` is a packed ``(W,) uint32`` bitset over corpus slots
    (``core.bitset``); it must be EXACT (one bit per slot — the live index
    sizes it off its fixed capacity), since a false-positive probe here
    would silently drop a live result. ``overflow`` is left as-is: it
    reports buffer pressure during the search, where dead candidates
    legitimately occupied slots."""
    fn = lambda i_, d_: _drop_dead_lane(tombstones, i_, d_)
    ids, dists, count = jax.vmap(fn)(res.ids, res.dists)
    return dataclasses.replace(res, ids=ids, dists=dists, count=count)


# ---------------------------------------------------------------------------
# Label-predicate filtering (filtered range retrieval — core.labels)
# ---------------------------------------------------------------------------
#
# The per-query label predicate follows the tombstone template exactly:
# points failing the predicate keep their vectors and edges, so the
# traversal routes THROUGH them unchanged (phase-1 beam, λ-saturation
# triggers, and the greedy frontier all run on the unfiltered sets — a
# filtered-out point never perturbs the walk or its early-stop/termination
# heuristics); only at the result stage are unmatched candidates dropped
# and counts recomputed. That placement is what makes the oracle
# guarantees hold: an all-pass predicate is bitwise-identical to no
# predicate, and the filtered result equals the brute-force oracle
# post-filter wherever the unfiltered walk recovers the full radius ball.

def _drop_unmatched_lane(labels: jnp.ndarray, mask: jnp.ndarray, is_and,
                         ids: jnp.ndarray, dists: jnp.ndarray):
    """Drop predicate-failing ids from one query's result buffer (stable
    left-compaction, one bounded scatter — the ``_drop_dead_lane`` shape)."""
    k = ids.shape[0]
    valid = ids != INVALID_ID
    rows = jnp.take(labels, jnp.where(valid, ids, 0), axis=0)     # (K, W)
    keep = valid & labels_match(rows, mask, is_and)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    wp = jnp.where(keep, pos, k)                                  # k == dropped
    out_ids = jnp.full((k,), INVALID_ID, jnp.int32).at[wp].set(ids, mode="drop")
    out_d = jnp.full((k,), jnp.inf, jnp.float32).at[wp].set(dists, mode="drop")
    return out_ids, out_d, jnp.sum(keep.astype(jnp.int32))


@jax.jit
def filter_labeled(labels: jnp.ndarray, filt: LabelFilter,
                   res: RangeResult) -> RangeResult:
    """Drop results failing each lane's label predicate and recount.

    ``labels`` is the ``(N, W)`` uint32 per-point label rows
    (``core.labels.pack_labels``); ``filt`` the batched per-lane predicate.
    ``overflow`` is left as-is, mirroring the tombstone drop (buffer
    pressure happened during the search, where unmatched candidates
    legitimately occupied slots)."""
    fn = lambda m_, a_, i_, d_: _drop_unmatched_lane(labels, m_, a_, i_, d_)
    ids, dists, count = jax.vmap(fn)(filt.masks, filt.is_and,
                                     res.ids, res.dists)
    return dataclasses.replace(res, ids=ids, dists=dists, count=count)


# ---------------------------------------------------------------------------
# Quantized-corpus two-pass: certified-lower-bound search + boundary rerank
# ---------------------------------------------------------------------------
#
# The quantized distance paths return certified LOWER bounds of the true
# distances (core.corpus), so the search loop's plain ``dist <= r`` tests
# already keep a provable per-candidate superset at the caller's radius —
# no radius plumbing. The stage below recovers each kept candidate's upper
# bound: ``ub <= r`` proves membership, the rest are ambiguous and get one
# batched exact f32 gather.

def _rerank_lane(points: QuantizedCorpus, q, r, ids, dists, metric: str):
    """Exact-rerank one query's guard-band boundary.

    Kept candidates split by the recovered per-vector upper bound:
    ``ub <= r`` are provably in range and pass through untouched; the rest
    (the *ambiguous band*) get one batched f32 gather against the raw
    corpus and the exact test ``d <= r``. Survivors are stable-compacted to
    the front. Returns (ids, dists, count, n_ambiguous).
    """
    k = ids.shape[0]
    valid = ids != INVALID_ID
    safe = jnp.where(valid, ids, 0)
    ub = upper_bound_dists(points, safe, dists, q, metric)        # (K,)
    amb = valid & (ub > r)
    exact = gather_dist(points.raw, jnp.where(amb, ids, INVALID_ID), q, metric)
    keep = valid & jnp.where(amb, exact <= r, True)
    new_d = jnp.where(amb & keep, exact, dists)
    # stable left-compaction (one bounded scatter; positions are unique)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    wp = jnp.where(keep, pos, k)                                  # k == dropped
    out_ids = jnp.full((k,), INVALID_ID, jnp.int32).at[wp].set(ids, mode="drop")
    out_d = jnp.full((k,), jnp.inf, jnp.float32).at[wp].set(new_d, mode="drop")
    return (out_ids, out_d, jnp.sum(keep.astype(jnp.int32)),
            jnp.sum(amb.astype(jnp.int32)))


def _rerank_fused(points: QuantizedCorpus, queries, r: jnp.ndarray,
                  res: RangeResult, metric: str) -> RangeResult:
    """In-program rerank over the whole result buffer (the fused path has no
    host sync to compact through; the compacted QPS path reranks only the
    ambiguous (lane, slot) pairs — see ``_rerank_host``)."""
    fn = lambda q_, r_, i_, d_: _rerank_lane(points, q_, r_, i_, d_, metric)
    with jax.named_scope("range.rerank"):
        ids, dists, count, n_amb = jax.vmap(fn)(queries, r, res.ids,
                                                res.dists)
    return dataclasses.replace(
        res, ids=ids, dists=dists, count=count,
        n_dist=res.n_dist + n_amb, n_rerank=res.n_rerank + n_amb)


# ---------------------------------------------------------------------------
# Shared building blocks: phase 1, result-stage finalization
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def range_phase1(
    corpus, graph: Graph, queries: jnp.ndarray, start_ids: jnp.ndarray,
    r, cfg: RangeConfig, es_radius=None,
):
    """Phase 1 (uniform beam search) for a batch of queries.

    Returns ``(beam_state, beam_result, needs_phase2)``: the finished beam
    states (the seeds for ``greedy_seed_batch``), the beam-filtered
    ``RangeResult`` that answers lanes which stop here, and the per-lane
    λ-saturation mask (all-False for non-greedy modes). This is the uniform
    front half of ``range_search_compacted``, exposed so a continuous
    scheduler can admit new lanes mid-flight without re-running phase 1 for
    the whole device batch."""
    rj = broadcast_radius(r, queries.shape[0])
    with jax.named_scope("range.phase1"):
        st = beam_search_batch(corpus, graph, queries, start_ids, rj,
                               cfg.search, es_radius)
        ids, dists, count, over = jax.vmap(
            lambda st_, r_: _beam_results(st_, r_, cfg.result_cap))(st, rj)
        if cfg.mode == "greedy":
            need = jax.vmap(
                lambda st_, r_: _needs_phase2(st_, r_, cfg.lam))(st, rj)
        else:
            need = jnp.zeros_like(st.done)
    res = RangeResult(ids=ids, dists=dists, count=count, overflow=over,
                      n_visited=st.n_visited, n_dist=st.n_dist,
                      es_stopped=st.es_stopped, phase2=jnp.zeros_like(st.done),
                      n_rerank=jnp.zeros_like(st.n_visited))
    return st, res, need


@partial(jax.jit, static_argnames=("cfg",))
def finalize_results(corpus, queries: jnp.ndarray, r, res: RangeResult,
                     cfg: RangeConfig, tombstones=None, labels=None,
                     label_filter: Optional[LabelFilter] = None) -> RangeResult:
    """Result-stage post-processing shared by every execution path: the
    tombstone drop, then the label-predicate drop (both route the
    traversal through dropped nodes; results never include them), then the
    quantized guard-band exact rerank — in that order, so the exact pass
    never wastes gathers on candidates the filters already killed."""
    rj = broadcast_radius(r, queries.shape[0])
    if tombstones is not None:  # live index: drop dead results, keep routing
        res = filter_tombstoned(tombstones, res)
    if labels is not None and label_filter is not None:
        res = filter_labeled(labels, label_filter, res)
    if (isinstance(corpus, QuantizedCorpus) and cfg.rerank
            and corpus.raw is not None):
        res = _rerank_fused(corpus, queries, rj, res, cfg.search.metric)
    return res


# ---------------------------------------------------------------------------
# Fused single-program batch (used by dry-run lowering + single-dispatch serve)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",))
def _range_search_fused(
    corpus,                       # (N, d) array or QuantizedCorpus
    graph: Graph,
    queries: jnp.ndarray,
    start_ids: jnp.ndarray,
    r: jnp.ndarray,               # scalar or (Q,) per-query radii
    cfg: RangeConfig,
    es_radius: Optional[jnp.ndarray] = None,  # scalar or (Q,)
    tombstones: Optional[jnp.ndarray] = None,  # (W,) uint32 dead-slot bitset
    labels: Optional[jnp.ndarray] = None,      # (N, W) uint32 label rows
    label_filter: Optional[LabelFilter] = None,
) -> RangeResult:
    r = broadcast_radius(r, queries.shape[0])
    # a quantized corpus searches on certified lower-bound distances, so
    # these r-threshold tests keep a per-candidate superset at the caller's
    # radius; the rerank stage below trims the boundary band exactly
    st = beam_search_batch(corpus, graph, queries, start_ids, r, cfg.search, es_radius)
    zeros = jnp.zeros_like(st.n_visited)

    if cfg.mode in ("beam", "doubling"):
        ids, dists, count, over = jax.vmap(
            lambda st_, r_: _beam_results(st_, r_, cfg.result_cap))(st, r)
        phase2 = (st.active_width > cfg.search.beam) if cfg.mode == "doubling" else jnp.zeros_like(st.done)
        res = RangeResult(ids=ids, dists=dists, count=count, overflow=over,
                          n_visited=st.n_visited, n_dist=st.n_dist,
                          es_stopped=st.es_stopped, phase2=phase2,
                          n_rerank=zeros)
    else:
        # greedy: phase 2 only for saturated lanes (masked, not compacted)
        active = jax.vmap(lambda st_, r_: _needs_phase2(st_, r_, cfg.lam))(st, r)
        gfn = lambda q_, r_, st_, a_: greedy_search(
            corpus, graph, q_, r_, st_, cfg.result_cap, cfg.frontier_rounds, cfg.search, a_
        )
        gs = jax.vmap(gfn)(queries, r, st, active)
        b_ids, b_dists, b_count, b_over = jax.vmap(
            lambda st_, r_: _beam_results(st_, r_, cfg.result_cap))(st, r)
        ids = jnp.where(active[:, None], gs.res_ids, b_ids)
        dists = jnp.where(active[:, None], gs.res_dists, b_dists)
        count = jnp.where(active, gs.res_count, b_count)
        over = jnp.where(active, gs.overflow, b_over)
        res = RangeResult(ids=ids, dists=dists, count=count, overflow=over,
                          n_visited=st.n_visited, n_dist=st.n_dist + jnp.where(active, gs.n_dist, 0),
                          es_stopped=st.es_stopped, phase2=active,
                          n_rerank=zeros)
    return finalize_results(corpus, queries, r, res, cfg, tombstones,
                            labels, label_filter)


# ---------------------------------------------------------------------------
# Two-phase pipeline with host-side query compaction (the QPS path)
# ---------------------------------------------------------------------------

def _tier_of(points):
    """The `TieredCorpus` wrapper, if ``points`` is one (duck-typed on the
    ``is_tiered`` marker — core never imports `repro.tier`)."""
    return points if getattr(points, "is_tiered", False) else None


def _exact_pairs_for(points, queries, ids_p, lanes_p, metric: str,
                     n_real=None):
    """Exact f32 pair distances for any exact-capable corpus view: resident
    raw rows go through `_exact_pairs`; a tiered corpus plans + fetches its
    host rows (`TieredCorpus.exact_pairs` — bit-identical by contract).
    ``n_real`` bounds the fetch planning to the unpadded pair prefix."""
    tier = _tier_of(points)
    if tier is not None:
        return tier.exact_pairs(queries, ids_p, lanes_p, metric,
                                n_real=n_real)
    raw = points.raw if isinstance(points, QuantizedCorpus) else points
    return _exact_pairs(raw, queries, ids_p, lanes_p, metric)


@partial(jax.profiler.annotate_function, name="range.rerank")
def _maybe_rerank_host(points, queries, rj: jnp.ndarray,
                       res: RangeResult, cfg: RangeConfig) -> RangeResult:
    """Host-compacted boundary rerank for the QPS path.

    The ambiguous band is collected as flat (lane, slot) pairs across the
    whole batch and padded to the next power of two, so the exact pass is
    ONE batched f32 gather whose size tracks the actual band population
    (O(log) compiled variants) — zero-band batches pay a single vectorized
    threshold test and no gather at all. A tiered corpus serves the gather
    from its host row store (dedup + cache + bucketed prefetch) with the
    same bits.
    """
    tier = _tier_of(points)
    qc = tier.device if tier is not None else points
    if not (isinstance(qc, QuantizedCorpus) and cfg.rerank
            and (tier is not None or qc.raw is not None)):
        return res
    metric = cfg.search.metric
    ids = np.array(jax.device_get(res.ids))
    dists = np.array(jax.device_get(res.dists))
    valid = ids != INVALID_ID
    safe = np.where(valid, ids, 0)
    ub = np.asarray(jax.vmap(
        lambda i_, d_, q_: upper_bound_dists(qc, i_, d_, q_, metric))(
            jnp.asarray(safe), jnp.asarray(dists), queries))
    amb = valid & (ub > np.asarray(rj)[:, None])
    n_rerank = amb.sum(axis=1).astype(np.int32)
    if not amb.any():
        return res
    lanes_p, slots_p = np.nonzero(amb)
    bucket = next_pow2(len(lanes_p))
    pad = bucket - len(lanes_p)
    ids_p = np.concatenate([ids[lanes_p, slots_p],
                            np.zeros(pad, np.int32)])
    lanes_pp = np.concatenate([lanes_p, np.zeros(pad, lanes_p.dtype)])
    exact_p = np.asarray(_exact_pairs_for(points, queries,
                                          jnp.asarray(ids_p, jnp.int32),
                                          jnp.asarray(lanes_pp, jnp.int32),
                                          metric, n_real=len(lanes_p)))
    rnp = np.asarray(rj)
    exact = np.full(ids.shape, np.inf, np.float32)
    exact[lanes_p, slots_p] = exact_p[:len(lanes_p)]
    keep = valid & np.where(amb, exact <= rnp[:, None], True)
    new_d = np.where(amb & keep, exact, dists)
    # stable left-compaction of the survivors, vectorized over lanes
    order = np.argsort(~keep, axis=1, kind="stable")
    out_ids = np.take_along_axis(np.where(keep, ids, INVALID_ID), order, axis=1)
    out_d = np.take_along_axis(np.where(keep, new_d, np.inf), order, axis=1)
    return dataclasses.replace(
        res,
        ids=jnp.asarray(out_ids),
        dists=jnp.asarray(out_d),
        count=jnp.asarray(keep.sum(axis=1).astype(np.int32)),
        n_dist=res.n_dist + jnp.asarray(n_rerank),
        n_rerank=res.n_rerank + jnp.asarray(n_rerank))


@partial(jax.jit, static_argnames=("metric",))
def _exact_pairs(raw, queries, ids_p, lanes_p, metric: str):
    """Exact f32 distances for flat (corpus id, query lane) pairs."""
    with jax.named_scope("range.rerank"):
        vecs = jnp.take(raw, ids_p, axis=0).astype(jnp.float32)
        qv = jnp.take(queries, lanes_p, axis=0).astype(jnp.float32)
        return point_dist(vecs, qv, metric)


# Lane expansions at which the compacted path's greedy phase 2 pauses its
# lockstep walk and re-packs the still-live lanes into the smallest pow2
# bucket that holds them, so finished lanes stop riding the straggler's
# loop (a vmapped while_loop runs its body on every lane of the bucket).
# Past the last end, or once the bucket is one lane, the rest runs to
# completion in one call. Per-lane rounds are heavy-tailed: most lanes stop
# within a few hundred expansions, one per batch may run the whole budget;
# ends that double from 128 served bigann-int8 faster on a v5e than ends
# every 256 expansions or ends doubling from 256.
P2_SLICE_ENDS = (128, 256, 512, 1024)


@jax.jit
def _retire_lanes(out, gs: GreedyState, rows, keep, carry):
    """One program per phase-2 slice boundary: write every lane of the
    bucket, with ``greedy_search``'s end-of-run overflow bit, into the
    output (a row per lane of the first bucket) at ``rows`` (out of range:
    dropped; ``out=None`` starts the output from the first bucket), then
    gather lanes ``keep`` of ``(gs, carry)`` into the next bucket
    (``keep=None`` on the last call). A lane still live is written again
    later, so each row ends with its lane's final state."""
    fin = (gs.res_ids, gs.res_dists, gs.res_count,
           gs.overflow | (gs.expand_ptr < gs.res_count), gs.n_dist,
           gs.rounds)
    if out is not None:
        fin = tuple(o.at[rows].set(f, mode="drop") for o, f in zip(out, fin))
    if keep is None:
        return fin, None
    return fin, jax.tree.map(lambda x: x[keep], (gs, carry))


def _greedy_sliced(points, graph: Graph, qs, rs, st: BeamState,
                   n_active: int, cfg: RangeConfig):
    """Greedy phase 2 over a pow2 bucket of compacted lanes (the first
    ``n_active`` carry requests, the rest are inactive padding), run in
    slices that end at ``P2_SLICE_ENDS``.

    After each slice one fetch of the per-lane ``(expand_ptr, res_count,
    rounds)`` tells which lanes are done; where the live ones fit a smaller
    pow2 bucket, ``_retire_lanes`` moves them there. The carry is the whole
    lane checkpoint (``greedy_resume_batch``), so the answers equal one
    vmapped ``greedy_search`` over the bucket, bit for bit.

    Returns the device-resident ``(ids, dists, count, overflow, n_dist,
    rounds)`` of the bucket's rows, the lane-rounds dispatched (over the
    slices, bucket x the slowest lane's advance) and each slice's bucket.
    """
    cap, budget, scfg = cfg.result_cap, cfg.frontier_rounds, cfg.search
    width = qs.shape[0]
    gs = greedy_seed_batch(points, st, rs, cap, scfg)
    rows = np.where(np.arange(width) < n_active, np.arange(width),
                    width).astype(np.int32)
    on = rows < width
    before = np.zeros(width, np.int32)
    out, slot_rounds, buckets, start = None, 0, [], 0
    for end in P2_SLICE_ENDS + (budget,):
        b = len(rows)
        to_end = b == 1 or end >= budget
        gs = greedy_resume_batch(points, graph, qs, rs, gs, jnp.asarray(on),
                                 cap, budget, budget if to_end else end - start,
                                 scfg)
        ptr, cnt, rds = jax.device_get((gs.expand_ptr, gs.res_count,
                                        gs.rounds))
        slot_rounds += b * int((rds - before).max())
        buckets.append(b)
        live = on & ~_lane_done(ptr, cnt, rds, budget)
        n_live = int(live.sum())
        if to_end or not n_live:
            break
        start, before = end, rds
        if next_pow2(n_live) == b:  # cannot shrink: go on in place
            continue
        keep = np.nonzero(live)[0].astype(np.int32)
        keep = np.concatenate(
            [keep, np.full(next_pow2(n_live) - n_live, keep[0], np.int32)])
        out, (gs, (qs, rs)) = _retire_lanes(out, gs, rows, keep, (qs, rs))
        on = np.arange(len(keep)) < n_live
        rows = np.where(on, rows[keep], width).astype(np.int32)
        before = rds[keep]
    out, _ = _retire_lanes(out, gs, rows, None, None)
    return out, slot_rounds, buckets


def _walk_compacted(
    corpus,               # (N, d) array or QuantizedCorpus
    graph: Graph,
    queries: jnp.ndarray,
    start_ids: jnp.ndarray,  # shared (S,) or per-lane (Q, S')
    r,                    # scalar or (Q,) per-query radii
    cfg: RangeConfig,
    es_radius=None,       # scalar or (Q,)
    tombstones=None,      # (W,) uint32 dead-slot bitset (live indices)
    labels=None,          # (N, W) uint32 per-point label rows
    label_filter: Optional[LabelFilter] = None,
) -> RangeResult:
    # a tiered corpus walks on its device arm (codes + meta only); the
    # host-fetched rerank in finish() sees the full tier
    tier = _tier_of(corpus)
    points = tier.device if tier is not None else corpus
    rj = broadcast_radius(r, queries.shape[0])

    def finish(res: RangeResult) -> RangeResult:
        # result-stage tombstone + label-predicate drops (traversal above
        # ran unfiltered), then the quantized boundary rerank on survivors
        if tombstones is not None:
            res = filter_tombstoned(tombstones, res)
        if labels is not None and label_filter is not None:
            res = filter_labeled(labels, label_filter, res)
        return _maybe_rerank_host(corpus, queries, rj, res, cfg)

    esj = None if es_radius is None else broadcast_radius(es_radius, queries.shape[0])
    # phase 1 runs at the BASE beam for every mode (for doubling this is the
    # §Perf iteration C3 change: in-place widening inside the batched while
    # made every lane wait for the widest one — a 10x QPS straggler penalty;
    # the paper's restart-style doubling now runs on the compacted survivors
    # only, like greedy). A quantized corpus searches on certified
    # lower-bound distances (superset at rj); _maybe_rerank_host trims the
    # boundary band exactly.
    p1_search = cfg.search if cfg.mode != "doubling" else dataclasses.replace(
        cfg.search, max_beam=cfg.search.beam,
        visit_cap=min(cfg.search.visit_cap, 4 * cfg.search.beam))
    with jax.profiler.TraceAnnotation("range.phase1"):
        st = beam_search_batch(points, graph, queries, start_ids, rj,
                               p1_search, esj)
    greedy = cfg.mode == "greedy"
    with jax.profiler.TraceAnnotation("range.compact") as span:
        b_ids, b_dists, b_count, b_over = jax.vmap(
            lambda st_, r_: _beam_results(st_, r_, cfg.result_cap))(st, rj)
        base = RangeResult(
            ids=b_ids, dists=b_dists, count=b_count, overflow=b_over,
            n_visited=st.n_visited, n_dist=st.n_dist,
            es_stopped=st.es_stopped, phase2=jnp.zeros_like(st.done),
            n_rerank=jnp.zeros_like(st.n_visited),
            p2_rounds=(np.full(queries.shape[0], -1, np.int32) if greedy
                       else None),
            p2_slot_rounds=0 if greedy else None,
            p2_slices=0 if greedy else None)
        n_active = bucket = 0
        if cfg.mode != "beam":
            active = np.asarray(jax.vmap(
                lambda st_, r_: _needs_phase2(st_, r_, cfg.lam))(st, rj))
            n_active = int(active.sum())
        if n_active:
            sel = np.nonzero(active)[0]
            bucket = next_pow2(n_active)
            pad = np.concatenate(
                [sel, np.full(bucket - n_active, sel[0], dtype=sel.dtype)])
            sub_q = queries[pad]
            sub_r = rj[pad]
            sub_es = None if esj is None else esj[pad]
            if greedy:
                sub_st = jax.tree.map(lambda x: x[pad], st)
            else:  # per-lane starts subset with their lanes
                sub_starts = start_ids if start_ids.ndim == 1 else start_ids[pad]
        span.set_metadata(active=n_active, bucket=bucket)
    if n_active == 0:
        return finish(base)

    with jax.profiler.TraceAnnotation("range.phase2") as span:
        if greedy:
            sub, slot_rounds, buckets = _greedy_sliced(
                points, graph, sub_q, sub_r, sub_st, n_active, cfg)
            # "64-16-8": a comma would split the trace event's arguments
            span.set_metadata(slices=len(buckets),
                              buckets="-".join(map(str, buckets)))
        else:
            # restart with widening enabled, survivors only (paper Alg. 5),
            # each at its own radius
            st2 = beam_search_batch(points, graph, sub_q, sub_starts, sub_r,
                                    cfg.search, sub_es)
            d_ids, d_dists, d_count, d_over = jax.vmap(
                lambda st_, r_: _beam_results(st_, r_, cfg.result_cap))(
                    st2, sub_r)
            sub = (d_ids, d_dists, d_count, d_over, st2.n_dist, None)

    with jax.profiler.TraceAnnotation("range.merge"):
        # one batched transfer for everything the host-side merge needs (the
        # per-leaf np.array() calls each synced the device separately)
        (ids, dists, count, over, ndist, s_ids, s_dists, s_count, s_over,
         s_nd, s_rounds) = jax.device_get(
            (base.ids, base.dists, base.count, base.overflow,
             base.n_dist) + sub)
        ids, dists, count, over, ndist = (
            np.array(ids), np.array(dists), np.array(count), np.array(over),
            np.array(ndist))  # device_get leaves may be read-only views
        ids[sel] = s_ids[:n_active]
        dists[sel] = s_dists[:n_active]
        count[sel] = s_count[:n_active]
        over[sel] = s_over[:n_active]
        ndist[sel] += s_nd[:n_active]
        p2 = {}
        if greedy:
            base.p2_rounds[sel] = s_rounds[:n_active]
            p2 = dict(p2_rounds=base.p2_rounds, p2_slot_rounds=slot_rounds,
                      p2_slices=len(buckets))
        merged = RangeResult(
            ids=jnp.asarray(ids), dists=jnp.asarray(dists),
            count=jnp.asarray(count), overflow=jnp.asarray(over),
            n_visited=base.n_visited, n_dist=jnp.asarray(ndist),
            es_stopped=base.es_stopped, phase2=jnp.asarray(active),
            n_rerank=jnp.zeros_like(base.n_visited), **p2)
    return finish(merged)


# Below this fraction of the corpus, a filtered walk lane gets its default
# entry points augmented with members of its own posting list (the beam
# then starts inside the predicate's region instead of routing to it).
# Lanes at or above it keep the shared defaults untouched, so broad and
# all-pass predicates stay bitwise-identical to the unfiltered program.
ENTRY_SEED_FRAC = 0.25


def _fallback_scan(points, queries, rj_np, tombstones, match, fb_sel,
                   cap: int, metric: str):
    """Brute exact scan of each fallback lane's posting list.

    ``points`` is any exact-capable corpus view (raw array, quantized
    corpus with raw rows, or tiered corpus), ``match`` the host (Q, N)
    predicate matrix, ``fb_sel`` the lanes taking this path. All posting
    lists flatten into one pow2-padded ``_exact_pairs`` call (O(log)
    compiled variants, like the rerank band), then each lane keeps
    ``d <= r`` survivors sorted ascending — exactly the oracle's
    post-filtered answer, by construction. Tombstoned ids are excluded
    up front so the scan matches the walk's result-stage semantics."""
    m = len(fb_sel)
    out_ids = np.full((m, cap), INVALID_ID, np.int32)
    out_d = np.full((m, cap), np.inf, np.float32)
    count = np.zeros(m, np.int32)
    over = np.zeros(m, bool)
    ndist = np.zeros(m, np.int32)
    tomb = None if tombstones is None else np.asarray(tombstones)
    per_ids = []
    for j, lane in enumerate(fb_sel):
        pid = np.nonzero(match[lane])[0].astype(np.int32)
        if tomb is not None and pid.size:
            live = ((tomb[pid // 32] >> (pid % 32)) & np.uint32(1)) == 0
            pid = pid[live]
        per_ids.append(pid)
        ndist[j] = pid.size
    total = int(sum(p.size for p in per_ids))
    if total == 0:
        return out_ids, out_d, count, over, ndist
    lanes_p = np.concatenate([np.full(p.size, lane, np.int32)
                              for p, lane in zip(per_ids, fb_sel)])
    ids_p = np.concatenate(per_ids)
    bucket = next_pow2(total)
    pad = bucket - total
    d = np.asarray(_exact_pairs_for(
        points, queries,
        jnp.asarray(np.concatenate([ids_p, np.zeros(pad, np.int32)])),
        jnp.asarray(np.concatenate([lanes_p, np.zeros(pad, np.int32)])),
        metric, n_real=total))[:total]
    off = 0
    for j, pid in enumerate(per_ids):
        dj = d[off:off + pid.size]
        off += pid.size
        keep = dj <= rj_np[fb_sel[j]]
        kid, kd = pid[keep], dj[keep]
        order = np.argsort(kd, kind="stable")
        kid, kd = kid[order], kd[order]
        k = min(kid.size, cap)
        out_ids[j, :k] = kid[:k]
        out_d[j, :k] = kd[:k]
        count[j] = k
        over[j] = kid.size > cap
    return out_ids, out_d, count, over, ndist


def _range_search_compacted(
    corpus,
    graph: Graph,
    queries: jnp.ndarray,
    start_ids: jnp.ndarray,
    r,
    cfg: RangeConfig,
    es_radius=None,
    tombstones=None,
    labels=None,
    label_filter: Optional[LabelFilter] = None,
) -> RangeResult:
    """Compacted-path front door: per-lane selectivity dispatch.

    Unfiltered batches go straight to the two-phase walk. Filtered batches
    first measure each lane's predicate selectivity (posting-list size /
    corpus size) on the host:

    * lanes below ``cfg.filter_threshold`` skip the graph entirely and
      brute-scan their posting list with the exact kernel
      (``_fallback_scan`` — FilterGraph's ``_threshold`` dispatch);
    * surviving walk lanes below ``ENTRY_SEED_FRAC`` get their entry
      points augmented with posting-list members (filter-aware entry
      selection) — broad/all-pass lanes keep the shared defaults;
    * one micro-batch freely mixes both paths; walk lanes are compacted
      and pow2-padded exactly like the phase-2 survivors.

    The fallback needs exact vectors (a ``QuantizedCorpus`` without
    ``raw`` walks every lane instead)."""
    if labels is None or label_filter is None:
        return _walk_compacted(corpus, graph, queries, start_ids, r, cfg,
                               es_radius, tombstones)
    n_q = queries.shape[0]
    rj = broadcast_radius(r, n_q)
    esj = (None if es_radius is None
           else broadcast_radius(es_radius, n_q))
    n_corpus = corpus_size(corpus)
    match = np.asarray(label_match_matrix(labels, label_filter))   # (Q, N)
    counts = match.sum(axis=1)
    if _tier_of(corpus) is not None:
        has_exact = True  # host store serves the fallback's exact scan
    else:
        has_exact = (corpus.raw is not None
                     if isinstance(corpus, QuantizedCorpus) else True)
    fb = (counts < cfg.filter_threshold * n_corpus
          if cfg.filter_threshold > 0.0 and has_exact
          else np.zeros(n_q, bool))

    # filter-aware entry points: selective walk lanes start inside their
    # predicate's region (deterministic evenly-spaced posting-list sample
    # appended to the defaults; INVALID padding and duplicate collapse in
    # init_state keep unseeded lanes bitwise-identical to shared starts)
    seed = (~fb) & (counts > 0) & (counts < ENTRY_SEED_FRAC * n_corpus)
    if seed.any():
        s0 = np.asarray(start_ids).astype(np.int32)
        n_seed = s0.shape[0]
        sm = np.concatenate(
            [np.broadcast_to(s0, (n_q, n_seed)),
             np.full((n_q, n_seed), INVALID_ID, np.int32)], axis=1).copy()
        for lane in np.nonzero(seed)[0]:
            pid = np.nonzero(match[lane])[0]
            pick = pid[np.linspace(0, pid.size - 1,
                                   min(n_seed, pid.size)).astype(np.int64)]
            sm[lane, n_seed:n_seed + pick.size] = pick
        walk_starts = jnp.asarray(sm)
    else:
        walk_starts = start_ids

    if not fb.any():
        return _walk_compacted(corpus, graph, queries, walk_starts, rj, cfg,
                               esj, tombstones, labels, label_filter)

    cap = cfg.result_cap
    fb_sel = np.nonzero(fb)[0]
    w_sel = np.nonzero(~fb)[0]
    f_ids, f_d, f_cnt, f_over, f_nd = _fallback_scan(
        corpus, queries, np.asarray(rj), tombstones, match, fb_sel, cap,
        cfg.search.metric)

    ids = np.full((n_q, cap), INVALID_ID, np.int32)
    dists = np.full((n_q, cap), np.inf, np.float32)
    count = np.zeros(n_q, np.int32)
    over = np.zeros(n_q, bool)
    nvis = np.zeros(n_q, np.int32)
    ndist = np.zeros(n_q, np.int32)
    ess = np.zeros(n_q, bool)
    ph2 = np.zeros(n_q, bool)
    nrr = np.zeros(n_q, np.int32)
    ids[fb_sel], dists[fb_sel], count[fb_sel] = f_ids, f_d, f_cnt
    over[fb_sel], ndist[fb_sel] = f_over, f_nd

    if w_sel.size:
        bucket = next_pow2(w_sel.size)
        padw = np.concatenate(
            [w_sel, np.full(bucket - w_sel.size, w_sel[0], w_sel.dtype)])
        sub_starts = (walk_starts if walk_starts.ndim == 1
                      else walk_starts[padw])
        sub_filter = LabelFilter(masks=label_filter.masks[padw],
                                 is_and=label_filter.is_and[padw])
        wres = _walk_compacted(
            corpus, graph, queries[padw], sub_starts, rj[padw], cfg,
            None if esj is None else esj[padw], tombstones, labels,
            sub_filter)
        (w_ids, w_d, w_cnt, w_over, w_nvis, w_nd, w_es, w_ph2,
         w_nrr) = jax.device_get(
            (wres.ids, wres.dists, wres.count, wres.overflow, wres.n_visited,
             wres.n_dist, wres.es_stopped, wres.phase2, wres.n_rerank))
        k = w_sel.size
        ids[w_sel], dists[w_sel], count[w_sel] = w_ids[:k], w_d[:k], w_cnt[:k]
        over[w_sel], nvis[w_sel], ndist[w_sel] = (w_over[:k], w_nvis[:k],
                                                  w_nd[:k])
        ess[w_sel], ph2[w_sel], nrr[w_sel] = w_es[:k], w_ph2[:k], w_nrr[:k]

    return RangeResult(
        ids=jnp.asarray(ids), dists=jnp.asarray(dists),
        count=jnp.asarray(count), overflow=jnp.asarray(over),
        n_visited=jnp.asarray(nvis), n_dist=jnp.asarray(ndist),
        es_stopped=jnp.asarray(ess), phase2=jnp.asarray(ph2),
        n_rerank=jnp.asarray(nrr))


# ---------------------------------------------------------------------------
# Public entry points — one keyword surface, shared parameter order
# ---------------------------------------------------------------------------
#
# The batch entry points share the parameter order
# ``(corpus, graph, queries, start_ids, r, cfg, es_radius, tombstones,
# labels, label_filter)`` and take everything by keyword
# (``dist.sharded_range_search`` prepends its mesh;
# ``engine.range``/``LiveSnapshot.range`` bind corpus/graph/start_ids/labels
# from the object and keep the same tail).

def range_search_fused(*, corpus, graph, queries, start_ids, r, cfg,
                       es_radius=None, tombstones=None, labels=None,
                       label_filter=None) -> RangeResult:
    """Single-XLA-program batched range search (no host sync): phase 1 plus
    masked (not compacted) greedy phase 2, tombstone + label-predicate
    filters, and in-program quantized rerank. Keyword-only; see the module
    note on the shared parameter order. ``r``/``es_radius`` are a scalar or
    per-query ``(Q,)`` radii; ``tombstones`` a packed ``(W,) uint32``
    dead-slot bitset; ``labels``/``label_filter`` the per-point label rows
    and batched predicate (``core.labels``). The fused program always
    walks — the selectivity fallback needs a host dispatch and lives on the
    compacted path. A tiered corpus runs the program on its device arm
    (raw=None skips the in-program rerank after the tombstone/label drops)
    and reranks through the host store afterwards — same filter→rerank
    order, same bits as the resident program."""
    tier = _tier_of(corpus)
    if tier is not None:
        res = _range_search_fused(corpus=tier.device, graph=graph,
                                  queries=queries, start_ids=start_ids, r=r,
                                  cfg=cfg, es_radius=es_radius,
                                  tombstones=tombstones, labels=labels,
                                  label_filter=label_filter)
        rj = broadcast_radius(r, queries.shape[0])
        return _maybe_rerank_host(corpus, queries, rj, res, cfg)
    return _range_search_fused(corpus=corpus, graph=graph, queries=queries,
                               start_ids=start_ids, r=r, cfg=cfg,
                               es_radius=es_radius, tombstones=tombstones,
                               labels=labels, label_filter=label_filter)


def range_search_compacted(*, corpus, graph, queries, start_ids, r, cfg,
                           es_radius=None, tombstones=None, labels=None,
                           label_filter=None) -> RangeResult:
    """Two-phase batched range search with host-side query compaction (the
    QPS path): phase 1 over the whole batch, phase 2 over the pow2-padded
    survivor subset only (O(log Q) compiled variants — lanes with zero
    results never enter the expensive loop), each survivor carrying its own
    radius. With ``labels``/``label_filter`` set, lanes whose predicate
    selectivity falls below ``cfg.filter_threshold`` brute-scan their
    posting list instead of walking (per-lane dispatch; one micro-batch
    mixes both paths) and selective walk lanes get filter-aware entry
    points. Keyword-only; see the module note on the shared parameter
    order."""
    return _range_search_compacted(corpus=corpus, graph=graph, queries=queries,
                                   start_ids=start_ids, r=r, cfg=cfg,
                                   es_radius=es_radius, tombstones=tombstones,
                                   labels=labels, label_filter=label_filter)
