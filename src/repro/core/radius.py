"""Radius-selection methodology (paper Sec. 3).

Given a corpus + query sample, sweep a radius grid, compute the
percent-captured curve (Fig. 3) and the match-size frequency distribution
(Fig. 4), score the *robustness* of each candidate radius (local slope of the
capture curve in log-space — flat == robust to perturbation), and select a
radius hitting a target match profile (most queries zero results, a few large
outliers — the Pareto shape real range workloads follow).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from .ground_truth import range_counts_at


@dataclasses.dataclass(frozen=True)
class RadiusProfile:
    radii: np.ndarray            # (G,) swept grid
    percent_captured: np.ndarray # (G,) mean fraction of DB inside the ball
    zero_frac: np.ndarray        # (G,) fraction of queries with 0 matches
    robustness: np.ndarray       # (G,) |d log10(captured) / d log10-ish step|, lower = more robust
    counts: np.ndarray           # (Q, G) per-query match counts


# Fig. 4 bucketing: 0, <=10, <=100, <=1e3, <=1e4, <=1e5
FIG4_BUCKETS = (0, 10, 100, 1_000, 10_000, 100_000)


def match_histogram(counts: np.ndarray) -> dict[str, int]:
    """Bucket per-query match counts exactly like the paper's Fig. 4 table.

    The terminal ``>1e5`` bucket catches heavy-tailed queries past the
    paper's last printed column, so the bucket sums always equal the number
    of queries (without it, a query with more than 1e5 matches silently
    vanished from the table)."""
    counts = np.asarray(counts)
    out = {"0": int((counts == 0).sum())}
    prev = 0
    for b in FIG4_BUCKETS[1:]:
        out[f"<=1e{int(np.log10(b))}"] = int(((counts > prev) & (counts <= b)).sum())
        prev = b
    out[f">1e{int(np.log10(FIG4_BUCKETS[-1]))}"] = int(
        (counts > FIG4_BUCKETS[-1]).sum())
    return out


def sweep(
    points,
    queries,
    radii,
    metric: str = "l2",
    block: int = 2048,
) -> RadiusProfile:
    radii = np.asarray(radii, np.float32)
    counts = np.asarray(range_counts_at(jnp.asarray(points), jnp.asarray(queries),
                                        jnp.asarray(radii), metric, block))
    n = points.shape[0]
    captured = counts.mean(axis=0) / n
    zero_frac = (counts == 0).mean(axis=0)
    # robustness: relative change of captured per grid step (flat == robust)
    eps = 1e-12
    lg = np.log10(np.maximum(captured, eps))
    # np.gradient needs >= 2 samples; a single-radius grid has no slope
    # information, so score it perfectly robust instead of crashing
    slope = np.abs(np.gradient(lg)) if lg.size >= 2 else np.zeros_like(lg)
    return RadiusProfile(radii=radii, percent_captured=captured,
                         zero_frac=zero_frac, robustness=slope, counts=counts)


# The grid's low edge never starts above the radius at which a query
# expects this many points in range.
GRID_LO_MATCHES = 50


def default_grid(points, queries, metric: str = "l2", num: int = 48) -> np.ndarray:
    """A grid spanning ~0% to ~100% capture, from a distance sample.

    The low edge is the 0.0005 quantile of the sampled query-to-point
    distances, or the ``GRID_LO_MATCHES / n`` quantile where that is lower.
    Up to n = 100,000 the two are the same. Above it the bare quantile
    would start the grid at more expected matches per query the larger the
    corpus (about 500 at 1M rows), above the zero-heavy regime
    ``select_radius`` aims for, and the selection would fall onto the
    capture plateau where whole clusters are in range."""
    pts = np.asarray(points)
    qs = np.asarray(queries)
    sample = pts[np.random.default_rng(0).choice(pts.shape[0], size=min(2048, pts.shape[0]), replace=False)]
    if metric == "l2":
        d = ((qs[:, None, :] - sample[None, : min(512, sample.shape[0]), :]) ** 2).sum(-1)
    else:
        d = -(qs @ sample[: min(512, sample.shape[0])].T)
    q_lo = min(0.0005, GRID_LO_MATCHES / pts.shape[0])
    lo, hi = np.quantile(d, q_lo), np.quantile(d, 0.9995)
    if metric == "l2":
        lo = max(lo, 1e-9)
        return np.geomspace(lo, hi, num).astype(np.float32)
    return np.linspace(lo, hi, num).astype(np.float32)


def select_radius(
    profile: RadiusProfile,
    target_zero_frac: float = 0.95,
    robustness_weight: float = 1.0,
) -> tuple[float, int]:
    """Pick the radius whose zero-result fraction is closest to target,
    penalized by capture-curve steepness (the paper's robustness criterion).

    Returns (radius, grid_index). Raises ``ValueError`` when no grid point
    is feasible (every radius yields zero matches for every query): an
    all-inf score would otherwise argmin to index 0 and silently bless a
    vacuous benchmark radius."""
    score = np.abs(profile.zero_frac - target_zero_frac) + robustness_weight * profile.robustness
    # require at least one query with a match, else the benchmark is vacuous
    feasible = profile.zero_frac < 1.0
    if not feasible.any():
        raise ValueError(
            "no feasible radius in the swept grid: every candidate yields "
            "zero matches for every query — widen the grid (default_grid) "
            "or check the corpus/query scales")
    score = np.where(feasible, score, np.inf)
    gi = int(np.argmin(score))
    return float(profile.radii[gi]), gi
