"""Frozen copy of the paper's Sec.-3 radius selection, on the host.

Sweep a grid of radii over the query sample, score each radius by how far
its zero-result fraction lies from the target plus how steep the capture
curve is there, and take the best. The grid is geometric for ``l2`` and
linear for ``ip``, whose distances ``-q.x`` are negative where points match.
Its low edge starts no higher than ``GRID_LO_MATCHES`` expected matches per
query. Counts are taken in float64 on the host, so the selection is the same
on every machine.
"""
from __future__ import annotations

import numpy as np

from .reference import check_metric

GRID_LO_MATCHES = 50
GRID_POINTS = 24
TARGET_ZERO_FRAC = 0.95
ROBUSTNESS_WEIGHT = 0.2


def dists(queries: np.ndarray, points: np.ndarray,
          metric: str) -> np.ndarray:
    """(Q, N) distances in float64: squared L2, or ``-q.x`` for ip."""
    check_metric(metric)
    q = queries.astype(np.float64)
    x = points.astype(np.float64)
    if metric == "ip":
        return -(q @ x.T)
    d = (q * q).sum(1)[:, None] + (x * x).sum(1)[None, :] - 2.0 * (q @ x.T)
    return np.maximum(d, 0.0)


def default_grid(points: np.ndarray, queries: np.ndarray,
                 metric: str) -> np.ndarray:
    sample = points[np.random.default_rng(0).choice(
        points.shape[0], size=min(2048, points.shape[0]), replace=False)]
    d = dists(queries, sample[:min(512, sample.shape[0])], metric)
    q_lo = min(0.0005, GRID_LO_MATCHES / points.shape[0])
    lo, hi = np.quantile(d, q_lo), np.quantile(d, 0.9995)
    if metric == "l2":
        return np.geomspace(max(lo, 1e-9), hi, GRID_POINTS).astype(np.float32)
    return np.linspace(lo, hi, GRID_POINTS).astype(np.float32)


def range_counts(points: np.ndarray, queries: np.ndarray, radii: np.ndarray,
                 metric: str, block: int = 32768) -> np.ndarray:
    """(Q, G) exact match counts at each radius."""
    counts = np.zeros((queries.shape[0], radii.shape[0]), np.int64)
    r = np.sort(radii.astype(np.float64))
    order = np.argsort(radii.astype(np.float64), kind="stable")
    for s in range(0, points.shape[0], block):
        d = dists(queries, points[s:s + block], metric)
        d.sort(axis=1)
        c = np.stack([np.searchsorted(row, r, side="right") for row in d])
        counts[:, order] += c
    return counts


def select_radius(points: np.ndarray, queries: np.ndarray,
                  metric: str) -> dict:
    """Returns the radius and the match profile of the query sample at it."""
    grid = default_grid(points, queries, metric)
    counts = range_counts(points, queries, grid, metric)
    captured = counts.mean(axis=0) / points.shape[0]
    zero_frac = (counts == 0).mean(axis=0)
    lg = np.log10(np.maximum(captured, 1e-12))
    slope = np.abs(np.gradient(lg)) if lg.size >= 2 else np.zeros_like(lg)
    score = np.abs(zero_frac - TARGET_ZERO_FRAC) + ROBUSTNESS_WEIGHT * slope
    feasible = zero_frac < 1.0
    if not feasible.any():
        raise ValueError("no radius in the grid gives any query a match")
    gi = int(np.argmin(np.where(feasible, score, np.inf)))
    c = counts[:, gi]
    return dict(radius=float(grid[gi]), grid_index=gi,
                zero_frac=float(zero_frac[gi]), mean_matches=float(c.mean()),
                max_matches=int(c.max()))
