"""The plain reference: brute-force exact range search, and the comparison.

Two metrics, as the configuration's profile names them: ``l2``, the squared
Euclidean distance, and ``ip``, the negative inner product ``-q.x`` (its
radii are negative). Distances are decided in float64 on the host: a
float32 screen with a safety margin picks the candidates, and each
candidate's distance is then taken elementwise in float64. Nothing here
imports the program. (On a TPU the default float32 matmul rounds through
bfloat16 and misplaces points that lie near the radius, which is why the
reference stays on the host.)
"""
from __future__ import annotations

import numpy as np

METRICS = ("l2", "ip")

# The screen keeps every point whose float32 distance lies within this share
# of the distance's scale of the radius (|q|^2 + |x|^2 for l2, |q| |x| for
# ip), far wider than float32 rounding of a 128-term dot product, so no true
# match is screened out.
SCREEN_MARGIN = 1e-4


def check_metric(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; expected one of "
                         f"{METRICS}")


def exact_dists(points: np.ndarray, query: np.ndarray, ids: np.ndarray,
                metric: str) -> np.ndarray:
    """Distances from ``query`` to ``points[ids]``, in float64; ``metric``
    as ``exact_range`` has checked it."""
    x = points[ids].astype(np.float64)
    q = query.astype(np.float64)[None, :]
    if metric == "ip":
        return -(x * q).sum(axis=1)
    diff = x - q
    return (diff * diff).sum(axis=1)


def exact_range(points: np.ndarray, queries: np.ndarray, radius: float,
                metric: str, block: int = 512) -> list[np.ndarray]:
    """For each query, the sorted ids of every point within ``radius``."""
    check_metric(metric)
    x = points.astype(np.float32)
    pn = (x * x).sum(axis=1)
    if metric == "l2":
        # one matmul gives |x|^2 - 2 q.x: [q, 1] @ [-2x, |x|^2]^T
        xa = np.concatenate([-2.0 * x, pn[:, None]], axis=1).T.copy()
        slack = SCREEN_MARGIN * float(pn.max())
    else:
        xa = (-x).T.copy()
        x_max = float(np.sqrt(pn.max()))
    out = []
    r = float(radius)
    for s in range(0, queries.shape[0], block):
        q = queries[s:s + block].astype(np.float32)
        qn = (q * q).sum(axis=1)
        if metric == "l2":
            qa = np.concatenate([q, np.ones((q.shape[0], 1), np.float32)],
                                axis=1)
            thresh = r - qn + SCREEN_MARGIN * qn + slack
        else:
            qa = q
            thresh = r + SCREEN_MARGIN * np.sqrt(qn) * x_max
        thresh = thresh.astype(np.float32)
        rows, cols = np.nonzero((qa @ xa) <= thresh[:, None])
        splits = np.searchsorted(rows, np.arange(1, q.shape[0]))
        for i, cand in enumerate(np.split(cols, splits)):
            keep = cand[exact_dists(points, q[i], cand, metric) <= r]
            out.append(np.sort(keep))
    return out


def average_precision(truth: list[np.ndarray],
                      answers: list[np.ndarray]) -> float:
    """sum |K ∩ K'| / sum |K| over the answers (size-weighted, as in the
    paper); 1.0 where no query has a match."""
    denom = sum(len(t) for t in truth)
    if denom == 0:
        return 1.0
    num = sum(len(np.intersect1d(t, a)) for t, a in zip(truth, answers))
    return num / denom


def compare(points: np.ndarray, queries: np.ndarray, radius: float,
            answered: list[tuple[int, np.ndarray]], metric: str) -> dict:
    """Score every answer ``(query index, returned ids)`` against the
    reference.

    Returns ``ap``; ``hits`` and ``sizes``, per answer the true matches it
    returned and the true matches there are; ``bad_ids``, the answers
    holding an id outside the corpus or the same id twice;
    ``false_positives``, the returned ids that lie outside the radius; and
    ``max_excess``, the largest relative excess ``(d - r) / |r|`` of a
    returned id's exact distance over the radius (0 where every id lies
    inside)."""
    used = sorted({qi for qi, _ in answered})
    truth = dict(zip(used, exact_range(points, queries[used], radius,
                                       metric)))
    n = points.shape[0]
    bad = fp = 0
    worst = 0.0
    ts, ans = [], []
    for qi, ids in answered:
        ids = np.asarray(ids, np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= n
                         or np.unique(ids).size != ids.size):
            bad += 1
            ids = np.unique(ids[(ids >= 0) & (ids < n)])
        if ids.size:
            d = exact_dists(points, queries[qi], ids, metric)
            fp += int((d > radius).sum())
            worst = max(worst, float((d.max() - radius) / abs(radius)))
        ts.append(truth[qi])
        ans.append(ids)
    return dict(ap=average_precision(ts, ans),
                hits=[len(np.intersect1d(t, a)) for t, a in zip(ts, ans)],
                sizes=[len(t) for t in ts], bad_ids=bad,
                false_positives=fp, max_excess=worst,
                matches=int(sum(len(t) for t in ts)))
