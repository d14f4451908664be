"""The plain reference: brute-force exact range search, and the comparison.

Squared L2 distances over the float32 rows, decided in float64 on the host:
a float32 screen with a safety margin picks the candidates, and each
candidate's distance is then taken as an explicit difference in float64.
Nothing here imports the program. (On a TPU the default float32 matmul
rounds through bfloat16 and misplaces points that lie near the radius,
which is why the reference stays on the host.)
"""
from __future__ import annotations

import numpy as np

# The screen keeps every point whose float32 distance lies within this share
# of |q|^2 + |x|^2 of the radius, far wider than float32 rounding of the
# expanded form, so no true match is screened out.
SCREEN_MARGIN = 1e-4


def exact_dists(points: np.ndarray, query: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Squared L2 distances from ``query`` to ``points[ids]``, in float64."""
    diff = points[ids].astype(np.float64) - query.astype(np.float64)[None, :]
    return (diff * diff).sum(axis=1)


def exact_range(points: np.ndarray, queries: np.ndarray, radius: float,
                block: int = 512) -> list[np.ndarray]:
    """For each query, the sorted ids of every point within ``radius``."""
    x = points.astype(np.float32)
    pn = (x * x).sum(axis=1)
    # one matmul gives |x|^2 - 2 q.x: [q, 1] @ [-2x, |x|^2]^T
    xa = np.concatenate([-2.0 * x, pn[:, None]], axis=1).T.copy()
    slack = SCREEN_MARGIN * float(pn.max())
    out = []
    r = float(radius)
    for s in range(0, queries.shape[0], block):
        q = queries[s:s + block].astype(np.float32)
        qn = (q * q).sum(axis=1)
        qa = np.concatenate([q, np.ones((q.shape[0], 1), np.float32)], axis=1)
        thresh = (r - qn + SCREEN_MARGIN * qn + slack).astype(np.float32)
        rows, cols = np.nonzero((qa @ xa) <= thresh[:, None])
        splits = np.searchsorted(rows, np.arange(1, q.shape[0]))
        for i, cand in enumerate(np.split(cols, splits)):
            keep = cand[exact_dists(points, q[i], cand) <= r]
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
            answered: list[tuple[int, np.ndarray]]) -> dict:
    """Score every answer ``(query index, returned ids)`` against the
    reference.

    Returns ``ap``; ``hits`` and ``sizes``, per answer the true matches it
    returned and the true matches there are; ``bad_ids``, the answers
    holding an id outside the corpus or the same id twice;
    ``false_positives``, the returned ids that lie outside the radius; and
    ``max_excess``, the largest relative excess ``(d - r) / r`` of a
    returned id's exact distance over the radius (0 where every id lies
    inside)."""
    used = sorted({qi for qi, _ in answered})
    truth = dict(zip(used, exact_range(points, queries[used], radius)))
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
            d = exact_dists(points, queries[qi], ids)
            fp += int((d > radius).sum())
            worst = max(worst, float((d.max() - radius) / radius))
        ts.append(truth[qi])
        ans.append(ids)
    return dict(ap=average_precision(ts, ans),
                hits=[len(np.intersect1d(t, a)) for t, a in zip(ts, ans)],
                sizes=[len(t) for t in ts], bad_ids=bad,
                false_positives=fp, max_excess=worst,
                matches=int(sum(len(t) for t in ts)))
