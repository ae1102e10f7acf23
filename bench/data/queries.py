"""The benchmark's own range-query generators.

``random_queries`` is a copy of the program's
``repro.core.query.random_queries`` (paper §5.1.2: rectangles whose
endpoints are data values), returning host float32 arrays: requests reach
the server from the network, as host data. ``covered_queries`` builds
predicates that cut no leaf of a synopsis (the probe of the exact part).
"""
from __future__ import annotations

import numpy as np

BIG = 3.0e38


def random_queries(c, num: int, seed: int = 0, min_frac: float = 0.005,
                   max_frac: float = 0.3):
    """(lo, hi), each (num, d) float32: per dimension a width drawn from
    U(min_frac, max_frac) of the rows, anchored on sorted data values."""
    c = np.asarray(c, dtype=np.float64)
    c2 = c[:, None] if c.ndim == 1 else c
    n, d = c2.shape
    rng = np.random.default_rng(seed)
    lo = np.zeros((num, d))
    hi = np.zeros((num, d))
    for j in range(d):
        vals = np.sort(c2[:, j])
        width = rng.uniform(min_frac, max_frac, size=num)
        start = rng.uniform(0, 1 - width)
        lo_idx = (start * (n - 1)).astype(np.int64)
        hi_idx = np.minimum(((start + width) * (n - 1)).astype(np.int64), n - 1)
        lo[:, j] = vals[lo_idx]
        hi[:, j] = vals[hi_idx]
    return lo.astype(np.float32), hi.astype(np.float32)


def clean_thresholds(lo, hi):
    """f32 thresholds t in one dimension at which ``x <= t`` or ``x >= t``
    splits the leaves as it splits their rows: every nonempty leaf box lies
    at least one f32 step from t (a box stores its rows' extremes rounded to
    f32, so its rows lie within half a step of it)."""
    ne = lo <= hi
    lo, hi = lo[ne].astype(np.float32), hi[ne].astype(np.float32)
    cand = np.unique(np.nextafter(lo, np.float32(-np.inf)))
    up = np.nextafter(hi, np.float32(np.inf))
    down = np.nextafter(lo, np.float32(-np.inf))
    clean = np.ones(cand.shape, bool)
    for s in range(0, lo.size, 4096):       # bounded (cand, leaves) blocks
        clean &= np.all((up[None, s:s + 4096] <= cand[:, None])
                        | (down[None, s:s + 4096] >= cand[:, None]), axis=1)
    return cand[(cand > lo.min()) & clean]


def covered_queries(leaf_lo, leaf_hi, n: int, seed: int):
    """(lo, hi) float32 predicates with no partial leaf: 1-D intervals
    between clean thresholds; for d > 1 the whole space and half-spaces at
    clean thresholds. The whole space comes first in every d."""
    lo = np.asarray(leaf_lo, np.float32)
    hi = np.asarray(leaf_hi, np.float32)
    d = lo.shape[1]
    rng = np.random.default_rng(seed)
    qlo, qhi = [[-BIG] * d], [[BIG] * d]
    if d == 1:
        t = np.concatenate([[-BIG], clean_thresholds(lo[:, 0], hi[:, 0]),
                            [BIG]]).astype(np.float32)
        for _ in range(n - 1):
            i, j = np.sort(rng.choice(t.size, 2, replace=False))
            qlo.append([t[i]])
            qhi.append([t[j]])
    else:
        per = max(1, (n - 1) // (2 * d))
        for j in range(d):
            t = clean_thresholds(lo[:, j], hi[:, j])
            for tj in rng.permutation(t)[:per]:
                a, b = [-BIG] * d, [BIG] * d
                b[j] = tj
                qlo.append(list(a))
                qhi.append(list(b))
                a[j], b[j] = tj, BIG
                qlo.append(a)
                qhi.append(b)
    return np.array(qlo, np.float32), np.array(qhi, np.float32)
