"""The benchmark's own NYC-taxi stand-in (PASS paper §5.1.1, §5.4).

A copy of the program's ``repro.data.synthetic.nyc_taxi`` as it stood when
the benchmark was defined, kept here so that a later change to the program
cannot move the yardstick. ``bench/tests/test_bench_data.py`` holds the two
equal at a fixed seed.

7.7 M trips at ``scale=1``: a heavy-tailed (lognormal) trip distance over a
pickup-time predicate with rush-hour structure; ``dims=3`` adds the §5.4
predicate columns (dropoff-day time, pickup location id).
"""
from __future__ import annotations

import numpy as np

PAPER_ROWS = 7_700_000


def nyc_taxi(scale: float = 1.0, seed: int = 2, dims: int = 1):
    """(c, a): predicate columns (n,) or (n, dims) and trip distance (n,),
    both float64, rows sorted by pickup time."""
    n = int(PAPER_ROWS * scale)
    rng = np.random.default_rng(seed)
    day = rng.integers(0, 31, size=n).astype(np.float64)
    hour_w = np.array([1, 1, 1, 1, 1, 2, 4, 7, 8, 6, 5, 5,
                       6, 6, 5, 5, 6, 8, 9, 8, 6, 5, 4, 2], dtype=np.float64)
    hour = rng.choice(24, size=n, p=hour_w / hour_w.sum()).astype(np.float64)
    minute = rng.uniform(0, 60, size=n)
    pickup_t = day * 1440 + hour * 60 + minute
    dist = rng.lognormal(mean=0.9, sigma=0.8, size=n)
    dist = np.clip(dist, 0.0, 80.0)
    long_trip = rng.random(n) < 0.01
    dist = np.where(long_trip, dist * rng.uniform(2, 5, size=n), dist)
    order = np.argsort(pickup_t)
    if dims == 1:
        return pickup_t[order], dist[order]
    cols = [pickup_t, day * 1440 + rng.uniform(0, 1440, size=n),
            rng.integers(1, 266, size=n).astype(np.float64),
            pickup_t + dist * rng.uniform(2, 6, size=n),
            rng.uniform(0, 1440, size=n)]
    c = np.stack(cols[:dims], axis=1)[order]
    return c, dist[order]


def stream_pool(rows: int, seed: int, dims: int):
    """``rows`` later trips of the same month, in random arrival order, as
    the float32 rows the ingest path receives: ((rows, dims), (rows,))."""
    scale = 1.01 * rows / PAPER_ROWS
    c, a = nyc_taxi(scale=scale, seed=seed, dims=dims)
    p = np.random.default_rng(seed).permutation(c.shape[0])[:rows]
    return (np.asarray(c[p], np.float32).reshape(rows, -1),
            np.asarray(a[p], np.float32))
