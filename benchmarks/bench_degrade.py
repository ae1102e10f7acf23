"""Deadline-degraded serving: time-to-first-answer through the tier-0
aggregates-only path vs the full sample-backed serving path
(DESIGN.md §15).

The workload the degradation ladder exists for: a request arrives with
no deadline budget left, so the engine must answer from the aggregate
tree alone — the planner DFS plus the §2.3 hard-bound envelope, zero
sample work, zero device dispatch. ``degraded_first_answer_ms`` clocks
``engine.answer(q, deadline_ms=0)`` end to end (what a deadline-blown
tenant actually pays) and gates against the tier-0 path silently
growing device work or going super-linear in the tree walk. Two
informational context numbers ride along: the *cold* full path on a
fresh engine (first answer including trace+compile — what tier-0 spares
a deadline-blown request from waiting on) and the warm plan-cache-hit
full path (the steady-state cost tier-0 intentionally does NOT try to
beat; a warm AOT dispatch on tiny data is faster than any host DFS).

Tier-0 correctness is asserted in the same run before any timing: on
leaf-aligned (covered) queries the tier-0 sum/count envelope collapses
onto the exact aggregate bit for bit, and the estimates equal the exact
path's (acceptance criterion of the ladder — a fast wrong answer would
make the metric meaningless).

``degraded_first_answer_ms`` is gated in bench-smoke via
``check_regression.py``'s REQUIRED_GATED set (lower is better).

Run: PYTHONPATH=src python -m benchmarks.bench_degrade
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.api import PassEngine, ServingConfig
from repro.core import build_synopsis
from repro.core.types import QueryBatch

SERVE_KINDS = ("sum", "count", "avg")


def _covered_queries(syn, m: int) -> QueryBatch:
    """Leaf-aligned queries: fully covered, zero partial strata, so the
    tier-0 answer must equal the exact aggregate."""
    lo = np.asarray(syn.leaf_lo, np.float32)[:, 0]
    hi = np.asarray(syn.leaf_hi, np.float32)[:, 0]
    k = lo.shape[0]
    qlo, qhi = [], []
    for i in range(m):
        a = (i * 3) % (k - 1)
        b = min(k - 1, a + 4)
        qlo.append(lo[a])
        qhi.append(hi[b])
    return QueryBatch(lo=np.asarray(qlo, np.float32)[:, None],
                      hi=np.asarray(qhi, np.float32)[:, None])


def run(n: int = 200_000, k: int = 64, rate: float = 0.01,
        n_queries: int = 8, reps: int = 50, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    c = np.sort(rng.uniform(0, 100, n))
    # integer-valued measures: f32 accumulation is exact, so the tier-0
    # bit-identity assertion below is meaningful rather than approximate
    a = np.floor(rng.uniform(0, 1000, n))
    syn, _ = build_synopsis(c, a, k=k, sample_rate=rate, method="eq",
                            seed=seed)
    q = _covered_queries(syn, n_queries)

    # Cold full-path first answer on a throwaway engine: the wait a
    # deadline-blown request is spared (trace + compile + dispatch).
    eng_cold = PassEngine(syn, serving=ServingConfig(kinds=SERVE_KINDS))
    t0 = time.perf_counter()
    eng_cold.answer(q)
    t_cold = time.perf_counter() - t0

    eng = PassEngine(syn, serving=ServingConfig(kinds=SERVE_KINDS))
    # Warm the full path (jit + AOT on the 2nd concrete call) and the
    # tier-0 path, then assert tier-0 == exact on the covered queries
    # BEFORE timing.
    for _ in range(2):
        exact = eng.answer(q)
        t0res = eng.answer(q, deadline_ms=0.0)
    for kind in SERVE_KINDS:
        w = np.asarray(exact[kind].estimate)
        g = np.asarray(t0res[kind].estimate)
        assert np.array_equal(w, g), (
            f"tier-0 NOT bit-identical to exact on covered queries: {kind}")

    t_deg, t_full = [], []
    for _ in range(reps):                    # interleaved medians: sub-ms
        t0 = time.perf_counter()             # clocks jitter under load
        eng.answer(q, deadline_ms=0.0)
        t_deg.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        eng.answer(q)
        t_full.append(time.perf_counter() - t0)
    t_d = float(np.median(t_deg))
    t_f = float(np.median(t_full))

    st = eng.stats()
    print(f"degraded serving: n={n}, k={k}, {n_queries} covered queries, "
          f"{st['degraded_serves']} degraded serves")
    print(f"  tier-0 first answer    {t_d * 1e3:8.3f} ms "
          f"(aggregates only, zero sample work; gated)")
    print(f"  cold full first answer {t_cold * 1e3:8.3f} ms "
          f"(trace + compile + dispatch — what tier-0 spares)")
    print(f"  warm full serving      {t_f * 1e3:8.3f} ms "
          f"(plan-cache hit; informational)")
    print(f"  degraded first answer lands {t_cold / max(t_d, 1e-9):.0f}x "
          f"ahead of the cold full path (tier-0 bit-identity asserted)")
    return {"degraded_first_answer_ms": t_d * 1e3,
            "degrade_cold_full_first_answer_ms": t_cold * 1e3,
            "degrade_warm_full_path_ms": t_f * 1e3}


def tiny_config() -> dict:
    """CI-sized run (bench_smoke / REPRO_BENCH_TINY): the acceptance
    workload — tiny synopsis, leaf-aligned query batch."""
    return dict(n=60_000, k=32, rate=0.01, n_queries=8, reps=50)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
