"""Partition-selection tier vs flat full-lake synopsis build (DESIGN.md
§14).

The workload: a well-clustered lake of P disjoint-support partitions and
selective range queries that touch only a handful of them. The flat
baseline does what a system without the catalog tier must — run the PASS
builder over EVERY row (one big synopsis) before it can answer. The
catalog path runs the one-pass sketch builder (cheap mergeable per-
partition summaries), prunes covered/disjoint partitions exactly from
the sketches, and materializes PASS synopses only for the few partially-
cut partitions.

Headline ``partition_pruning_speedup_x`` is end-to-end time-to-first-
answer (build/materialize + answer) with kernels pre-compiled on both
sides (separate warm-up replicas populate jax's compile cache, so the
timed sections compare data-touching work, not tracing). The catalog
side is charged its full sketch pass AND its selective synopsis builds;
the flat side is charged its one full-lake build. Both answer the same
batch; the run asserts the catalog estimates agree with the flat ground
truth before any timing. Gated in bench-smoke via
``check_regression.py``'s REQUIRED_GATED set.

Run: PYTHONPATH=src python -m benchmarks.bench_partitions
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax.numpy as jnp

from repro.api import PassEngine, CatalogConfig, ServingConfig
from repro.core.synopsis import build_synopsis
from repro.core.types import QueryBatch

BENCH_KINDS = ("sum", "count")


def _lake(num_partitions, rows_per_part, seed):
    """Disjoint clustered supports: partition p covers [10p, 10p+8]."""
    rng = np.random.default_rng(seed)
    parts = []
    for p in range(num_partitions):
        c = rng.uniform(10.0 * p, 10.0 * p + 8.0,
                        size=rows_per_part).astype(np.float32)
        a = rng.gamma(2.0, 1.0, size=rows_per_part).astype(np.float32)
        parts.append((c, a))
    return parts


def _selective_queries(num_partitions, q, seed, touch=4):
    """Each query spans ~``touch`` adjacent clusters, nearly aligned to
    the cluster boundaries: the inner clusters are covered exactly and
    the two edge clusters are cut partially (the rows the synopses must
    estimate)."""
    rng = np.random.default_rng(seed + 1)
    starts = rng.integers(0, num_partitions - touch, size=q)
    lo = 10.0 * starts + rng.uniform(5.5, 7.5, size=q)
    hi = 10.0 * (starts + touch - 1) + rng.uniform(0.5, 2.5, size=q)
    return QueryBatch(lo=jnp.asarray(lo[:, None], jnp.float32),
                      hi=jnp.asarray(hi[:, None], jnp.float32))


def run(num_partitions: int = 64, rows_per_part: int = 80_000,
        k_flat: int = 64, k_part: int = 8, s_per_leaf: int = 32,
        q: int = 8, budget: int = 10, reps: int = 5, seed: int = 0) -> dict:
    parts = _lake(num_partitions, rows_per_part, seed)
    c_all = np.concatenate([c for c, _ in parts])
    a_all = np.concatenate([a for _, a in parts])
    queries = _selective_queries(num_partitions, q, seed)
    cfg = CatalogConfig(k=k_part, s_per_leaf=s_per_leaf, method="eq",
                        max_partitions=budget, seed=seed)
    sv = ServingConfig(kinds=BENCH_KINDS)
    build_kw = dict(k=k_flat, sample_budget=k_flat * s_per_leaf,
                    method="eq", seed=seed)

    def flat_path():
        syn, _ = build_synopsis(c_all, a_all, **build_kw)
        eng = PassEngine(syn, serving=sv, ci=0.95)
        out = eng.answer(queries)
        return {kind: np.asarray(r.estimate) for kind, r in out.items()}

    def catalog_path():
        eng = PassEngine.from_catalog(parts, catalog=cfg, serving=sv,
                                      ci=0.95)
        out = eng.answer(queries)
        return ({kind: np.asarray(r.estimate) for kind, r in out.items()},
                eng.stats()["catalog"])

    # Warm both paths once (jit compile; cache is process-global per
    # shape), then sanity-check estimate quality against exact truth.
    flat_est = flat_path()
    cat_est, cat_stats = catalog_path()
    lo = np.asarray(queries.lo)[:, 0]
    hi = np.asarray(queries.hi)[:, 0]
    truth = {
        "sum": np.array([a_all[(c_all >= l) & (c_all <= h)].sum()
                         for l, h in zip(lo, hi)]),
        "count": np.array([((c_all >= l) & (c_all <= h)).sum()
                           for l, h in zip(lo, hi)], np.float64),
    }
    rel = {}
    for kind in BENCH_KINDS:
        t = truth[kind]
        for name, est in (("flat", flat_est[kind]), ("cat", cat_est[kind])):
            r = float(np.median(np.abs(est.astype(np.float64) - t)
                                / np.maximum(np.abs(t), 1.0)))
            rel[f"{name}_{kind}"] = r
            assert r <= 0.15, (
                f"{name} {kind} median relerr {r:.3f} > 0.15")

    t_flat, t_cat, built = [], [], []
    for _ in range(reps):                        # interleaved medians
        t0 = time.perf_counter()
        flat_path()
        t_flat.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _, st = catalog_path()
        t_cat.append(time.perf_counter() - t0)
        built.append(st["materialized"])
    t_f = float(np.median(t_flat))
    t_c = float(np.median(t_cat))
    speedup = t_f / t_c

    n = num_partitions * rows_per_part
    print(f"partition pruning: {num_partitions} partitions x "
          f"{rows_per_part} rows (n={n}), Q={q} selective queries, "
          f"budget={budget}")
    print(f"  flat full-lake build+answer   {t_f * 1e3:8.1f} ms "
          f"(k={k_flat}, relerr sum={rel['flat_sum']:.3f})")
    print(f"  catalog sketch+select+answer  {t_c * 1e3:8.1f} ms "
          f"({int(np.median(built))} of {num_partitions} partitions "
          f"materialized, relerr sum={rel['cat_sum']:.3f})")
    print(f"  partition pruning speedup: {speedup:.2f}x time-to-first-"
          f"answer")
    return {"partition_pruning_speedup_x": speedup,
            "partition_flat_build_ms": t_f * 1e3,
            "partition_catalog_ms": t_c * 1e3,
            "partition_materialized_frac":
                float(np.median(built)) / num_partitions}


def tiny_config() -> dict:
    """CI-sized run (bench_smoke)."""
    return dict(num_partitions=48, rows_per_part=40_000, k_flat=48,
                k_part=4, s_per_leaf=16, q=8, budget=6, reps=3)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
