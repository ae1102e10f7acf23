"""Fused-kernel serving hot paths (DESIGN.md §10): bootstrap megakernel vs
the scan formulations, and tiled vs dense multi-D routing.

Bootstrap — three contenders over the same (key, R) at the latency-shaped
serving case (small interactive query batch, R = 256):

* **legacy scan** — the formulation this PR replaces (PR 3/4 production
  path): ``jax.random.poisson`` Knuth-loop draws, one flat one-hot-matmul
  ``weighted_moments`` dispatch and one ``weighted_segment_reduce`` per
  replicate inside ``lax.scan``. ``bootstrap_fused_speedup_x`` gates the
  fused default against THIS — the user-visible win of the PR.
* **scan reference** — the modernized per-replicate ``lax.scan`` kept in
  ``uncertainty/bootstrap.py`` (inverse-CDF draws, fixed-order tree
  reductions): the bit-identity oracle. Reported ungated
  (``bootstrap_scan_ms``); the fused path's edge over it is loop
  amortization only, since the per-replicate arithmetic is identical by
  contract.
* **fused** — the one-pass replicate block (``fused=True``), bit-identity
  against the scan reference asserted before reporting.

Router: dense (B, k) distance-matrix routing vs the leaf-tile streaming
formulation at a k where the dense matrix is the dominant ingest
temporary. Peak live routing memory is reported analytically
(``route_peak_mb_*``: the distance-matrix bytes each formulation holds at
once — B·k floats dense vs B·bk per tile).

Run: PYTHONPATH=src python -m benchmarks.bench_fused
"""
from __future__ import annotations

import os
import time
import statistics
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.synopsis import build_synopsis
from repro.core.types import QueryBatch, AGG_SUM, AGG_COUNT
from repro.engine import executor as _executor
from repro.kernels.registry import get_backend
from repro.kernels.route import route_multid_dense, route_multid_tiled
from repro.uncertainty.bootstrap import bootstrap_replicates


@partial(jax.jit, static_argnames=("kinds", "n_boot", "backend_name"))
def _legacy_scan_bootstrap(syn, queries, key, kinds, n_boot, backend_name):
    """The pre-fusion production path, reproduced verbatim for the bench:
    per replicate, a Knuth-loop Poisson draw over the flattened sample,
    one flat (one-hot matmul) weighted-moments dispatch, one
    weighted-segment-reduce for the Hájek sizes — all inside ``lax.scan``.
    Returns (R, K, Q) replicate estimates like ``bootstrap_replicates``."""
    be = get_backend(backend_name)
    art = _executor.compute_artifacts(syn, queries, kinds,
                                      backend_name=backend_name)
    k, s, d = syn.sample_c.shape
    sc = syn.sample_c.reshape(k * s, d)
    sa = syn.sample_a.reshape(k * s)
    leaf = jnp.where(syn.sample_valid.reshape(k * s),
                     jnp.repeat(jnp.arange(k, dtype=jnp.int32), s), -1)
    Ni = syn.n_rows.astype(jnp.float32)[None]
    partf = (art.partial & ~art.cover).astype(jnp.float32)

    def step(carry, r):
        w = jax.random.poisson(jax.random.fold_in(key, r), 1.0,
                               (sa.shape[0],)).astype(jnp.float32)
        w = jnp.where(leaf >= 0, w, 0.0)
        mom = be.weighted_moments_flat(sc, sa, leaf, w,
                                       queries.lo, queries.hi, k)
        w_pred, ws_sum = mom[..., 0], mom[..., 1]
        k_star = be.weighted_segment_reduce(sa, w, leaf, k)[:, 2][None]
        scale = Ni / jnp.maximum(k_star, 1.0)
        s_part = jnp.sum(partf * scale * ws_sum, axis=1)
        c_part = jnp.sum(partf * scale * w_pred, axis=1)
        est = {}
        if "sum" in kinds:
            est["sum"] = art.exact[:, AGG_SUM] + s_part
        if "count" in kinds:
            est["count"] = art.exact[:, AGG_COUNT] + c_part
        if "avg" in kinds:
            S = art.exact[:, AGG_SUM] + s_part
            C = jnp.maximum(art.exact[:, AGG_COUNT] + c_part, 1.0)
            est["avg"] = S / C
        return carry, jnp.stack([est[kk] for kk in kinds], axis=0)

    _, reps = jax.lax.scan(step, 0, jnp.arange(n_boot))
    return reps


def _bench(f, reps=5):
    """(median seconds, last result) — the result is reused for the
    correctness cross-checks so they cost no extra bench passes."""
    out = f()
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts), out


def run(n_rows: int = 100_000, d: int = 2, k: int = 64,
        samples_per_leaf: int = 32, n_queries: int = 16, n_boot: int = 256,
        route_rows: int = 20_000, route_k: int = 512, route_bk: int = 128,
        seed: int = 0) -> dict:
    """Returns a flat metric dict (consumed by bench_smoke/BENCH_pr.json)."""
    rng = np.random.default_rng(seed)

    # -- bootstrap megakernel vs scan ---------------------------------------
    c = rng.uniform(0, 100, (n_rows, d))
    a = rng.lognormal(0, 1, n_rows)
    syn, _ = build_synopsis(c, a, k=k, sample_budget=k * samples_per_leaf,
                            method="kd")
    lo = rng.uniform(0, 60, (n_queries, d))
    qs = QueryBatch(jnp.asarray(lo, jnp.float32),
                    jnp.asarray(lo + 30.0, jnp.float32))
    kinds = ("sum", "avg")
    key = jax.random.PRNGKey(seed)
    t_legacy, r_legacy = _bench(lambda: _legacy_scan_bootstrap(
        syn, qs, key, kinds, n_boot, "jnp"))
    t_scan, r_scan = _bench(lambda: bootstrap_replicates(
        syn, qs, kinds, n_boot=n_boot, seed=seed, fused=False))
    t_fused, r_fused = _bench(lambda: bootstrap_replicates(
        syn, qs, kinds, n_boot=n_boot, seed=seed, fused=True))
    # correctness gate: the comparison is only meaningful if bit-identical
    assert np.array_equal(np.asarray(r_scan), np.asarray(r_fused)), \
        "fused bootstrap diverged from the scan reference"
    # ... and the legacy path must agree statistically (same estimator,
    # different RNG stream): compare replicate means loosely
    np.testing.assert_allclose(np.asarray(r_legacy).mean(axis=0),
                               np.asarray(r_fused).mean(axis=0), rtol=0.2)

    # -- tiled vs dense multi-D router --------------------------------------
    b_lo = jnp.asarray(rng.uniform(-1, 1, (route_k, d)), jnp.float32)
    b_hi = b_lo + jnp.asarray(rng.uniform(0, 0.3, (route_k, d)), jnp.float32)
    rows = jnp.asarray(rng.uniform(-1.2, 1.2, (route_rows, d)), jnp.float32)
    dense_j = jax.jit(route_multid_dense)
    t_dense, (di, dd) = _bench(lambda: dense_j(b_lo, b_hi, rows))
    t_tiled, (ti, td) = _bench(lambda: route_multid_tiled(b_lo, b_hi, rows,
                                                          bk=route_bk))
    assert np.array_equal(np.asarray(di), np.asarray(ti)), \
        "tiled router diverged from the dense oracle"
    assert np.array_equal(np.asarray(dd), np.asarray(td))

    metrics = {
        "bootstrap_legacy_scan_ms": t_legacy * 1e3,
        "bootstrap_scan_ms": t_scan * 1e3,
        "bootstrap_fused_ms": t_fused * 1e3,
        "bootstrap_fused_speedup_x": t_legacy / t_fused,
        "route_multid_dense_ms": t_dense * 1e3,
        "route_multid_tiled_ms": t_tiled * 1e3,
        "route_multid_tiled_speedup_x": t_dense / t_tiled,
        # peak live routing memory (distance buffers), analytic
        "route_peak_mb_dense": route_rows * route_k * 4 / 1e6,
        "route_peak_mb_tiled": route_rows * route_bk * 4 / 1e6,
    }
    # measured counterparts of the analytic numbers (benchmarks.common):
    # RSS high-water catches the XLA buffers the analytic model describes,
    # the tracemalloc peak bounds host-side bench overhead. Informational
    # (not gated) — RSS is a process-lifetime maximum.
    from .common import measure_peak
    _, peak = measure_peak(lambda: jax.block_until_ready(
        route_multid_tiled(b_lo, b_hi, rows, bk=route_bk)))
    metrics["route_peak_rss_mb"] = peak["peak_rss_mb"]
    metrics["route_py_heap_peak_mb"] = peak["py_heap_peak_mb"]
    print(f"bootstrap R={n_boot}, Q={n_queries}, k={k}, d={d}:")
    print(f"  legacy scan (pre-fusion path) {t_legacy * 1e3:8.2f} ms")
    print(f"  scan reference                {t_scan * 1e3:8.2f} ms")
    print(f"  fused                         {t_fused * 1e3:8.2f} ms   "
          f"({t_legacy / t_fused:.2f}x vs legacy, "
          f"{t_scan / t_fused:.2f}x vs reference, bit-identical to it)")
    print(f"router B={route_rows:,}, k={route_k}, d={d}:")
    print(f"  dense {t_dense * 1e3:8.2f} ms "
          f"({metrics['route_peak_mb_dense']:.0f} MB live)")
    print(f"  tiled {t_tiled * 1e3:8.2f} ms "
          f"({metrics['route_peak_mb_tiled']:.0f} MB live, "
          f"{t_dense / t_tiled:.2f}x, bit-identical)")
    return metrics


def tiny_config() -> dict:
    """CI-sized run (bench_smoke) — the defaults are already tiny."""
    return dict()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
