"""Multi-tenant coalesced serving vs per-tenant sequential dispatch
(DESIGN.md §12).

The workload the coalescer exists for: N tenants each holding a small
ragged query batch against the same engine. The baseline answers them the
way a naive service would — one ``engine.answer`` call per tenant, each a
warm plan-cache hit on its own shape — so every tenant pays one device
dispatch plus the per-call Python plumbing. The coalesced path submits
all N requests and serves them in one deterministic ``tick()``: the
shape-class ladder packs them into a handful of padded cross-tenant
dispatches through ONE prepared AOT executable per class.

Both paths deliver the same artifact — host-materialized per-tenant
result pytrees, which is what a service hands back to its tenants. (The
coalescer's demux materializes on host by construction; the baseline
pulls each tenant's results explicitly so neither side hides a lazy
device array as "done".)

Demux bit-identity is asserted in the same run, on the same engines,
before any timing is reported (acceptance criterion: the speedup is only
valid if the coalesced answers are the per-tenant answers, bit for bit).

``coalesced_serving_speedup_x`` is gated in bench-smoke via
``check_regression.py``'s REQUIRED_GATED set.

Run: PYTHONPATH=src python -m benchmarks.bench_coalescer
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax

from repro.api import PassEngine, ServingConfig, CoalescerConfig
from repro.core import build_synopsis, random_queries
from repro.data import synthetic
from repro.serve import RequestCoalescer

SERVE_KINDS = ("sum", "count", "avg")


def _to_host(results):
    """Materialize one tenant's {kind: QueryResult} on host — the
    artifact a service actually returns. No-op on the coalesced path
    (its demux already produced numpy views)."""
    return jax.tree_util.tree_map(np.asarray, results)


def run(n_tenants: int = 8, k: int = 64, rate: float = 0.01,
        scale: float = 0.05, shape_classes: tuple = (96,),
        reps: int = 30, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    c, a = synthetic.nyc_taxi(scale=scale)
    syn, _ = build_synopsis(c, a, k=k, sample_rate=rate, kind="sum")
    serving = ServingConfig(kinds=SERVE_KINDS)
    # ragged per-tenant batches: no two tenants share a shape, so the
    # per-tenant baseline cannot amortize executables across tenants the
    # way real multi-tenant traffic cannot
    sizes = [3 + 2 * i + int(rng.integers(0, 2)) for i in range(n_tenants)]
    batches = {f"tenant-{i}": random_queries(c, q, seed=seed + 10 + i)
               for i, q in enumerate(sizes)}

    eng_seq = PassEngine(syn, serving=serving)
    eng_co = PassEngine(syn, serving=serving)
    co = RequestCoalescer(eng_co, CoalescerConfig(
        shape_classes=shape_classes, max_outstanding=n_tenants + 1,
        max_queue_depth=4 * n_tenants))

    def per_tenant_sequential():
        return {t: _to_host(eng_seq.answer(qs)) for t, qs in batches.items()}

    def coalesced():
        futs = {t: co.submit(t, qs) for t, qs in batches.items()}
        co.tick()
        return {t: f.result(timeout=0) for t, f in futs.items()}

    # Warm both paths (jit + AOT compile on 2nd concrete call), then
    # assert demux bit-identity on the warm answers BEFORE timing.
    for _ in range(2):
        want = per_tenant_sequential()
        got = coalesced()
    for t, qs in batches.items():
        for kind in SERVE_KINDS:
            for f in ("estimate", "ci_half", "lower", "upper",
                      "frac_rows_touched"):
                w = np.asarray(getattr(want[t][kind], f))
                g = np.asarray(getattr(got[t][kind], f))
                assert np.array_equal(w, g), (
                    f"coalesced demux NOT bit-identical: {t} {kind} {f}")

    t_seq, t_coal = [], []
    for _ in range(reps):                    # interleaved medians: sub-ms
        t0 = time.perf_counter()             # clocks jitter under load
        per_tenant_sequential()
        t_seq.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        coalesced()
        t_coal.append(time.perf_counter() - t0)
    t_s = float(np.median(t_seq))
    t_c = float(np.median(t_coal))
    speedup = t_s / t_c
    s = co.stats()
    amort = s["coalesced_rows"] / max(s["dispatches"], 1)

    print(f"coalesced serving: {n_tenants} tenants, ragged sizes {sizes}, "
          f"k={k}, classes={shape_classes}")
    print(f"  per-tenant sequential  {t_s * 1e3:8.3f} ms/round "
          f"({n_tenants} dispatches)")
    print(f"  coalesced tick         {t_c * 1e3:8.3f} ms/round "
          f"({s['dispatches'] / max(s['ticks'] - 1, 1):.1f} dispatches, "
          f"{amort:.1f} rows/dispatch, "
          f"pad overhead {s['padded_rows'] / max(s['coalesced_rows'], 1):.2f})")
    print(f"  coalesced serving speedup: {speedup:.2f}x "
          f"(demux bit-identity asserted)")
    return {"coalesced_serving_speedup_x": speedup,
            "coalesced_rows_per_dispatch": amort,
            "coalesced_tick_ms": t_c * 1e3}


def tiny_config() -> dict:
    """CI-sized run (bench_smoke / REPRO_BENCH_TINY): the acceptance
    workload — 8 tenants, ragged batches, tiny synopsis."""
    return dict(n_tenants=8, k=64, rate=0.01, scale=0.01,
                shape_classes=(96,), reps=30)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
