"""§Perf hillclimb cell 3: PASS query serving (the paper's own technique).

Unlike the LM cells (dry-run/analytic only), the serving path runs for real
on this host, so these iterations are wall-clock measured. Iterations:

  it0  baseline: broadcast moments — pred (Q, k, s) elementwise + reduce
  it1  flattened one-hot matmul formulation (the Pallas kernel's shape:
       (Q, S_total) predicate @ (S_total, k) one-hot — MXU-shaped)
  it2  f32 end-to-end + fused jit epilogue (single compiled answer())
  it3  two-phase skip: classify first, then moments only over strata that
       any query touches (the tree's data-skipping, batched)
  it4  multi-aggregate serving: SUM+COUNT+AVG from ONE engine artifact pass
       (PassEngine.answer) vs looping the legacy single-kind
       estimate() three times — the layered engine's shared classification
       + moments must deliver >= 2x throughput here.
  it5  prepared-query steady state: a pinned PreparedQuery handle (config
       pre-validated, backend pre-resolved, AOT-compiled entry) vs per-call
       engine.answer() on repeated same-shape batches — the facade's
       Python-overhead win (ISSUE 4 acceptance).

Run: PYTHONPATH=src python -m benchmarks.perf_pass_serving
"""
from __future__ import annotations

import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro import engine
from repro.api import PassEngine, ServingConfig
from repro.core import build_synopsis, random_queries
from repro.core import estimators as E
from repro.core.types import QueryBatch
from repro.kernels import ops as kops
from repro.data import synthetic

SERVE_KINDS = ("sum", "count", "avg")


def bench(fn, *args, reps=5):
    fn(*args)
    fn(*args)       # 2nd warmup: prepared handles AOT-compile on call #2
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(Q=2048, k=256, rate=0.01, scale=0.05, Q4=1024, rate4=0.03, Q5=64):
    c, a = synthetic.nyc_taxi(scale=scale)
    syn, _ = build_synopsis(c, a, k=k, sample_rate=rate, kind="sum")
    qs = random_queries(c, Q, seed=3)
    kk, s, d = syn.sample_c.shape
    rows = []

    # it0: broadcast (Q,k,s) moments
    f0 = jax.jit(lambda lo, hi: E.sample_moments(
        syn.sample_c, syn.sample_a, syn.sample_valid, lo, hi))
    t0 = bench(f0, qs.lo, qs.hi)
    rows.append(("it0_broadcast_moments", t0))

    # it1: flattened one-hot matmul (kernel formulation, jnp backend)
    flat_c = syn.sample_c.reshape(kk * s, d)
    flat_a = syn.sample_a.reshape(kk * s)
    leaf = jnp.where(syn.sample_valid.reshape(kk * s),
                     jnp.repeat(jnp.arange(kk, dtype=jnp.int32), s), -1)
    f1 = jax.jit(lambda lo, hi: kops.stratified_moments_op(
        flat_c, flat_a, leaf, lo, hi, kk))
    t1 = bench(f1, qs.lo, qs.hi)
    rows.append(("it1_onehot_matmul", t1))

    # it2: full fused answer() epilogue (classification + exact + CI)
    f2 = jax.jit(lambda lo, hi: E.estimate(
        syn, type(qs)(lo, hi), kind="sum").estimate)
    t2 = bench(f2, qs.lo, qs.hi)
    rows.append(("it2_full_answer_fused", t2))

    # it3: two-phase — moments computed only over the strata the batch
    # touches (static gather of the union of partial strata; emulates the
    # tree skip for clustered workloads)
    rel = E.classify_leaves(syn.leaf_lo, syn.leaf_hi, qs.lo, qs.hi)
    touched = np.unique(np.asarray(jnp.where(rel == 1)[1]))
    sc = syn.sample_c[touched]
    sa = syn.sample_a[touched]
    sv = syn.sample_valid[touched]
    f3 = jax.jit(lambda lo, hi: E.sample_moments(sc, sa, sv, lo, hi))
    t3 = bench(f3, qs.lo, qs.hi)
    rows.append((f"it3_skip_gather({len(touched)}/{kk} strata)", t3))

    # it4: multi-aggregate serving — one shared artifact pass answers all
    # three kinds, vs the legacy loop paying classification + moments per
    # kind. Both paths produce bit-identical results (tests/test_engine.py).
    # As deployed: the legacy API dispatches one compiled program per kind
    # (classification + moments re-run each time); the engine API dispatches
    # a single program whose shared artifact stage feeds all three epilogues.
    # Serving-shaped scenario: a denser stratified sample (3%) so the moment
    # pass — the part the engine shares — carries the cost, as in the
    # paper's serving configurations.
    syn4, _ = build_synopsis(c, a, k=min(128, k), sample_rate=rate4,
                             kind="sum")
    qs4 = random_queries(c, Q4, seed=4)

    eng4 = PassEngine(syn4, serving=ServingConfig(kinds=SERVE_KINDS))

    def legacy_loop(lo, hi):
        q = QueryBatch(lo, hi)
        return tuple(E.estimate(syn4, q, kind=kd).estimate
                     for kd in SERVE_KINDS)

    def multi_answer(lo, hi):
        res = eng4.answer(QueryBatch(lo, hi))
        return tuple(res[kd].estimate for kd in SERVE_KINDS)

    t_legacy = bench(legacy_loop, qs4.lo, qs4.hi)
    t_multi = bench(multi_answer, qs4.lo, qs4.hi)
    rows.append((f"it4a_legacy_loop_{len(SERVE_KINDS)}_kinds", t_legacy))
    rows.append((f"it4b_engine_multi_aggregate", t_multi))

    # it5: steady-state serving through a pinned PreparedQuery handle vs
    # per-call engine.answer() — same compiled program, the delta is pure
    # Python re-setup (kwarg plumbing, validation, synopsis re-resolution,
    # jit-cache dispatch vs the AOT executable). Measured on a SMALL batch
    # against the low-rate synopsis so the per-call overhead — the thing
    # the prepared layer removes — is the dominant cost, as in a
    # high-QPS serving steady state; interleaved median-of-many because
    # sub-ms wall clocks jitter under host contention.
    qs5 = random_queries(c, Q5, seed=5)
    eng5 = PassEngine(syn, serving=ServingConfig(kinds=SERVE_KINDS))
    prepared = eng5.prepare(qs5)

    def per_call_answer(lo, hi):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            res = engine.answer(syn, QueryBatch(lo, hi), kinds=SERVE_KINDS)
        return tuple(res[kd].estimate for kd in SERVE_KINDS)

    def prepared_call(lo, hi):
        res = prepared(QueryBatch(lo, hi))
        return tuple(res[kd].estimate for kd in SERVE_KINDS)

    for fn in (per_call_answer, prepared_call, prepared_call):
        jax.block_until_ready(fn(qs5.lo, qs5.hi))   # warm jit + AOT paths
    t_a, t_p = [], []
    for _ in range(30):
        t0 = time.perf_counter()
        jax.block_until_ready(per_call_answer(qs5.lo, qs5.hi))
        t_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(prepared_call(qs5.lo, qs5.hi))
        t_p.append(time.perf_counter() - t0)
    t_per_call = float(np.median(t_a))
    t_prepared = float(np.median(t_p))
    rows.append(("it5a_per_call_engine_answer", t_per_call))
    rows.append(("it5b_prepared_query", t_prepared))

    print(f"PASS serving hillclimb: Q={Q}, k={k}, samples={kk*s}")
    base = rows[0][1]
    for name, t in rows:
        print(f"  {name:42s} {t*1e3:8.2f} ms/batch "
              f"({t/Q*1e6:6.2f} us/query, {base/t:4.2f}x vs it0)")
    speedup = t_legacy / t_multi
    prepared_speedup = t_per_call / t_prepared
    print(f"  multi-aggregate serving speedup: {speedup:.2f}x "
          f"(PassEngine.answer kinds={SERVE_KINDS} vs legacy estimate() loop)")
    print(f"  prepared-query speedup: {prepared_speedup:.2f}x "
          f"(PreparedQuery steady state vs per-call engine.answer)")
    return rows, {"serving_multi_aggregate_speedup_x": speedup,
                  "serving_prepared_speedup_x": prepared_speedup}


def tiny_config() -> dict:
    """CI-sized run (bench_smoke / REPRO_BENCH_TINY)."""
    return dict(Q=256, k=64, rate=0.01, scale=0.01, Q4=128, rate4=0.02,
                Q5=48)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import os
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
