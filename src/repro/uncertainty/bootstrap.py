"""Deterministic, key-threaded on-device Poisson bootstrap (DESIGN.md §7,
§10).

Cross-check estimator for non-linear aggregates (AVG = ratio of two HT
estimates, where the delta-method CLT is only asymptotically valid): each
replicate draws i.i.d. Poisson(1) resample weights over the stratified
sample — the streaming-friendly surrogate for multinomial resampling, one
weight per sample, no index shuffling — and re-runs the per-stratum
estimate through the *weighted* one-pass kernels. Per-stratum resampled
sizes ``K*_i = sum_j w_ij`` feed the Hájek normalization ``N_i / K*_i``
that keeps AVG replicates scale-stable when a stratum resamples light or
heavy.

Two execution strategies produce bit-identical replicates (tested):

* **fused** (the default, ``CIConfig(boot_fused=True)``): one
  ``bootstrap_moments`` registry op emits the whole (R, 3, Q, k)
  replicate-moment block from a single pass over the sample arrays — the
  Pallas megakernel on the ``pallas`` backend (``kernels/bootstrap.py``),
  a replicate-tiled broadcast-reduce on ``jnp``, the per-replicate oracle
  loop on ``ref``. The epilogue (Hájek scale, partial-stratum sums,
  estimate assembly) runs replicate-batched.
* **scan** (the reference): one ``weighted_moments`` registry-op dispatch
  per replicate inside a ``lax.scan`` — R passes over the samples. Kept
  as the bit-identity oracle and the bench baseline
  (``benchmarks/bench_fused.py``).

Randomness is threaded from a single PRNG key with ``fold_in(key, r)``;
the fused path draws all R weight matrices in one batched threefry pass
that bit-matches the scan path's sequential draws, so a given
(key, n_boot) is bit-reproducible across runs, jax versions, and
strategies. Exact-covered strata enter every replicate through the
artifact stage's exact accumulation with no resample noise, so fully
exact-covered queries produce zero-width percentile intervals.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..core.types import (QueryBatch, QueryResult, AGG_SUM, AGG_COUNT)
from ..engine import executor as _executor
from ..engine.assemble import assemble as _assemble_kind
from ..kernels.registry import get_backend

BOOT_KINDS = ("sum", "count", "avg")


# Poisson(1) CDF table for inverse-CDF sampling: P(X <= t) for t = 0..15.
# A single f32 uniform has 24-bit granularity, so u can never exceed
# P(X <= 10) = 1 - 1.0e-8 > 1 - 2^-24 — the table is exhaustive w.r.t.
# the draw, not a truncation. One uniform + 16 threshold compares per
# sample replaces jax.random.poisson's Knuth rejection loop (expected e
# key-splits + uniforms per sample), which profiled as the dominant cost
# of BOTH bootstrap strategies.
_P1_CDF = jnp.asarray(
    [float(sum((2.718281828459045 ** -1) / _f
               for _f in [1, 1, 2, 6, 24, 120, 720, 5040, 40320, 362880,
                          3628800, 39916800, 479001600, 6227020800,
                          87178291200, 1307674368000][:t + 1]))
     for t in range(16)], jnp.float32)


def _draw_weights(key, r, shape):
    """Poisson(1) resample weights for replicate r, drawn by inverse CDF
    from one ``fold_in(key, r)`` threefry uniform per sample: w = #{t :
    u >= P(X <= t)}. Deterministic and bit-stable across jax versions
    (threefry contract), and shared verbatim by the scan and fused
    strategies, so their draws are bit-identical by construction."""
    u = jax.random.uniform(jax.random.fold_in(key, r), shape, jnp.float32)
    return jnp.sum(u[..., None] >= _P1_CDF, axis=-1).astype(jnp.float32)


def _scan_moments(syn, queries, key, n_boot, backend_name):
    """The reference strategy: one weighted-moments op per replicate inside
    ``lax.scan`` — R passes over the samples. Returns the replicate-moment
    block ((R, 3, Q, k) f32) and the resampled sizes K* ((R, k) f32)."""
    be = get_backend(backend_name)

    def step(carry, r):
        w = jnp.where(syn.sample_valid,
                      _draw_weights(key, r, syn.sample_valid.shape), 0.0)
        w_pred, ws_sum, ws_sumsq = be.weighted_moments(
            syn.sample_c, syn.sample_a, syn.sample_valid, w,
            queries.lo, queries.hi)
        # K* is a sum of small integers — exact in f32 in any order, so it
        # is safe to compute it per replicate here and batched below.
        return carry, (jnp.stack([w_pred, ws_sum, ws_sumsq], axis=0),
                       jnp.sum(w, axis=-1))

    _, (mom, k_star) = jax.lax.scan(step, 0, jnp.arange(n_boot))
    return mom, k_star


def _fused_moments(syn, queries, key, n_boot, backend_name):
    """The fused strategy: all R weight matrices drawn in one batched
    threefry pass (bit-matching the scan path's sequential ``fold_in``
    draws), then one ``bootstrap_moments`` registry op for the whole
    replicate-moment block — a single pass over the samples."""
    be = get_backend(backend_name)
    W = jax.vmap(
        lambda r: _draw_weights(key, r, syn.sample_valid.shape)
    )(jnp.arange(n_boot))                                   # (R, k, s)
    W = jnp.where(syn.sample_valid[None], W, 0.0)
    mom = be.bootstrap_moments(syn.sample_c, syn.sample_a,
                               syn.sample_valid, W,
                               queries.lo, queries.hi)      # (R, 3, Q, k)
    return mom, jnp.sum(W, axis=-1)


def _replicates(syn, art, queries, key, kinds, n_boot, normalize,
                backend_name, fused):
    """(R, K, Q) replicate estimates. The two strategies differ ONLY in how
    the (R, 3, Q, k) moment block is produced; the estimate epilogue below
    is one shared replicate-batched program, so fused-vs-scan bit-identity
    reduces to the moment ops' (tested per backend) — identical epilogue
    code on identical inputs cannot diverge through fusion-context
    differences."""
    strategy = _fused_moments if fused else _scan_moments
    mom, k_star = strategy(syn, queries, key, n_boot, backend_name)
    w_pred, ws_sum = mom[:, 0], mom[:, 1]                   # (R, Q, k)
    Ni = syn.n_rows.astype(jnp.float32)
    if normalize == "hajek":
        scale = (Ni / jnp.maximum(k_star, 1.0))[:, None, :]  # (R, 1, k)
    else:                                   # 'ht': fixed design scale
        Ki = jnp.maximum(syn.k_per_leaf.astype(jnp.float32), 1.0)
        scale = (Ni / Ki)[None, None, :]
    partf = (art.partial & ~art.cover).astype(jnp.float32)[None]
    s_part = jnp.sum(partf * scale * ws_sum, axis=-1)       # (R, Q)
    c_part = jnp.sum(partf * scale * w_pred, axis=-1)
    est = {}
    if "sum" in kinds:
        est["sum"] = art.exact[:, AGG_SUM] + s_part
    if "count" in kinds:
        est["count"] = art.exact[:, AGG_COUNT] + c_part
    if "avg" in kinds:
        S = art.exact[:, AGG_SUM] + s_part
        C = jnp.maximum(art.exact[:, AGG_COUNT] + c_part, 1.0)
        est["avg"] = S / C
    return jnp.stack([est[k] for k in kinds], axis=1)       # (R, K, Q)


@partial(jax.jit, static_argnames=("kinds", "n_boot", "level", "normalize",
                                   "use_aggregates", "backend_name",
                                   "fused"))
def _bootstrap_jit(syn, queries, plan_masks, key, kinds, n_boot, level,
                   normalize, use_aggregates, backend_name, fused=True):
    art = _executor.compute_artifacts(syn, queries, kinds,
                                      use_aggregates=use_aggregates,
                                      backend_name=backend_name,
                                      plan_masks=plan_masks)
    reps = _replicates(syn, art, queries, key, kinds, n_boot, normalize,
                       backend_name, fused)                    # (R, K, Q)
    alpha = (1.0 - level) / 2.0
    qs = jnp.quantile(reps, jnp.asarray([alpha, 1.0 - alpha]), axis=0)
    out = {}
    for i, kind in enumerate(kinds):
        res = _assemble_kind(syn, art, kind,
                                 use_aggregates=use_aggregates)
        lo, hi = qs[0, i], qs[1, i]
        if use_aggregates:
            lo = jnp.clip(lo, res.lower, res.upper)
            hi = jnp.clip(hi, res.lower, res.upper)
        out[kind] = dataclasses.replace(
            res, ci_half=0.5 * (hi - lo), ci_lo=lo, ci_hi=hi)
    return out


@partial(jax.jit, static_argnames=("kinds", "n_boot", "normalize",
                                   "use_aggregates", "backend_name",
                                   "fused"))
def _replicates_jit(syn, queries, key, kinds, n_boot, normalize,
                    use_aggregates, backend_name, fused):
    art = _executor.compute_artifacts(syn, queries, kinds,
                                      use_aggregates=use_aggregates,
                                      backend_name=backend_name)
    return _replicates(syn, art, queries, key, kinds, n_boot, normalize,
                       backend_name, fused)


def bootstrap_replicates(syn, queries: QueryBatch, kinds=("avg",), *,
                         n_boot: int = 200, key: jax.Array | None = None,
                         seed: int = 0, normalize: str = "hajek",
                         use_aggregates: bool = True,
                         backend: str | None = None,
                         fused: bool = True) -> jax.Array:
    """(R, K, Q) replicate estimates for ``kinds`` (subset of
    SUM/COUNT/AVG) — the raw resampling distribution behind the percentile
    intervals. ``fused=True`` runs the one-pass megakernel strategy,
    ``fused=False`` the per-replicate ``lax.scan`` reference; the two are
    bit-identical for the same (key, n_boot) (tested per backend)."""
    kinds = (kinds,) if isinstance(kinds, str) else tuple(kinds)
    k = key if key is not None else jax.random.PRNGKey(seed)
    return _replicates_jit(_executor.resolve_synopsis(syn), queries, k,
                           kinds, int(n_boot), normalize, use_aggregates,
                           get_backend(backend).name, bool(fused))


def poisson_bootstrap(syn, queries: QueryBatch, kinds=("avg",), *,
                      level: float = 0.95, n_boot: int = 200,
                      key: jax.Array | None = None, seed: int = 0,
                      normalize: str = "hajek", use_aggregates: bool = True,
                      backend: str | None = None,
                      plan=None) -> dict[str, QueryResult]:
    """Deprecated shim: percentile bootstrap intervals for ``kinds``
    (subset of SUM/COUNT/AVG). Returns ``{kind: QueryResult}`` with
    ``ci_lo``/``ci_hi`` set to the (1-level)/2 replicate percentiles and
    ``estimate`` the plain (non-resampled) estimator.

    ``key`` (or ``seed``) fully determines the resample weights —
    replicates use ``fold_in(key, r)``, so results are bit-reproducible.
    ``normalize='hajek'`` rescales each stratum by its resampled size
    (recommended for AVG); ``'ht'`` keeps the fixed N_i/K_i design scale.

    Use ``repro.api.PassEngine(syn, serving=ServingConfig(kinds=...),
    ci=CIConfig(method='bootstrap', ...)).answer(queries)`` instead.
    """
    from .. import api
    api.warn_once(
        "repro.uncertainty.poisson_bootstrap",
        "repro.api.PassEngine(syn, serving=ServingConfig(kinds=...), "
        "ci=CIConfig(level=..., method='bootstrap', n_boot=..., key=...))"
        ".answer(queries)")
    eng = api.PassEngine(
        syn,
        serving=api.ServingConfig(kinds=kinds,
                                  use_aggregates=use_aggregates,
                                  backend=backend),
        ci=api.CIConfig(level=level, method="bootstrap", n_boot=int(n_boot),
                        key=key if key is not None else int(seed),
                        boot_normalize=normalize))
    return eng.answer(queries, plan=plan)


__all__ = ["poisson_bootstrap", "bootstrap_replicates", "BOOT_KINDS"]
