"""Drift-triggered re-optimization (closing the paper's §4.5 open loop).

The paper leaves re-optimization cadence as future work; here a
:class:`DriftPolicy` thresholds two live signals of the ingestor —
``staleness`` (fraction of rows streamed since the base build) and
``oob_frac`` (fraction of streamed rows outside every leaf box, i.e. the
value distribution moved) — and, when either trips, re-runs the paper's
starred "Sampling + Discretization" (ADP) optimizer *on device*:
``dp_monotone_jnp`` over (at most ``OPT_SAMPLES`` of) the live reservoir
pool yields fresh cuts, and
the synopsis is rebuilt through the builder's shared assembly tail
(``synopsis_from_assignment``) with re-stratified samples.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp

from ..core import dp as dp_mod
from ..core.synopsis import synopsis_from_assignment
from .ingest import StreamingIngestor


# Largest pool the re-optimization DP plans on: the build's own plan size
# (``build_synopsis(opt_samples=4096)``). The DP is k sequential layers of
# binary searches over the whole pool, so at deployment sizes (k ~ 1e3,
# pools ~ 1e6 samples) an unbounded pool does not finish on a TPU.
OPT_SAMPLES = 4096


def pool_thresholds(cs, as_, valid, k: int, opt_samples: int = OPT_SAMPLES
                    ) -> tuple[jnp.ndarray, float]:
    """Monotone-DP thresholds over a reservoir pool.

    The valid samples are sorted by coordinate; a pool of m > n =
    max(opt_samples, k + 1) samples is thinned to the n of ranks
    floor(i * m / n) (a systematic sample of the sorted pool). The SUM
    oracle DP (`dp_monotone_jnp`) runs on their values and the cut ranks
    map to value thresholds. Returns ((k-1,) thresholds, sample-space max
    variance)."""
    valid = np.asarray(valid).reshape(-1)
    m = int(valid.sum())
    if m < k + 1:
        raise ValueError(
            f"reservoir pool too small to re-optimize: {m} < {k + 1}")
    cs = jnp.asarray(cs).reshape(-1)
    as_ = jnp.asarray(as_).reshape(-1)
    order = jnp.argsort(jnp.where(jnp.asarray(valid), cs, jnp.inf))[:m]
    n = min(m, max(int(opt_samples), k + 1))
    if n < m:
        order = order[(np.arange(n, dtype=np.int64) * m) // n]
    cuts, vmax = dp_mod.dp_monotone_jnp(as_[order], k)
    thr = dp_mod.cuts_to_thresholds_jnp(cs[order], cuts)
    return thr, float(vmax)


def reoptimize_cuts(ing: StreamingIngestor, k: int | None = None
                    ) -> tuple[jnp.ndarray, float]:
    """On-device re-partitioning: DP over the live reservoir pool
    (:func:`pool_thresholds`, at most ``OPT_SAMPLES`` of it). Returns
    ((k-1,) thresholds, sample-space max variance). 1-D synopses only —
    KD synopses re-optimize through ``build_synopsis(method='kd')``.

    Caveat: the pooled reservoir is a *per-stratum equal-capacity* sample,
    not a uniform sample of the current dataset — strata whose population
    grew far beyond their slot count (exactly what heavy drift produces)
    are under-represented, so the cuts are drift-adapted but not the cuts
    a fresh uniform-sample ADP run would pick. The subsequent rebuild's
    aggregates and samples are exact/fresh either way; see ROADMAP
    (reservoir-aware budget rebalancing) for the planned fix.
    """
    base = ing.base
    if base.d != 1:
        raise ValueError("on-device re-optimization supports 1-D synopses; "
                         "rebuild KD synopses with build_synopsis(method='kd')")
    state = ing.state
    return pool_thresholds(state.sample_c, state.sample_a,
                           state.sample_valid, k or base.num_leaves)


def reoptimize(ing: StreamingIngestor, c, a, *, k: int | None = None,
               s_per_leaf: int | None = None, seed: int = 0,
               backend: str | None = None, allocation: str = "neyman"
               ) -> tuple[StreamingIngestor, dict]:
    """Full drift-adapted rebuild: device DP cuts -> shared builder
    assembly (exact stats + re-stratified samples). ``c``/``a`` are the
    current full dataset (base + streamed rows, owned by the caller).
    Returns a fresh ingestor anchored on the re-optimized base plus a
    report dict.

    ``allocation`` (used only when ``s_per_leaf`` is None) decides how the
    old total sample budget is re-split across the NEW strata:

    * ``'neyman'`` (default) — per-new-stratum n_h·sigma_h weighting from
      the full dataset's exact moments, so strata the drift grew (or made
      volatile) reclaim reservoir slots from quiet ones — the
      "reservoir-aware budget rebalancing" follow-up of
      :func:`reoptimize_cuts`'s caveat;
    * ``'equal'`` — the historical behaviour: every stratum keeps the old
      uniform per-leaf capacity.
    """
    thr, vmax = reoptimize_cuts(ing, k)
    k = thr.shape[0] + 1
    c_np = np.asarray(c, dtype=np.float64).reshape(-1)
    a_np = np.asarray(a, dtype=np.float64).reshape(-1)
    assign = np.searchsorted(np.asarray(thr), c_np, side="right"
                             ).astype(np.int32)
    if s_per_leaf is None:
        cap = ing.base.sample_c.shape[1]
        if allocation == "neyman":
            from ..core.sampling import neyman_allocation
            counts = np.bincount(assign, minlength=k).astype(np.float64)
            sums = np.bincount(assign, weights=a_np, minlength=k)
            sumsqs = np.bincount(assign, weights=a_np * a_np, minlength=k)
            mean = sums / np.maximum(counts, 1.0)
            stds = np.sqrt(np.maximum(
                sumsqs / np.maximum(counts, 1.0) - mean * mean, 0.0))
            s_per_leaf = neyman_allocation(counts, stds, cap * k)
        elif allocation == "equal":
            s_per_leaf = cap
        else:
            raise ValueError(f"unknown allocation: {allocation!r}")
    # same assembly tail as build_synopsis (host f64 exact stats)
    syn, _ = synopsis_from_assignment(c_np, a_np, assign, k,
                                      s_per_leaf=s_per_leaf, seed=seed)
    report = {"k": k, "sample_max_variance": vmax,
              "thresholds": np.asarray(thr),
              "staleness_at_reopt": ing.staleness(),
              "oob_frac_at_reopt": ing.oob_frac()}
    return StreamingIngestor(syn, seed=seed + 1,
                             backend=backend or ing._backend), report


@dataclasses.dataclass
class DriftPolicy:
    """Thresholded drift triggers for the re-optimization loop.

    ``staleness_threshold``: re-optimize once this fraction of the dataset
    arrived after the base build. ``oob_threshold``: re-optimize once this
    fraction of streamed rows landed outside every leaf box (the partition
    no longer tiles the data's support). ``min_stream_rows`` suppresses
    triggers before the signals mean anything.
    """
    staleness_threshold: float = 0.25
    oob_threshold: float = 0.05
    min_stream_rows: int = 1024

    def should_reoptimize(self, ing: StreamingIngestor) -> bool:
        if ing.n_stream < self.min_stream_rows:
            return False
        return (ing.staleness() >= self.staleness_threshold
                or ing.oob_frac() >= self.oob_threshold)

    def maybe_reoptimize(self, ing: StreamingIngestor, c, a, **kw
                         ) -> tuple[StreamingIngestor, dict | None]:
        """Re-optimize iff a drift signal trips; returns (ingestor, report)
        where report is None when nothing happened."""
        if not self.should_reoptimize(ing):
            return ing, None
        return reoptimize(ing, c, a, **kw)


__all__ = ["DriftPolicy", "OPT_SAMPLES", "pool_thresholds", "reoptimize_cuts",
           "reoptimize"]
