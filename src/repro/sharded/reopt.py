"""Mesh-parallel drift re-optimization (DESIGN.md §11, paper §4.5).

Same loop as :mod:`repro.streaming.policy`, scaled out: the drift signals
(``staleness``/``oob_frac``) accumulate shard-locally inside the sharded
ingestor; when a :class:`DriftPolicy` trips, the DP runs over (at most
``OPT_SAMPLES`` of) the *collectively merged* reservoir pool (its
per-shard partial moments were composed by the O(k) merge — no raw rows
move), the fresh cuts broadcast to every shard as a static skeleton, and
the rebuild streams the caller's rows through the data-parallel fill. The expensive O(N) phase is the
fill, and it is the part that scales with the mesh.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..streaming.policy import DriftPolicy, pool_thresholds
from .build import fill_skeleton, thresholds_to_boxes
from .ingest import ShardedIngestor


def reoptimize_cuts_sharded(ing: ShardedIngestor, k: int | None = None
                            ) -> tuple[jnp.ndarray, float]:
    """DP cuts over the merged (all-shard) reservoir pool, at most
    ``OPT_SAMPLES`` of it (``streaming.policy.pool_thresholds``). 1-D only
    — KD synopses rebuild through ``build_synopsis_sharded``. Inherits the
    equal-capacity-pool caveat of ``streaming.policy.reoptimize_cuts``."""
    merged = ing.as_synopsis()
    if merged.d != 1:
        raise ValueError("sharded re-optimization supports 1-D synopses; "
                         "rebuild KD synopses with build_synopsis_sharded")
    return pool_thresholds(merged.sample_c, merged.sample_a,
                           merged.sample_valid, k or merged.num_leaves)


def reoptimize_sharded(ing: ShardedIngestor, c, a, *, k: int | None = None,
                       seed: int = 0, batch_rows: int = 1 << 16
                       ) -> tuple[ShardedIngestor, dict]:
    """Full mesh-parallel rebuild: merged-pool DP -> broadcast cuts ->
    shard-local fill. ``c``/``a`` are the current full dataset (base +
    streamed rows, owned by the caller, already sharded or shardable).
    Returns (fresh committed ingestor on the same mesh, report)."""
    thr, vmax = reoptimize_cuts_sharded(ing, k)
    route_lo, route_hi = thresholds_to_boxes(np.asarray(thr))
    report = {"k": int(route_lo.shape[0]),
              "sample_max_variance": vmax,
              "thresholds": np.asarray(thr),
              "n_shards": ing.n_shards,
              "staleness_at_reopt": ing.staleness(),
              "oob_frac_at_reopt": ing.oob_frac()}
    new_ing = fill_skeleton(c, a, route_lo, route_hi, mesh=ing.mesh,
                            s_cap=ing.base.sample_c.shape[1],
                            seed=seed + 1, backend=ing._backend,
                            batch_rows=batch_rows)
    return new_ing, report


def maybe_reoptimize_sharded(policy: DriftPolicy, ing: ShardedIngestor,
                             c, a, **kw
                             ) -> tuple[ShardedIngestor, dict | None]:
    """Sharded counterpart of ``DriftPolicy.maybe_reoptimize`` (the policy
    itself is reused as-is — its drift signals are duck-typed)."""
    if not policy.should_reoptimize(ing):
        return ing, None
    return reoptimize_sharded(ing, c, a, **kw)


__all__ = ["reoptimize_cuts_sharded", "reoptimize_sharded",
           "maybe_reoptimize_sharded"]
