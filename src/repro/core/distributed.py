"""Distributed PASS: pod-scale synopsis build and query serving.

Build (paper §3.2 at cluster scale, DESIGN.md §3/§4):
  rows are sharded over the data-parallel mesh axes; each device computes
  *local* per-leaf aggregates with the segment_reduce kernel and a single
  (k, 5) ``psum`` merges them (the mergeable-summaries property — SUM/COUNT
  add, MIN/MAX combine). Collective bytes are O(k), independent of N, so the
  build weak-scales to arbitrarily many nodes.

Serve: two modes (both shard_map):
  * shard_queries  — the synopsis is replicated (it is O(K) small by
    design); the query batch shards across every device; zero collectives
    in the hot loop.
  * shard_samples  — for huge-K synopses the per-leaf samples shard across
    the 'model' axis; per-device partial moments are psum'd before the
    estimator epilogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .types import Synopsis, QueryBatch
from ..kernels import ops as kops


def _auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with Auto axis types.

    ``jax.make_mesh`` returns Explicit axes, under which the shard bodies'
    closed-over synopsis arrays and jnp scatters would need sharding-in-
    types annotations; everything here is laid out by the in/out specs."""
    return Mesh(mesh.devices, mesh.axis_names)


# --------------------------------------------------------------------------
# Distributed build
# --------------------------------------------------------------------------

def local_leaf_aggregates(values: jnp.ndarray, assign: jnp.ndarray, k: int
                          ) -> jnp.ndarray:
    """(k, 5) aggregates of this shard's rows (kernel-backed)."""
    return kops.segment_reduce_op(values, assign, k)


def build_leaf_aggregates(mesh: Mesh, values: jnp.ndarray,
                          assign: jnp.ndarray, k: int,
                          data_axes=("data",)) -> jnp.ndarray:
    """Global (k, 5) leaf aggregates over rows sharded on `data_axes`.

    ``values``/``assign`` are global arrays laid out with the row dim
    sharded; the psum merges the mergeable summaries.
    """
    def shard_fn(v, a):
        local = local_leaf_aggregates(v, a, k)
        sums = jax.lax.psum(local[:, 0:3], data_axes)
        mins = -jax.lax.pmax(-local[:, 3], data_axes)
        maxs = jax.lax.pmax(local[:, 4], data_axes)
        return jnp.concatenate([sums, mins[:, None], maxs[:, None]], axis=1)

    row_spec = P(data_axes)
    return jax.shard_map(shard_fn, mesh=_auto_axes(mesh),
                         in_specs=(row_spec, row_spec),
                         out_specs=P())(values, assign)


# --------------------------------------------------------------------------
# Distributed serving
# --------------------------------------------------------------------------

def serve_queries_sharded(mesh: Mesh, syn: Synopsis, queries: QueryBatch,
                          kind: str = "sum", lam: float = 2.576):
    """shard_queries mode: replicate synopsis, shard the query batch over
    every mesh axis. Ragged batches are handled internally: Q pads up to a
    multiple of the device count with degenerate point queries whose rows
    are sliced off the result, so callers never see the padding."""
    from ..api import PassEngine, ServingConfig
    eng = PassEngine(syn, serving=ServingConfig(kinds=(kind,), lam=lam))
    axes = tuple(mesh.axis_names)
    q = queries.num_queries
    n_dev = int(mesh.size)
    q_lo = pad_to(queries.lo, n_dev, axis=0)
    q_hi = pad_to(queries.hi, n_dev, axis=0)

    def shard_fn(q_lo, q_hi):
        res = eng.answer(QueryBatch(q_lo, q_hi))[kind]
        return res.estimate, res.ci_half, res.lower, res.upper

    qspec = P(axes)
    est, ci, lo, hi = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh), in_specs=(qspec, qspec),
        out_specs=(qspec,) * 4)(q_lo, q_hi)
    return est[:q], ci[:q], lo[:q], hi[:q]


def serve_samples_sharded(mesh: Mesh, syn: Synopsis, queries: QueryBatch,
                          kind: str = "sum", lam: float = 2.576,
                          sample_axis: str = "model"):
    """shard_samples mode: per-leaf samples shard on `sample_axis` (the
    per-stratum sample dim), queries replicate along it; moments are psum'd
    and the estimator epilogue runs on the combined moments.

    Returns (estimate, ci_half) — the moment-based estimates only (hard
    bounds are aggregate-only and identical to the replicated path).
    """
    from .types import REL_COVER, REL_PARTIAL
    from . import estimators as E

    k, s, d = syn.sample_c.shape

    def shard_fn(sc, sa, sv, kpl):
        # Local moments over this shard's slice of every stratum.
        kp, sm, sq = E.sample_moments(sc, sa, sv, queries.lo, queries.hi)
        kp = jax.lax.psum(kp, sample_axis)
        sm = jax.lax.psum(sm, sample_axis)
        sq = jax.lax.psum(sq, sample_axis)
        rel = E.classify_leaves(syn.leaf_lo, syn.leaf_hi,
                                queries.lo, queries.hi)
        cover = (rel == REL_COVER).astype(jnp.float32)
        partf = (rel == REL_PARTIAL).astype(jnp.float32)
        Ni = syn.n_rows.astype(jnp.float32)[None]
        Ki = jnp.maximum(kpl.astype(jnp.float32), 1.0)[None]
        agg = syn.leaf_agg
        if kind == "sum":
            exact = cover @ agg[:, 0]
            est = exact + jnp.sum(partf * Ni / Ki * sm, axis=1)
            var_phi = Ni * Ni * jnp.maximum(sq / Ki - (sm / Ki) ** 2, 0.0)
        elif kind == "count":
            exact = cover @ agg[:, 2]
            est = exact + jnp.sum(partf * Ni / Ki * kp, axis=1)
            p = kp / Ki
            var_phi = Ni * Ni * jnp.maximum(p - p * p, 0.0)
        else:
            raise ValueError("shard_samples serves sum/count")
        ci = lam * jnp.sqrt(jnp.sum(partf * var_phi / Ki, axis=1))
        return est, ci

    # Shard the per-stratum sample dim.
    in_specs = (P(None, sample_axis, None), P(None, sample_axis),
                P(None, sample_axis), P())
    # k_per_leaf refers to the GLOBAL stratum sample count.
    return jax.shard_map(shard_fn, mesh=_auto_axes(mesh),
                         in_specs=in_specs, out_specs=(P(), P()))(
        syn.sample_c, syn.sample_a, syn.sample_valid, syn.k_per_leaf)


def pad_to(x: jnp.ndarray, mult: int, axis: int = 0, fill=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


__all__ = ["local_leaf_aggregates", "build_leaf_aggregates",
           "serve_queries_sharded", "serve_samples_sharded", "pad_to"]
