"""Public wrappers for the PASS kernel ops, dispatched through the backend
registry (DESIGN.md §4).

Each op takes an optional ``backend`` name (``pallas | jnp | ref``) for
per-call selection; ``None`` resolves via ``REPRO_KERNEL_BACKEND`` or the
platform default. Shape adaptation (padding to block multiples, coordinate
transposition to the lane-aligned (d_pad, ·) layout) lives with the backends
in ``backends.py``; every backend is shape/value-equivalent to the `ref.py`
oracles and the kernel test suite sweeps shapes and dtypes against them.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import backends as _backends  # noqa: F401  (registers the backends)
from .registry import get_backend, default_backend_name

D_PAD = _backends.D_PAD


def backend() -> str:
    """Resolved default backend name (kept for compatibility)."""
    return default_backend_name()


def segment_reduce_op(values: jnp.ndarray, seg_ids: jnp.ndarray, k: int,
                      bn: int = 2048, bk: int = 256,
                      backend: str | None = None) -> jnp.ndarray:
    """Per-segment [sum, sumsq, count, min, max] over rows. Returns (k, 5)."""
    return get_backend(backend).segment_reduce(values, seg_ids, k,
                                               bn=bn, bk=bk)


def stratified_moments_op(sample_c: jnp.ndarray, sample_a: jnp.ndarray,
                          sample_leaf: jnp.ndarray, q_lo: jnp.ndarray,
                          q_hi: jnp.ndarray, k: int,
                          bq: int = 128, bk: int = 128, bs: int = 1024,
                          backend: str | None = None) -> jnp.ndarray:
    """Flattened-sample moments. sample_c (S, d), sample_a (S,), sample_leaf
    (S,) int32 (-1 pad); q_lo/q_hi (Q, d). Returns (Q, k, 3)."""
    return get_backend(backend).stratified_moments_flat(
        sample_c, sample_a, sample_leaf, q_lo, q_hi, k, bq=bq, bk=bk, bs=bs)


def weighted_segment_reduce_op(values: jnp.ndarray, weights: jnp.ndarray,
                               seg_ids: jnp.ndarray, k: int,
                               bn: int | None = 2048, bk: int = 256,
                               backend: str | None = None) -> jnp.ndarray:
    """Per-segment weighted sums [sum w*v, sum w*v^2, sum w]. Returns (k, 3).
    Padding rows (seg id -1) must carry weight 0 on the matmul backends;
    the scatter backend drops them regardless."""
    return get_backend(backend).weighted_segment_reduce(values, weights,
                                                        seg_ids, k,
                                                        bn=bn, bk=bk)


def weighted_moments_op(sample_c: jnp.ndarray, sample_a: jnp.ndarray,
                        sample_leaf: jnp.ndarray, weights: jnp.ndarray,
                        q_lo: jnp.ndarray, q_hi: jnp.ndarray, k: int,
                        bq: int = 128, bk: int = 128, bs: int = 1024,
                        backend: str | None = None) -> jnp.ndarray:
    """Flattened-sample weighted moments (bootstrap resample pass).
    sample_c (S, d), sample_a/weights (S,), sample_leaf (S,) int32 (-1 pad,
    weight 0); q_lo/q_hi (Q, d). Returns (Q, k, 3)."""
    return get_backend(backend).weighted_moments_flat(
        sample_c, sample_a, sample_leaf, weights, q_lo, q_hi, k,
        bq=bq, bk=bk, bs=bs)


def bootstrap_moments_op(sample_c: jnp.ndarray, sample_a: jnp.ndarray,
                         sample_valid: jnp.ndarray, weights: jnp.ndarray,
                         q_lo: jnp.ndarray, q_hi: jnp.ndarray,
                         br: int | None = None,
                         backend: str | None = None) -> jnp.ndarray:
    """Fused bootstrap replicate moments (DESIGN.md §10): all R replicates'
    weighted relevant-sample moments in one op. sample_c (k, s, d),
    sample_a/sample_valid (k, s), weights (R, k, s) resample weights;
    q_lo/q_hi (Q, d). ``br=None`` auto-sizes the replicate block.
    Returns (R, 3, Q, k) = [sum w*pred, sum w*pred*a, sum w*pred*a^2]."""
    return get_backend(backend).bootstrap_moments(
        sample_c, sample_a, sample_valid, weights, q_lo, q_hi, br=br)


def route_multid_op(leaf_lo: jnp.ndarray, leaf_hi: jnp.ndarray,
                    c: jnp.ndarray, bk: int | None = None,
                    backend: str | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Nearest-leaf batch routing (streaming ingest, d > 1): leaf whose box
    contains (distance 0) or is L1-nearest to each row; lowest leaf id wins
    ties. leaf_lo/leaf_hi (k, d); c (B, d). Returns (leaf (B,) int32,
    distance (B,) f32). The ``pallas`` backend streams leaf tiles with an
    online (min, argmin) pair — no (B, k) matrix; others use the dense
    oracle."""
    return get_backend(backend).route_multid(leaf_lo, leaf_hi, c, bk=bk)


def query_eval_op(leaf_lo: jnp.ndarray, leaf_hi: jnp.ndarray,
                  leaf_agg: jnp.ndarray, q_lo: jnp.ndarray,
                  q_hi: jnp.ndarray, bq: int = 128, bk: int = 128,
                  backend: str | None = None
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Classify leaves vs queries and accumulate exact covered aggregates.

    leaf_lo/leaf_hi (k, d); leaf_agg (k, A<=8); q_lo/q_hi (Q, d).
    Returns (rel (Q, k) int32, exact (Q, A) f32)."""
    return get_backend(backend).query_eval(leaf_lo, leaf_hi, leaf_agg,
                                           q_lo, q_hi, bq=bq, bk=bk)


__all__ = ["segment_reduce_op", "weighted_segment_reduce_op",
           "stratified_moments_op", "weighted_moments_op",
           "bootstrap_moments_op", "route_multid_op", "query_eval_op",
           "backend"]
