"""The three registered kernel backends: ``pallas`` | ``jnp`` | ``ref``.

Each backend exposes the same user-shape API (DESIGN.md §4):

* ``query_eval(leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi)``
    -> (rel (Q, k) int32, exact (Q, A) f32)
  classifies every leaf against every query AND accumulates the exact
  covered-aggregate sums in the same pass (the MXU matmul of the Pallas
  kernel; the engine consumes ``exact`` instead of recomputing it).
* ``stratified_moments(sample_c, sample_a, sample_valid, q_lo, q_hi)``
    -> (k_pred, s_sum, s_sumsq), each (Q, k) f32
  per-(query, stratum) relevant-sample moments over the synopsis-shaped
  (k, s, ·) sample arrays.
* ``stratified_moments_flat(...)`` — the flattened (S, ·) calling
  convention kept for the public ``ops.py`` wrappers.
* ``segment_reduce(values, seg_ids, k)`` -> (k, 5) per-segment aggregates.
* ``sample_extremes(...)`` -> per-(query, stratum) relevant-sample MIN/MAX
  (shared broadcast implementation — no Pallas kernel exists for it yet).

``pallas`` runs the TPU kernels (interpret mode off-TPU), ``ref`` runs the
kernel-convention oracles of ``ref.py`` through the identical padding
adapters, and ``jnp`` is the broadcast formulation that is fastest on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref as _ref
from .registry import register_backend
from .bootstrap import (bootstrap_moments as _boot_pallas, auto_block_r)
from .route import (route_multid_dense as _route_dense,
                    route_multid_pallas as _route_pallas,
                    auto_block_k, ROW_TILE)
from .segment_reduce import (segment_reduce as _segment_reduce_pallas,
                             weighted_segment_reduce as _wseg_pallas,
                             auto_block_n)
from .stratified_estimate import (stratified_moments as _strat_pallas,
                                  stratified_weighted_moments as _wstrat_pallas)
from .query_eval import query_eval as _query_eval_pallas

D_PAD = 8

# Relation codes — must match core.types (kernels stay import-free of core).
REL_NONE, REL_PARTIAL, REL_COVER = 0, 1, 2

_BIG = jnp.float32(3.4e38)


def interpret_mode() -> bool:
    """Whether the ``pallas`` backend runs its kernels in the Pallas
    interpreter: everywhere but on a TPU, where they are compiled."""
    return jax.default_backend() != "tpu"


def _pad_axis(x: jnp.ndarray, mult: int, axis: int, fill=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _transpose_coords(c: jnp.ndarray) -> jnp.ndarray:
    """(N, d) -> (D_PAD, N) with padded dims filled so they never filter."""
    c_t = jnp.swapaxes(c, 0, 1)
    return _pad_axis(c_t, D_PAD, 0, fill=0.0)


# --------------------------------------------------------------------------
# Pure-jnp broadcast formulations (also the semantic references for the
# kernels; re-exported by core.estimators for compatibility)
# --------------------------------------------------------------------------

def classify_leaves(leaf_lo, leaf_hi, q_lo, q_hi):
    """(k,d) boxes vs (Q,d) rectangles -> (Q,k) int32 relation codes."""
    nonempty = jnp.all(leaf_lo <= leaf_hi, axis=-1)          # (k,)
    ql = q_lo[:, None, :]                                    # (Q,1,d)
    qh = q_hi[:, None, :]
    disjoint = (jnp.any(qh < leaf_lo[None], axis=-1)
                | jnp.any(ql > leaf_hi[None], axis=-1)
                | ~nonempty[None])
    cover = (jnp.all(ql <= leaf_lo[None], axis=-1)
             & jnp.all(leaf_hi[None] <= qh, axis=-1)
             & nonempty[None])
    return jnp.where(cover, REL_COVER,
                     jnp.where(disjoint, REL_NONE, REL_PARTIAL)).astype(jnp.int32)


def sample_moments(sample_c, sample_a, sample_valid, q_lo, q_hi):
    """Per-(query, stratum) relevant-sample moments.

    Returns (k_pred, s_sum, s_sumsq), each (Q, k) f32. Pure-jnp reference
    semantics for the `stratified_estimate` Pallas kernel.
    """
    # pred: (Q, k, s)
    inside = (jnp.all(q_lo[:, None, None, :] <= sample_c[None], axis=-1)
              & jnp.all(sample_c[None] <= q_hi[:, None, None, :], axis=-1))
    pred = (inside & sample_valid[None]).astype(jnp.float32)
    a = sample_a.astype(jnp.float32)[None]
    k_pred = jnp.sum(pred, axis=-1)
    s_sum = jnp.sum(pred * a, axis=-1)
    s_sumsq = jnp.sum(pred * a * a, axis=-1)
    return k_pred, s_sum, s_sumsq


def tree_sum_last(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-deterministic pairwise reduction over the trailing axis.

    ``jnp.sum`` leaves the accumulation strategy to the XLA reduce
    emitter, which picks different vectorizations in different fusion
    contexts — two programs summing identical values can disagree in the
    last ulp. This fixed-structure binary tree of *elementwise* adds pins
    the accumulation order in the graph itself (elementwise ops are
    bit-deterministic regardless of surrounding fusion), which is what the
    fused-vs-scan bootstrap bit-identity contract (DESIGN.md §10) rests
    on. Same flops as a linear sum; zero-padding to the next power of two
    is exact (x + 0.0 == x in f32 for all finite x)."""
    n = x.shape[-1]
    pow2 = 1 << max(n - 1, 0).bit_length()
    if pow2 != n:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pow2 - n)]
        x = jnp.pad(x, widths)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]          # contiguous halves: SIMD-friendly
    return x[..., 0]


def weighted_sample_moments(sample_c, sample_a, sample_valid, weights,
                            q_lo, q_hi):
    """Per-(query, stratum) weighted relevant-sample moments.

    ``weights`` (k, s) f32 resample weights (the uncertainty subsystem's
    Poisson bootstrap); invalid slots are masked regardless of weight.
    Returns (w_pred, ws_sum, ws_sumsq), each (Q, k) f32. The slot
    reduction is the fixed-order :func:`tree_sum_last`, so one replicate
    computed here bit-matches the same replicate inside the fused
    ``bootstrap_moments`` block."""
    inside = (jnp.all(q_lo[:, None, None, :] <= sample_c[None], axis=-1)
              & jnp.all(sample_c[None] <= q_hi[:, None, None, :], axis=-1))
    pred = (inside & sample_valid[None]).astype(jnp.float32)
    pred = pred * weights.astype(jnp.float32)[None]
    a = sample_a.astype(jnp.float32)[None]
    w_pred = tree_sum_last(pred)
    ws_sum = tree_sum_last(pred * a)
    ws_sumsq = tree_sum_last(pred * a * a)
    return w_pred, ws_sum, ws_sumsq


def _flat_leaf_ids(sample_valid: jnp.ndarray) -> jnp.ndarray:
    k, s = sample_valid.shape
    return jnp.where(sample_valid.reshape(k * s),
                     jnp.repeat(jnp.arange(k, dtype=jnp.int32), s), -1)


# --------------------------------------------------------------------------
# Backend classes
# --------------------------------------------------------------------------

class KernelBackend:
    """Uniform op surface; subclasses fill in the hot paths."""

    name = "base"

    # -- classification + exact accumulation --------------------------------
    def query_eval(self, leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi,
                   bq: int = 128, bk: int = 128):
        raise NotImplementedError

    # -- stratified moments --------------------------------------------------
    def stratified_moments(self, sample_c, sample_a, sample_valid,
                           q_lo, q_hi, **kw):
        k, s, d = sample_c.shape
        mom = self.stratified_moments_flat(
            sample_c.reshape(k * s, d), sample_a.reshape(k * s),
            _flat_leaf_ids(sample_valid), q_lo, q_hi, k, **kw)
        return mom[..., 0], mom[..., 1], mom[..., 2]

    def stratified_moments_flat(self, sample_c, sample_a, sample_leaf,
                                q_lo, q_hi, k: int, bq: int = 128,
                                bk: int = 128, bs: int = 1024):
        raise NotImplementedError

    # -- weighted stratified moments (uncertainty / bootstrap path) ----------
    def weighted_moments(self, sample_c, sample_a, sample_valid, weights,
                         q_lo, q_hi, **kw):
        k, s, d = sample_c.shape
        w = jnp.where(sample_valid, weights.astype(jnp.float32), 0.0)
        mom = self.weighted_moments_flat(
            sample_c.reshape(k * s, d), sample_a.reshape(k * s),
            _flat_leaf_ids(sample_valid), w.reshape(k * s), q_lo, q_hi, k,
            **kw)
        return mom[..., 0], mom[..., 1], mom[..., 2]

    def weighted_moments_flat(self, sample_c, sample_a, sample_leaf, weights,
                              q_lo, q_hi, k: int, bq: int = 128,
                              bk: int = 128, bs: int = 1024):
        raise NotImplementedError

    # -- fused bootstrap replicate moments (DESIGN.md §10) -------------------
    # One op for the whole (R, 3, Q, k) replicate-moment block; the default
    # is the per-replicate oracle loop (structurally bit-identical to the
    # scan path), which `pallas`/`jnp` replace with genuinely fused
    # formulations. ``br=None`` auto-sizes the replicate block.
    def bootstrap_moments(self, sample_c, sample_a, sample_valid, weights,
                          q_lo, q_hi, **kw):
        """``weights`` (R, k, s) resample weights -> (R, 3, Q, k) f32
        [sum w*pred, sum w*pred*a, sum w*pred*a^2] per replicate."""
        k, s, d = sample_c.shape
        R = weights.shape[0]
        w = jnp.where(sample_valid[None], weights.astype(jnp.float32), 0.0)
        return self.bootstrap_moments_flat(
            sample_c.reshape(k * s, d), sample_a.reshape(k * s),
            _flat_leaf_ids(sample_valid), w.reshape(R, k * s),
            q_lo, q_hi, k, **kw)

    def bootstrap_moments_flat(self, sample_c, sample_a, sample_leaf,
                               weights, q_lo, q_hi, k: int,
                               br: int | None = None, bq: int = 128,
                               bk: int = 128, bs: int = 1024):
        # Oracle default: the scan path's per-replicate op, stacked.
        return jnp.stack([
            jnp.moveaxis(self.weighted_moments_flat(
                sample_c, sample_a, sample_leaf, weights[r], q_lo, q_hi, k,
                bq=bq, bk=bk, bs=bs), -1, 0)
            for r in range(weights.shape[0])])

    # -- multi-D batch routing (streaming ingest hot path) -------------------
    def route_multid(self, leaf_lo, leaf_hi, c, bk: int | None = None):
        """Nearest-leaf routing for (B, d) rows against (k, d) boxes.
        Returns (leaf ids (B,) int32, selected L1 distance (B,) f32).
        Default: the dense (B, k) distance-matrix oracle."""
        return _route_dense(leaf_lo, leaf_hi, c)

    # -- segment reduction ---------------------------------------------------
    # ``bn=None`` sizes the row block to the input (auto_block_n) — the
    # streaming ingest path reduces small batches where the build-path
    # default of 2048 would pad 2-4x.
    def segment_reduce(self, values, seg_ids, k: int, bn: int | None = 2048,
                       bk: int = 256):
        bn = bn or auto_block_n(values.shape[0])
        v = _pad_axis(values.astype(jnp.float32), bn, 0)
        ids = _pad_axis(seg_ids.astype(jnp.int32), bn, 0, fill=-1)
        return _ref.segment_reduce_ref(v, ids, k)[:, :5]

    def weighted_segment_reduce(self, values, weights, seg_ids, k: int,
                                bn: int | None = 2048, bk: int = 256):
        """Per-segment [sum w*v, sum w*v^2, sum w]. Returns (k, 3)."""
        bn = bn or auto_block_n(values.shape[0])
        v = _pad_axis(values.astype(jnp.float32), bn, 0)
        w = _pad_axis(weights.astype(jnp.float32), bn, 0)
        ids = _pad_axis(seg_ids.astype(jnp.int32), bn, 0, fill=-1)
        return _ref.weighted_segment_reduce_ref(v, w, ids, k)

    # -- relevant-sample extremes (shared broadcast implementation) ----------
    def sample_extremes(self, sample_c, sample_a, sample_valid, q_lo, q_hi):
        """Per-(query, stratum) MIN/MAX over relevant samples; irrelevant
        strata read +BIG / -BIG. Returns (samp_min, samp_max), each (Q, k)."""
        inside = (jnp.all(q_lo[:, None, None, :] <= sample_c[None], axis=-1)
                  & jnp.all(sample_c[None] <= q_hi[:, None, None, :], axis=-1)
                  & sample_valid[None])
        a = sample_a.astype(jnp.float32)[None]
        samp_min = jnp.min(jnp.where(inside, a, _BIG), axis=-1)
        samp_max = jnp.max(jnp.where(inside, a, -_BIG), axis=-1)
        return samp_min, samp_max


def _pad_query_eval_inputs(leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi, bq, bk):
    # Empty-leaf boxes (lo > hi) must stay inverted after padding.
    lo_t = _pad_axis(_transpose_coords(leaf_lo.astype(jnp.float32)), bk, 1,
                     fill=1.0)
    hi_t = _pad_axis(_transpose_coords(leaf_hi.astype(jnp.float32)), bk, 1,
                     fill=-1.0)
    agg = _pad_axis(_pad_axis(leaf_agg.astype(jnp.float32), 8, 1), bk, 0)
    qlo_t = _pad_axis(_transpose_coords(q_lo.astype(jnp.float32)), bq, 1,
                      fill=1.0)
    qhi_t = _pad_axis(_transpose_coords(q_hi.astype(jnp.float32)), bq, 1,
                      fill=-1.0)
    return lo_t, hi_t, agg, qlo_t, qhi_t


def _pad_moment_inputs(sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs):
    c_t = _pad_axis(_transpose_coords(sample_c.astype(jnp.float32)), bs, 1)
    a = _pad_axis(sample_a.astype(jnp.float32), bs, 0)
    leaf = _pad_axis(sample_leaf.astype(jnp.int32), bs, 0, fill=-1)
    qlo_t = _pad_axis(_transpose_coords(q_lo.astype(jnp.float32)), bq, 1,
                      fill=1.0)
    qhi_t = _pad_axis(_transpose_coords(q_hi.astype(jnp.float32)), bq, 1,
                      fill=-1.0)
    return c_t, a, leaf, qlo_t, qhi_t


@register_backend("pallas")
class PallasBackend(KernelBackend):
    """Pallas TPU kernels (compiled on TPU, interpret mode elsewhere)."""

    def query_eval(self, leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi,
                   bq: int = 128, bk: int = 128):
        k, d = leaf_lo.shape
        Q, A = q_lo.shape[0], leaf_agg.shape[1]
        lo_t, hi_t, agg, qlo_t, qhi_t = _pad_query_eval_inputs(
            leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi, bq, bk)
        rel, exact = _query_eval_pallas(lo_t, hi_t, agg, qlo_t, qhi_t, d,
                                        bq=bq, bk=bk, interpret=interpret_mode())
        return rel[:Q, :k], exact[:Q, :A]

    def stratified_moments_flat(self, sample_c, sample_a, sample_leaf,
                                q_lo, q_hi, k: int, bq: int = 128,
                                bk: int = 128, bs: int = 1024):
        d = sample_c.shape[1]
        Q = q_lo.shape[0]
        c_t, a, leaf, qlo_t, qhi_t = _pad_moment_inputs(
            sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs)
        k_pad = k + ((-k) % bk)
        out = _strat_pallas(c_t, a, leaf, qlo_t, qhi_t, k_pad, d,
                            bq=bq, bk=bk, bs=bs, interpret=interpret_mode())
        return jnp.moveaxis(out[:, :Q, :k], 0, -1)

    def weighted_moments_flat(self, sample_c, sample_a, sample_leaf, weights,
                              q_lo, q_hi, k: int, bq: int = 128,
                              bk: int = 128, bs: int = 1024):
        d = sample_c.shape[1]
        Q = q_lo.shape[0]
        c_t, a, leaf, qlo_t, qhi_t = _pad_moment_inputs(
            sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs)
        w = _pad_axis(weights.astype(jnp.float32), bs, 0)
        k_pad = k + ((-k) % bk)
        out = _wstrat_pallas(c_t, a, leaf, w, qlo_t, qhi_t, k_pad, d,
                             bq=bq, bk=bk, bs=bs, interpret=interpret_mode())
        return jnp.moveaxis(out[:, :Q, :k], 0, -1)

    def bootstrap_moments_flat(self, sample_c, sample_a, sample_leaf,
                               weights, q_lo, q_hi, k: int,
                               br: int | None = None, bq: int = 128,
                               bk: int = 128, bs: int = 1024):
        d = sample_c.shape[1]
        R = weights.shape[0]
        Q = q_lo.shape[0]
        br = br or auto_block_r(R)
        c_t, a, leaf, qlo_t, qhi_t = _pad_moment_inputs(
            sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs)
        w = _pad_axis(_pad_axis(weights.astype(jnp.float32), bs, 1), br, 0)
        k_pad = k + ((-k) % bk)
        out = _boot_pallas(c_t, a, leaf, w, qlo_t, qhi_t, k_pad, d,
                           br=br, bq=bq, bk=bk, bs=bs,
                           interpret=interpret_mode())
        return out[:R, :, :Q, :k]

    def route_multid(self, leaf_lo, leaf_hi, c, bk: int | None = None):
        b, d = c.shape
        k = leaf_lo.shape[0]
        bk = bk or auto_block_k(k)
        bb = min(ROW_TILE, 128 * ((b + 127) // 128))
        # Padding strata are inverted ±BIG boxes: unreachable distance.
        lo = _pad_axis(_pad_axis(leaf_lo.astype(jnp.float32), D_PAD, 1),
                       bk, 0, fill=_ref.POS_BIG)
        hi = _pad_axis(_pad_axis(leaf_hi.astype(jnp.float32), D_PAD, 1),
                       bk, 0, fill=_ref.NEG_BIG)
        c_t = _pad_axis(_transpose_coords(c.astype(jnp.float32)), bb, 1)
        idx, dist = _route_pallas(lo, hi, c_t, d, bb=bb, bk=bk,
                                  interpret=interpret_mode())
        return idx[0, :b], dist[0, :b]

    def segment_reduce(self, values, seg_ids, k: int, bn: int | None = 2048,
                       bk: int = 256):
        bn = bn or auto_block_n(values.shape[0])
        v = _pad_axis(values.astype(jnp.float32), bn, 0)
        ids = _pad_axis(seg_ids.astype(jnp.int32), bn, 0, fill=-1)
        k_pad = k + ((-k) % bk)
        out = _segment_reduce_pallas(v, ids, k_pad, bn=bn, bk=bk,
                                     interpret=interpret_mode())
        return out[:k, :5]

    def weighted_segment_reduce(self, values, weights, seg_ids, k: int,
                                bn: int | None = 2048, bk: int = 256):
        bn = bn or auto_block_n(values.shape[0])
        v = _pad_axis(values.astype(jnp.float32), bn, 0)
        w = _pad_axis(weights.astype(jnp.float32), bn, 0)
        ids = _pad_axis(seg_ids.astype(jnp.int32), bn, 0, fill=-1)
        k_pad = k + ((-k) % bk)
        out = _wseg_pallas(v, w, ids, k_pad, bn=bn, bk=bk,
                           interpret=interpret_mode())
        return out[:k, :3]


@register_backend("ref")
class RefBackend(KernelBackend):
    """The ref.py oracles through the exact Pallas padding adapters —
    value-identical to ``pallas`` without the interpreter overhead."""

    def query_eval(self, leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi,
                   bq: int = 128, bk: int = 128):
        k, d = leaf_lo.shape
        Q, A = q_lo.shape[0], leaf_agg.shape[1]
        lo_t, hi_t, agg, qlo_t, qhi_t = _pad_query_eval_inputs(
            leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi, bq, bk)
        rel, exact = _ref.query_eval_ref(lo_t, hi_t, agg, qlo_t, qhi_t, d)
        return rel[:Q, :k], exact[:Q, :A]

    def stratified_moments_flat(self, sample_c, sample_a, sample_leaf,
                                q_lo, q_hi, k: int, bq: int = 128,
                                bk: int = 128, bs: int = 1024):
        d = sample_c.shape[1]
        Q = q_lo.shape[0]
        c_t, a, leaf, qlo_t, qhi_t = _pad_moment_inputs(
            sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs)
        return _ref.stratified_moments_ref(c_t, a, leaf, qlo_t, qhi_t, k, d)[:Q]

    def weighted_moments_flat(self, sample_c, sample_a, sample_leaf, weights,
                              q_lo, q_hi, k: int, bq: int = 128,
                              bk: int = 128, bs: int = 1024):
        d = sample_c.shape[1]
        Q = q_lo.shape[0]
        c_t, a, leaf, qlo_t, qhi_t = _pad_moment_inputs(
            sample_c, sample_a, sample_leaf, q_lo, q_hi, bq, bs)
        w = _pad_axis(weights.astype(jnp.float32), bs, 0)
        return _ref.stratified_weighted_moments_ref(
            c_t, a, leaf, w, qlo_t, qhi_t, k, d)[:Q]


@register_backend("jnp")
class JnpBackend(KernelBackend):
    """Broadcast jnp formulation — the CPU-fast default off-TPU."""

    def query_eval(self, leaf_lo, leaf_hi, leaf_agg, q_lo, q_hi,
                   bq: int = 128, bk: int = 128):
        rel = classify_leaves(leaf_lo, leaf_hi, q_lo, q_hi)
        cover = (rel == REL_COVER).astype(jnp.float32)
        exact = cover @ leaf_agg.astype(jnp.float32)
        return rel, exact

    def stratified_moments(self, sample_c, sample_a, sample_valid,
                           q_lo, q_hi, **kw):
        return sample_moments(sample_c, sample_a, sample_valid, q_lo, q_hi)

    def weighted_moments(self, sample_c, sample_a, sample_valid, weights,
                         q_lo, q_hi, **kw):
        return weighted_sample_moments(sample_c, sample_a, sample_valid,
                                       weights, q_lo, q_hi)

    def bootstrap_moments(self, sample_c, sample_a, sample_valid, weights,
                          q_lo, q_hi, br: int | None = None, **kw):
        # Replicate-tiled broadcast-reduce: the predicate mask (the
        # w-independent half of `weighted_sample_moments`) is computed once
        # and reused by every replicate; a lax.scan walks (br, k, s) weight
        # tiles so the (br, Q, k, s) product is the largest temporary. The
        # per-replicate arithmetic (elementwise products + trailing-axis
        # sums) is exactly the scan path's, so replicates are bit-identical
        # to per-replicate `weighted_moments` calls.
        k, s, _ = sample_c.shape
        Q = q_lo.shape[0]
        R = weights.shape[0]
        br = br or auto_block_r(R)
        w = jnp.where(sample_valid[None], weights.astype(jnp.float32), 0.0)
        pad = (-R) % br
        if pad:
            w = jnp.concatenate(
                [w, jnp.zeros((pad, k, s), jnp.float32)], axis=0)
        inside = (jnp.all(q_lo[:, None, None, :] <= sample_c[None], axis=-1)
                  & jnp.all(sample_c[None] <= q_hi[:, None, None, :],
                            axis=-1))
        pred = (inside & sample_valid[None]).astype(jnp.float32)  # (Q,k,s)
        a = sample_a.astype(jnp.float32)[None, None]              # (1,1,k,s)

        def step(carry, wt):                                      # (br,k,s)
            p = pred[None] * wt[:, None]                          # (br,Q,k,s)
            return carry, jnp.stack(
                [tree_sum_last(p), tree_sum_last(p * a),
                 tree_sum_last(p * a * a)], axis=1)               # (br,3,Q,k)

        _, out = jax.lax.scan(step, 0, w.reshape(-1, br, k, s))
        return out.reshape(-1, 3, Q, k)[:R]

    def weighted_segment_reduce(self, values, weights, seg_ids, k: int,
                                bn: int | None = 2048, bk: int = 256):
        # Scatter formulation, mirroring segment_reduce: O(N) work with a
        # spill slot for padding/out-of-range ids.
        v = values.astype(jnp.float32)
        w = weights.astype(jnp.float32)
        ids = jnp.where((seg_ids >= 0) & (seg_ids < k),
                        seg_ids.astype(jnp.int32), k)
        s = jnp.zeros(k + 1, jnp.float32).at[ids].add(w * v)
        ssq = jnp.zeros(k + 1, jnp.float32).at[ids].add(w * v * v)
        wsum = jnp.zeros(k + 1, jnp.float32).at[ids].add(w)
        return jnp.stack([s, ssq, wsum], axis=-1)[:k]

    def segment_reduce(self, values, seg_ids, k: int, bn: int | None = 2048,
                       bk: int = 256):
        # Scatter formulation: O(N) work instead of the O(N*k) one-hot
        # matmul — the right shape for CPU and for the streaming ingest
        # hot path, where N is a small row batch. Padding rows (-1) and
        # out-of-range ids drop into a spill slot that is sliced away.
        v = values.astype(jnp.float32)
        ids = jnp.where((seg_ids >= 0) & (seg_ids < k),
                        seg_ids.astype(jnp.int32), k)
        s = jnp.zeros(k + 1, jnp.float32).at[ids].add(v)
        ssq = jnp.zeros(k + 1, jnp.float32).at[ids].add(v * v)
        cnt = jnp.zeros(k + 1, jnp.float32).at[ids].add(1.0)
        vmin = jnp.full(k + 1, _ref.POS_BIG, jnp.float32).at[ids].min(v)
        vmax = jnp.full(k + 1, _ref.NEG_BIG, jnp.float32).at[ids].max(v)
        return jnp.stack([s, ssq, cnt, vmin, vmax], axis=-1)[:k]

    def stratified_moments_flat(self, sample_c, sample_a, sample_leaf,
                                q_lo, q_hi, k: int, bq: int = 128,
                                bk: int = 128, bs: int = 1024):
        pred = (jnp.all(q_lo[:, None, :] <= sample_c[None], axis=-1)
                & jnp.all(sample_c[None] <= q_hi[:, None, :], axis=-1)
                & (sample_leaf >= 0)[None])
        predf = pred.astype(jnp.float32)
        a = sample_a.astype(jnp.float32)
        onehot = (sample_leaf[:, None] == jnp.arange(k, dtype=jnp.int32)[None]
                  ).astype(jnp.float32)            # (S, k)
        kp = predf @ onehot
        sm = (predf * a[None]) @ onehot
        sq = (predf * (a * a)[None]) @ onehot
        return jnp.stack([kp, sm, sq], axis=-1)

    def weighted_moments_flat(self, sample_c, sample_a, sample_leaf, weights,
                              q_lo, q_hi, k: int, bq: int = 128,
                              bk: int = 128, bs: int = 1024):
        pred = (jnp.all(q_lo[:, None, :] <= sample_c[None], axis=-1)
                & jnp.all(sample_c[None] <= q_hi[:, None, :], axis=-1)
                & (sample_leaf >= 0)[None])
        predf = pred.astype(jnp.float32) * weights.astype(jnp.float32)[None]
        a = sample_a.astype(jnp.float32)
        onehot = (sample_leaf[:, None] == jnp.arange(k, dtype=jnp.int32)[None]
                  ).astype(jnp.float32)            # (S, k)
        kp = predf @ onehot
        sm = (predf * a[None]) @ onehot
        sq = (predf * (a * a)[None]) @ onehot
        return jnp.stack([kp, sm, sq], axis=-1)


__all__ = ["KernelBackend", "PallasBackend", "RefBackend", "JnpBackend",
           "classify_leaves", "sample_moments", "weighted_sample_moments",
           "interpret_mode", "D_PAD"]
