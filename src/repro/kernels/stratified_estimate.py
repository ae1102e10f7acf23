"""Pallas TPU kernel: per-(query, stratum) relevant-sample moments.

The PASS query-serving hot path (paper §3.3 "Sample Estimation"): for every
query q and stratum i, compute over the stratum's samples
    k_pred = #relevant, s_sum = sum(a), s_sumsq = sum(a^2).

TPU mapping (DESIGN.md §3): the predicate mask pred (BQ, BS) is built in
VMEM from lane-aligned transposed coordinates (d_pad, BS)/(d_pad, BQ), then
three MXU matmuls against the one-hot stratum matrix produce the (BQ, BK)
moment tiles. The moment axis leads the output, (3, Q, k), so the stratum
tile stays on the 128 lanes; a trailing 3-wide axis would be padded to 128
lanes and overflow the scoped VMEM at deployment widths. Samples are stored
leaf-major so the one-hot is nearly block diagonal; padding samples carry
leaf id -1.

Grid: (q_tiles, k_tiles, s_tiles) with the sample dimension innermost
(sequential accumulation into the (3, BQ, BK) output tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BF16 = jnp.bfloat16


def bf16_parts(v: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
    """Three bf16 arrays whose f32 sum is exactly ``v`` (f32): each part
    rounds the remainder the previous ones left, 8 significand bits at a
    time (f32 has 24)."""
    hi = v.astype(BF16)
    r = v - hi.astype(jnp.float32)
    mid = r.astype(BF16)
    lo = (r - mid.astype(jnp.float32)).astype(BF16)
    return hi, mid, lo


def mm_bf16(lhs: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """(BQ, BS) @ (BS, BK) in one MXU pass: bf16 operands, f32
    accumulation. Exact products when ``lhs`` holds bf16-exact values."""
    return jax.lax.dot_general(lhs.astype(BF16), onehot,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def masked_sum_mm(pred01: jnp.ndarray, v: jnp.ndarray,
                  onehot: jnp.ndarray) -> jnp.ndarray:
    """sum_s pred01[q, s] * v[s] * onehot[s, k] with exact f32 products.

    The MXU rounds f32 operands to bf16 at the default precision (a 2^-9
    relative error per sample). ``pred01`` (0/1) and the one-hot are
    exact in bf16, so splitting ``v`` into three bf16 parts and summing
    three single-pass matmuls gives every product exactly, at half the
    passes of ``Precision.HIGHEST``."""
    hi, mid, lo = bf16_parts(v)
    part = [mm_bf16(pred01 * p.astype(jnp.float32)[None, :], onehot)
            for p in (hi, mid, lo)]
    return (part[0] + part[1]) + part[2]


def _moment_tile(c_ref, a_ref, leaf_ref, qlo_ref, qhi_ref,
                 *, bk: int, d: int, w=None):
    """Shared kernel body: the (3, BQ, BK) moment tile of one grid step.

    ``w`` (BS,) optionally reweights each sample's contribution (the
    uncertainty subsystem's bootstrap resample weights, small integers);
    ``w=None`` is the plain unweighted pass."""
    kt = pl.program_id(1)
    a = a_ref[...]                        # (BS,)
    leaf = leaf_ref[...]                  # (BS,)
    bq = qlo_ref.shape[1]
    bs = a.shape[0]
    pred = jnp.ones((bq, bs), dtype=jnp.bool_)
    for j in range(d):
        cj = c_ref[j, :][None, :]                         # (1, BS)
        lo = qlo_ref[j, :][:, None]                       # (BQ, 1)
        hi = qhi_ref[j, :][:, None]
        pred = pred & (lo <= cj) & (cj <= hi)
    predf = pred.astype(jnp.float32)
    k_base = kt * bk
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (bs, bk), 1) + k_base
    onehot = (leaf[:, None] == k_iota).astype(jnp.float32).astype(BF16)
    return weighted_moment_tile(predf, a, onehot, w)


def weighted_moment_tile(predf, a, onehot, w=None) -> jnp.ndarray:
    """(3, BQ, BK) [count, sum, sumsq] of the samples ``predf`` (0/1,
    (BQ, BS)) selects, each weighted by ``w`` (BS,) when given. The count
    operand (0/1, or times small integer weights) is exact in bf16 and
    takes one MXU pass; the value sums go through
    :func:`masked_sum_mm`."""
    a2 = a * a
    if w is None:
        kp = mm_bf16(predf, onehot)
    else:
        kp = mm_bf16(predf * w[None, :], onehot)
        a, a2 = w * a, w * a2
    sm = masked_sum_mm(predf, a, onehot)
    sq = masked_sum_mm(predf, a2, onehot)
    return jnp.stack([kp, sm, sq], axis=0)                # (3, BQ, BK)


def _kernel(c_ref, a_ref, leaf_ref, qlo_ref, qhi_ref, out_ref,
            *, bk: int, d: int):
    st = pl.program_id(2)
    tile = _moment_tile(c_ref, a_ref, leaf_ref, qlo_ref, qhi_ref, bk=bk, d=d)

    @pl.when(st == 0)
    def _init():
        out_ref[...] = tile

    @pl.when(st != 0)
    def _acc():
        out_ref[...] += tile


def _kernel_weighted(c_ref, a_ref, leaf_ref, w_ref, qlo_ref, qhi_ref,
                     out_ref, *, bk: int, d: int):
    st = pl.program_id(2)
    tile = _moment_tile(c_ref, a_ref, leaf_ref, qlo_ref, qhi_ref, bk=bk, d=d,
                        w=w_ref[...])

    @pl.when(st == 0)
    def _init():
        out_ref[...] = tile

    @pl.when(st != 0)
    def _acc():
        out_ref[...] += tile


@functools.partial(jax.jit,
                   static_argnames=("k", "d", "bq", "bk", "bs", "interpret"))
def stratified_moments(c_t: jnp.ndarray, a: jnp.ndarray, leaf: jnp.ndarray,
                       qlo_t: jnp.ndarray, qhi_t: jnp.ndarray, k: int, d: int,
                       bq: int = 128, bk: int = 128, bs: int = 1024,
                       interpret: bool = True) -> jnp.ndarray:
    """c_t (d_pad, S) f32; a (S,) f32; leaf (S,) int32 (-1 padding);
    qlo_t/qhi_t (d_pad, Q). S % bs == 0, Q % bq == 0, k % bk == 0.
    Returns (3, Q, k) f32 = [k_pred, sum, sumsq]."""
    d_pad, S = c_t.shape
    Q = qlo_t.shape[1]
    assert S % bs == 0 and Q % bq == 0 and k % bk == 0, (S, bs, Q, bq, k, bk)
    grid = (Q // bq, k // bk, S // bs)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_pad, bs), lambda qt, kt, st: (0, st)),
            pl.BlockSpec((bs,), lambda qt, kt, st: (st,)),
            pl.BlockSpec((bs,), lambda qt, kt, st: (st,)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt, st: (0, qt)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt, st: (0, qt)),
        ],
        out_specs=pl.BlockSpec((3, bq, bk), lambda qt, kt, st: (0, qt, kt)),
        out_shape=jax.ShapeDtypeStruct((3, Q, k), jnp.float32),
        interpret=interpret,
    )(c_t, a, leaf, qlo_t, qhi_t)


@functools.partial(jax.jit,
                   static_argnames=("k", "d", "bq", "bk", "bs", "interpret"))
def stratified_weighted_moments(c_t: jnp.ndarray, a: jnp.ndarray,
                                leaf: jnp.ndarray, w: jnp.ndarray,
                                qlo_t: jnp.ndarray, qhi_t: jnp.ndarray,
                                k: int, d: int, bq: int = 128, bk: int = 128,
                                bs: int = 1024, interpret: bool = True
                                ) -> jnp.ndarray:
    """Weighted variant of :func:`stratified_moments`: every sample's
    predicate contribution is scaled by ``w`` (S,) f32 — the resample-weight
    pass of the uncertainty subsystem's Poisson bootstrap. ``w`` holds
    small integers (at most 256, exact in bf16: the count takes one bf16
    MXU pass). Padding samples must carry ``w == 0`` (the adapters enforce
    it).
    Returns (3, Q, k) f32 = [sum w*pred, sum w*pred*a, sum w*pred*a^2]."""
    d_pad, S = c_t.shape
    Q = qlo_t.shape[1]
    assert S % bs == 0 and Q % bq == 0 and k % bk == 0, (S, bs, Q, bq, k, bk)
    grid = (Q // bq, k // bk, S // bs)
    return pl.pallas_call(
        functools.partial(_kernel_weighted, bk=bk, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_pad, bs), lambda qt, kt, st: (0, st)),
            pl.BlockSpec((bs,), lambda qt, kt, st: (st,)),
            pl.BlockSpec((bs,), lambda qt, kt, st: (st,)),
            pl.BlockSpec((bs,), lambda qt, kt, st: (st,)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt, st: (0, qt)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt, st: (0, qt)),
        ],
        out_specs=pl.BlockSpec((3, bq, bk), lambda qt, kt, st: (0, qt, kt)),
        out_shape=jax.ShapeDtypeStruct((3, Q, k), jnp.float32),
        interpret=interpret,
    )(c_t, a, leaf, w, qlo_t, qhi_t)


__all__ = ["stratified_moments", "stratified_weighted_moments"]
