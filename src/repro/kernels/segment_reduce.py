"""Pallas TPU kernel: per-segment aggregate reduction (PASS build phase).

Computes [SUM, SUMSQ, COUNT, MIN, MAX] per leaf over assigned rows — the
bottom-up aggregation of paper §3.2 at dataset scale. TPU mapping
(DESIGN.md §3): each grid step loads a (BN,) tile of values + leaf ids into
VMEM, builds a one-hot (BN, BK) tile, and drives the MXU with
``onehot.T @ [v, v^2, 1]``; MIN/MAX use masked VPU reductions. The (BK, 8)
output tile lives in VMEM across the reduction grid dimension.

Grid: (k_tiles, n_tiles) with the row dimension innermost ("arbitrary"
semantics — sequential accumulation into the output block).

Block shapes: BN is a multiple of 8*128 = 1024 (flattened row tile), BK a
multiple of 128 (lane-aligned segment tile). VMEM footprint per step:
one-hot BN*BK*4 B (e.g. 2048 x 256 -> 2 MiB) + tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import NEG_BIG, POS_BIG


ROW_TILE = 1024            # 8 sublanes x 128 lanes, flattened
MAX_BN = 2048


def auto_block_n(n: int, max_bn: int = MAX_BN, tile: int = ROW_TILE) -> int:
    """Row-block size for an n-row reduction: the smallest multiple of the
    (8, 128) flattened register tile that covers n, capped at ``max_bn``.

    Streaming ingest reduces small (B,)-row batches; padding a 512-row
    batch to the build-path default of 2048 wastes 4x the one-hot VMEM and
    MXU work, so backends pass ``bn=None`` and let the batch size pick the
    block."""
    if n <= 0:
        return tile
    return min(max_bn, tile * ((n + tile - 1) // tile))


def _kernel(v_ref, id_ref, out_ref, *, bk: int):
    j = pl.program_id(1)          # row-tile index (reduction dim)
    kt = pl.program_id(0)         # segment-tile index
    v = v_ref[...]                # (BN,)
    ids = id_ref[...]             # (BN,)
    k_base = kt * bk
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], bk), 1) + k_base
    onehot = (ids[:, None] == k_iota).astype(jnp.float32)       # (BN, BK)
    moments = jnp.stack([v, v * v, jnp.ones_like(v)], axis=-1)  # (BN, 3)
    # HIGHEST: the default MXU precision rounds the f32 values to bf16,
    # and the per-leaf sums stop being the exact aggregates.
    part = jax.lax.dot_general(onehot, moments,
                               (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (BK,3)
    sel = onehot > 0
    vmin = jnp.min(jnp.where(sel, v[:, None], POS_BIG), axis=0)     # (BK,)
    vmax = jnp.max(jnp.where(sel, v[:, None], NEG_BIG), axis=0)

    @pl.when(j == 0)
    def _init():
        out_ref[:, 0:3] = part
        out_ref[:, 3] = vmin
        out_ref[:, 4] = vmax
        out_ref[:, 5:8] = jnp.zeros((bk, 3), jnp.float32)

    @pl.when(j != 0)
    def _acc():
        out_ref[:, 0:3] += part
        out_ref[:, 3] = jnp.minimum(out_ref[:, 3], vmin)
        out_ref[:, 4] = jnp.maximum(out_ref[:, 4], vmax)


@functools.partial(jax.jit, static_argnames=("k", "bn", "bk", "interpret"))
def segment_reduce(values: jnp.ndarray, seg_ids: jnp.ndarray, k: int,
                   bn: int = 2048, bk: int = 256,
                   interpret: bool = True) -> jnp.ndarray:
    """values (N,) f32, seg_ids (N,) int32 (-1 = padding), N % bn == 0,
    k % bk == 0. Returns (k, 8): [sum, sumsq, count, min, max, 0, 0, 0]."""
    n = values.shape[0]
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    grid = (k // bk, n // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn,), lambda kt, j: (j,)),
            pl.BlockSpec((bn,), lambda kt, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bk, 8), lambda kt, j: (kt, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 8), jnp.float32),
        interpret=interpret,
    )(values, seg_ids)
    return out


def _kernel_weighted(v_ref, w_ref, id_ref, out_ref, *, bk: int):
    """Weighted per-segment sums: [sum w*v, sum w*v^2, sum w] per segment.

    The one-hot MXU mapping of ``_kernel`` with the moment matrix scaled by
    the per-row weight — the reduce the uncertainty subsystem's Poisson
    bootstrap runs once per resample replicate."""
    j = pl.program_id(1)
    kt = pl.program_id(0)
    v = v_ref[...]                # (BN,)
    w = w_ref[...]                # (BN,)
    ids = id_ref[...]             # (BN,)
    k_base = kt * bk
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], bk), 1) + k_base
    onehot = (ids[:, None] == k_iota).astype(jnp.float32)       # (BN, BK)
    moments = jnp.stack([w * v, w * v * v, w], axis=-1)         # (BN, 3)
    part = jax.lax.dot_general(onehot, moments,
                               (((0,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (BK,3)

    @pl.when(j == 0)
    def _init():
        out_ref[:, 0:3] = part
        out_ref[:, 3:8] = jnp.zeros((bk, 5), jnp.float32)

    @pl.when(j != 0)
    def _acc():
        out_ref[:, 0:3] += part


@functools.partial(jax.jit, static_argnames=("k", "bn", "bk", "interpret"))
def weighted_segment_reduce(values: jnp.ndarray, weights: jnp.ndarray,
                            seg_ids: jnp.ndarray, k: int,
                            bn: int = 2048, bk: int = 256,
                            interpret: bool = True) -> jnp.ndarray:
    """values/weights (N,) f32, seg_ids (N,) int32 (-1 = padding; padding
    rows must carry weight 0), N % bn == 0, k % bk == 0.
    Returns (k, 8): [sum w*v, sum w*v^2, sum w, 0, 0, 0, 0, 0]."""
    n = values.shape[0]
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    grid = (k // bk, n // bn)
    return pl.pallas_call(
        functools.partial(_kernel_weighted, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn,), lambda kt, j: (j,)),
            pl.BlockSpec((bn,), lambda kt, j: (j,)),
            pl.BlockSpec((bn,), lambda kt, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bk, 8), lambda kt, j: (kt, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 8), jnp.float32),
        interpret=interpret,
    )(values, weights, seg_ids)


__all__ = ["segment_reduce", "weighted_segment_reduce", "auto_block_n"]
