"""Pallas TPU megakernel: all bootstrap replicate moments in one pass.

The uncertainty subsystem's Poisson bootstrap (DESIGN.md §7) needs, for
every replicate r, the weighted relevant-sample moments the
``stratified_weighted_moments`` kernel computes for one resample-weight
vector. The scan path dispatches that kernel once per replicate — R full
passes over the sample arrays. This megakernel instead revisits each
sample tile once per (replicate-tile, query-tile, stratum-tile) and emits
the whole (R, 3, Q, k) replicate-moment block from a single
``pallas_call``: the sample tile (coordinates, values, leaf ids) is loaded
into VMEM once per grid step and reused for all BR replicates of the
weight tile, so the data pass is amortized over the replicate block
instead of being repeated per replicate.

Bit-identity contract (DESIGN.md §10): the per-replicate arithmetic is an
*unrolled loop of exactly the 2-D matmuls the scan path's weighted kernel
performs* — same (BQ, BS) x (BS, BK) contraction shapes, same sample-tile
accumulation order (the s grid dimension stays innermost/sequential), so a
replicate's (3, Q, k) slice is bit-identical to one
``stratified_weighted_moments`` call with the same weight row. Resample
weights are NOT generated in-kernel: they arrive as an (R, S) operand
drawn in one batched ``fold_in(key, r)`` threefry pass (see
``uncertainty/bootstrap.py``), which keeps the draws bit-matching the
sequential scan path on every jax version; the kernel streams them in
(BR, BS) tiles, so only one tile of the weight matrix is resident per
step.

Grid: (r_tiles, q_tiles, k_tiles, s_tiles) with the sample dimension
innermost (sequential accumulation into the (BR, 3, BQ, BK) output tile).
As in ``stratified_estimate``, the moment axis sits ahead of the (BQ, BK)
tile so that the stratum tile stays on the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .stratified_estimate import BF16, weighted_moment_tile


# Replicate-tile size: BR unrolled per-replicate matmul groups per grid
# step. 8 keeps the VMEM-resident predicate + one (BQ, BS) scratch per
# replicate small while amortizing the sample-tile load 8x.
REP_TILE = 8


def auto_block_r(r: int, tile: int = REP_TILE) -> int:
    """Replicate-block size for an R-replicate bootstrap: the full tile
    when R covers it, else R itself (small-R calls stay un-padded). The
    ``br=None`` convention mirrors ``segment_reduce.auto_block_n``."""
    if r <= 0:
        return tile
    return min(tile, r)


def _kernel(c_ref, a_ref, leaf_ref, w_ref, qlo_ref, qhi_ref, out_ref,
            *, br: int, bk: int, d: int):
    st = pl.program_id(3)
    kt = pl.program_id(2)
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
    predb = pred.astype(jnp.float32)
    k_base = kt * bk
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (bs, bk), 1) + k_base
    onehot = (leaf[:, None] == k_iota).astype(jnp.float32).astype(BF16)
    # each replicate: exactly the weighted kernel's tile arithmetic
    tiles = [weighted_moment_tile(predb, a, onehot, w_ref[r, :])
             for r in range(br)]                          # unrolled
    tile = jnp.stack(tiles, axis=0)                       # (BR, 3, BQ, BK)

    @pl.when(st == 0)
    def _init():
        out_ref[...] = tile

    @pl.when(st != 0)
    def _acc():
        out_ref[...] += tile


@functools.partial(jax.jit,
                   static_argnames=("k", "d", "br", "bq", "bk", "bs",
                                    "interpret"))
def bootstrap_moments(c_t: jnp.ndarray, a: jnp.ndarray, leaf: jnp.ndarray,
                      w: jnp.ndarray, qlo_t: jnp.ndarray, qhi_t: jnp.ndarray,
                      k: int, d: int, br: int = REP_TILE, bq: int = 128,
                      bk: int = 128, bs: int = 1024,
                      interpret: bool = True) -> jnp.ndarray:
    """c_t (d_pad, S) f32; a (S,) f32; leaf (S,) int32 (-1 padding);
    w (R, S) f32 resample weights, small integers at most 256 (padding
    samples carry w == 0);
    qlo_t/qhi_t (d_pad, Q). R % br == 0, S % bs == 0, Q % bq == 0,
    k % bk == 0. Returns (R, 3, Q, k) f32 =
    [sum w*pred, sum w*pred*a, sum w*pred*a^2] per replicate."""
    d_pad, S = c_t.shape
    R = w.shape[0]
    Q = qlo_t.shape[1]
    assert R % br == 0 and S % bs == 0 and Q % bq == 0 and k % bk == 0, \
        (R, br, S, bs, Q, bq, k, bk)
    grid = (R // br, Q // bq, k // bk, S // bs)
    return pl.pallas_call(
        functools.partial(_kernel, br=br, bk=bk, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_pad, bs), lambda rt, qt, kt, st: (0, st)),
            pl.BlockSpec((bs,), lambda rt, qt, kt, st: (st,)),
            pl.BlockSpec((bs,), lambda rt, qt, kt, st: (st,)),
            pl.BlockSpec((br, bs), lambda rt, qt, kt, st: (rt, st)),
            pl.BlockSpec((d_pad, bq), lambda rt, qt, kt, st: (0, qt)),
            pl.BlockSpec((d_pad, bq), lambda rt, qt, kt, st: (0, qt)),
        ],
        out_specs=pl.BlockSpec((br, 3, bq, bk),
                               lambda rt, qt, kt, st: (rt, 0, qt, kt)),
        out_shape=jax.ShapeDtypeStruct((R, 3, Q, k), jnp.float32),
        interpret=interpret,
    )(c_t, a, leaf, w, qlo_t, qhi_t)


__all__ = ["bootstrap_moments", "auto_block_r", "REP_TILE"]
