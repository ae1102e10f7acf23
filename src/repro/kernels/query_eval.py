"""Pallas TPU kernel: vectorized MCF classification + exact partial aggregates.

The level-synchronous replacement for the paper's Algorithm 1 (DESIGN.md
§3): every (query, leaf) pair is classified cover/partial/none from the leaf
data bounding boxes, and the exact part of the answer is accumulated on the
MXU as ``cover_mask (BQ, BK) @ leaf_agg (BK, 8)``.

Grid: (q_tiles, k_tiles) with the leaf dimension innermost (sequential
accumulation of the exact part; the relation codes stream out per tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(lo_ref, hi_ref, agg_ref, qlo_ref, qhi_ref, rel_ref, exact_ref,
            *, d: int):
    kt = pl.program_id(1)
    bq = qlo_ref.shape[1]
    bk = lo_ref.shape[1]
    nonempty = jnp.ones((bk,), dtype=jnp.bool_)
    cover = jnp.ones((bq, bk), dtype=jnp.bool_)
    disjoint = jnp.zeros((bq, bk), dtype=jnp.bool_)
    for j in range(d):
        lo = lo_ref[j, :][None, :]
        hi = hi_ref[j, :][None, :]
        qlo = qlo_ref[j, :][:, None]
        qhi = qhi_ref[j, :][:, None]
        nonempty = nonempty & (lo_ref[j, :] <= hi_ref[j, :])
        cover = cover & (qlo <= lo) & (hi <= qhi)
        disjoint = disjoint | (qhi < lo) | (qlo > hi)
    disjoint = disjoint | ~nonempty[None, :]
    cover = cover & nonempty[None, :]
    rel_ref[...] = jnp.where(cover, 2, jnp.where(disjoint, 0, 1)
                             ).astype(jnp.int32)
    # HIGHEST: at the default precision the MXU rounds the f32 leaf
    # aggregates to bf16, and covered answers stop being exact.
    part = jax.lax.dot_general(cover.astype(jnp.float32), agg_ref[...],
                               (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    @pl.when(kt == 0)
    def _init():
        exact_ref[...] = part

    @pl.when(kt != 0)
    def _acc():
        exact_ref[...] += part


@functools.partial(jax.jit, static_argnames=("d", "bq", "bk", "interpret"))
def query_eval(leaf_lo_t: jnp.ndarray, leaf_hi_t: jnp.ndarray,
               leaf_agg: jnp.ndarray, qlo_t: jnp.ndarray, qhi_t: jnp.ndarray,
               d: int, bq: int = 128, bk: int = 128,
               interpret: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """leaf_lo_t/leaf_hi_t (d_pad, k); leaf_agg (k, 8); qlo_t/qhi_t (d_pad, Q).
    Q % bq == 0, k % bk == 0. Returns (rel (Q, k) int32, exact (Q, 8) f32)."""
    d_pad, k = leaf_lo_t.shape
    Q = qlo_t.shape[1]
    assert Q % bq == 0 and k % bk == 0, (Q, bq, k, bk)
    grid = (Q // bq, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d_pad, bk), lambda qt, kt: (0, kt)),
            pl.BlockSpec((d_pad, bk), lambda qt, kt: (0, kt)),
            pl.BlockSpec((bk, 8), lambda qt, kt: (kt, 0)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt: (0, qt)),
            pl.BlockSpec((d_pad, bq), lambda qt, kt: (0, qt)),
        ],
        out_specs=[
            pl.BlockSpec((bq, bk), lambda qt, kt: (qt, kt)),
            pl.BlockSpec((bq, 8), lambda qt, kt: (qt, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, 8), jnp.float32),
        ],
        interpret=interpret,
    )(leaf_lo_t, leaf_hi_t, leaf_agg, qlo_t, qhi_t)


__all__ = ["query_eval"]
