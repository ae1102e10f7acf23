"""Tiled multi-D batch routing: nearest leaf box per row, O(tile) memory.

Streaming ingest routes every row of a batch to the leaf box that contains
it (distance 0) or is L1-nearest (``streaming/ingest.py``). The d > 1 path
used to materialize the dense (B, k) distance matrix and argmin it — fine
for small synopses, but the matrix is the single largest temporary of the
ingest step and grows with k. The formulations here stream leaf-box tiles
instead, keeping only an online (min-distance, argmin-leaf) pair per row:
same O(B·k) work, O(B·bk) live memory.

Tie semantics are bit-matched to the dense oracle: ``jnp.argmin`` takes
the *lowest* index among equal distances, reproduced by (a) per-tile
argmin (lowest index within the tile) and (b) a strict ``<`` merge across
tiles (an equal distance in a later tile never displaces the earlier
winner). Distances are accumulated per coordinate dimension in the same
order as the dense formulation, so the selected distance is bit-identical,
not just the leaf choice.

Padding strata (k padded to the tile multiple) are filled with inverted
±BIG boxes whose distance is ~BIG per dimension — unreachable, exactly
like the inverted empty-leaf boxes the build path stores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import NEG_BIG, POS_BIG

# Leaf-box tile of the streamed dimension (sublane-aligned).
BOX_TILE = 128
# Row tile of the Pallas router (lane-aligned).
ROW_TILE = 1024


def auto_block_k(k: int, tile: int = BOX_TILE) -> int:
    """Leaf-tile size for a k-leaf router call (``bk=None`` convention):
    the full lane tile, or k itself when the synopsis is smaller."""
    if k <= 0:
        return tile
    return min(tile, k)


def dist_matrix(lo, hi, c):
    """(B, K) L1 box distance, accumulated dimension-major exactly like
    the dense oracle (``max(lo - c, c - hi, 0)`` per dim, then add)."""
    d = c.shape[1]
    dist = None
    for j in range(d):
        lo_j = lo[:, j][None]                        # (1, K)
        hi_j = hi[:, j][None]
        cj = c[:, j][:, None]                        # (B, 1)
        dj = jnp.maximum(jnp.maximum(lo_j - cj, cj - hi_j), 0.0)
        dist = dj if dist is None else dist + dj
    return dist


def route_multid_dense(leaf_lo, leaf_hi, c):
    """Dense-oracle routing: materializes the (B, k) distance matrix.

    Returns (leaf ids (B,) int32, selected distance (B,) f32)."""
    dist = dist_matrix(leaf_lo, leaf_hi, c)
    leaf = jnp.argmin(dist, axis=1).astype(jnp.int32)
    dsel = jnp.take_along_axis(dist, leaf[:, None], axis=1)[:, 0]
    return leaf, dsel


def _pad_boxes(leaf_lo, leaf_hi, bk):
    k = leaf_lo.shape[0]
    pad = (-k) % bk
    if pad:
        d = leaf_lo.shape[1]
        leaf_lo = jnp.concatenate(
            [leaf_lo, jnp.full((pad, d), POS_BIG, leaf_lo.dtype)], axis=0)
        leaf_hi = jnp.concatenate(
            [leaf_hi, jnp.full((pad, d), NEG_BIG, leaf_hi.dtype)], axis=0)
    return leaf_lo, leaf_hi


@functools.partial(jax.jit, static_argnames=("bk",))
def route_multid_tiled(leaf_lo, leaf_hi, c, bk: int | None = None):
    """Streamed-jnp routing: ``lax.scan`` over (bk,)-leaf tiles carrying
    the per-row (best distance, best leaf) pair — never materializes more
    than a (B, bk) tile. Bit-matches :func:`route_multid_dense`."""
    bk = bk or auto_block_k(leaf_lo.shape[0])
    lo_p, hi_p = _pad_boxes(leaf_lo, leaf_hi, bk)
    k_pad = lo_p.shape[0]
    n_tiles = k_pad // bk
    b = c.shape[0]
    lo_tiles = lo_p.reshape(n_tiles, bk, -1)
    hi_tiles = hi_p.reshape(n_tiles, bk, -1)
    bases = (jnp.arange(n_tiles, dtype=jnp.int32) * bk)

    def step(carry, tile):
        best_d, best_i = carry
        lo_t, hi_t, base = tile
        dist = dist_matrix(lo_t, hi_t, c)                     # (B, bk)
        loc = jnp.min(dist, axis=1)
        arg = jnp.argmin(dist, axis=1).astype(jnp.int32) + base
        better = loc < best_d                                # strict: ties
        return (jnp.where(better, loc, best_d),              # keep earlier
                jnp.where(better, arg, best_i)), None

    init = (jnp.full((b,), jnp.inf, jnp.float32),
            jnp.zeros((b,), jnp.int32))
    (best_d, best_i), _ = jax.lax.scan(step, init,
                                       (lo_tiles, hi_tiles, bases))
    return best_i, best_d


def _route_kernel(lo_ref, hi_ref, c_ref, dist_ref, idx_ref, *, bk: int,
                  d: int):
    # Leaves on sublanes, rows on lanes: the per-row (min, argmin) pair
    # reduces over sublanes and lands as a lane-dense (1, BB) row.
    kt = pl.program_id(1)
    dist = None
    for j in range(d):
        lo_j = lo_ref[:, j:j + 1]                          # (BK, 1)
        hi_j = hi_ref[:, j:j + 1]
        cj = c_ref[j:j + 1, :]                             # (1, BB)
        dj = jnp.maximum(jnp.maximum(lo_j - cj, cj - hi_j), 0.0)
        dist = dj if dist is None else dist + dj           # (BK, BB)
    loc = jnp.min(dist, axis=0, keepdims=True)             # (1, BB)
    arg = (jnp.argmin(dist, axis=0, keepdims=True).astype(jnp.int32)
           + kt * bk)

    @pl.when(kt == 0)
    def _init():
        dist_ref[...] = loc
        idx_ref[...] = arg

    @pl.when(kt != 0)
    def _merge():
        better = loc < dist_ref[...]                       # strict <: the
        idx_ref[...] = jnp.where(better, arg, idx_ref[...])  # earlier tile
        dist_ref[...] = jnp.where(better, loc, dist_ref[...])  # wins ties


@functools.partial(jax.jit, static_argnames=("d", "bb", "bk", "interpret"))
def route_multid_pallas(lo: jnp.ndarray, hi: jnp.ndarray,
                        c_t: jnp.ndarray, d: int, bb: int = ROW_TILE,
                        bk: int = BOX_TILE, interpret: bool = True
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """lo/hi (k_pad, d_pad) leaf boxes (padding strata at ±BIG inverted);
    c_t (d_pad, B_pad) transposed row coordinates. B_pad % bb == 0,
    k_pad % bk == 0. Returns (idx (1, B_pad) int32, dist (1, B_pad) f32)
    — the grid keeps the (min, argmin) running pair in the VMEM output
    block across the leaf-tile dimension, so no (B, k) buffer ever
    exists. The outputs are 2-D rows: a 1-D (bb,) block disagrees with
    the tiling XLA gives a long 1-D array on TPU."""
    k_pad, d_pad = lo.shape
    B = c_t.shape[1]
    assert B % bb == 0 and k_pad % bk == 0, (B, bb, k_pad, bk)
    grid = (B // bb, k_pad // bk)
    dist, idx = pl.pallas_call(
        functools.partial(_route_kernel, bk=bk, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, d_pad), lambda bt, kt: (kt, 0)),
            pl.BlockSpec((bk, d_pad), lambda bt, kt: (kt, 0)),
            pl.BlockSpec((d_pad, bb), lambda bt, kt: (0, bt)),
        ],
        out_specs=[
            pl.BlockSpec((1, bb), lambda bt, kt: (0, bt)),
            pl.BlockSpec((1, bb), lambda bt, kt: (0, bt)),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, B), jnp.float32),
                   jax.ShapeDtypeStruct((1, B), jnp.int32)],
        interpret=interpret,
    )(lo, hi, c_t)
    return idx, dist


__all__ = ["dist_matrix", "route_multid_dense", "route_multid_tiled",
           "route_multid_pallas", "auto_block_k", "BOX_TILE", "ROW_TILE"]
