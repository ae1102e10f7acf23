"""Delta-merge serving: immutable base + device-resident stream delta.

The paper's aggregates are mergeable summaries (§2.4): SUM/SUMSQ/COUNT add,
MIN/MAX combine. The streamed-rows delta therefore merges into the base
synopsis with O(k) element-wise ops plus one (num_nodes, k) masked reduce
that lifts the per-leaf delta onto every internal tree node — all on
device, so ``snapshot()``-style host round-trips and O(K) re-uploads per
batch are gone. The subtree incidence matrix is computed once per base
(host, at ingestor construction) from the explicit child pointers, so it
works for both the complete-heap 1-D trees and unbalanced KD trees.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from ..core.types import Synopsis, PartitionTree, AGG_COUNT
from ..kernels.ref import NEG_BIG, POS_BIG


def subtree_leaf_matrix(tree: PartitionTree, k: int) -> jnp.ndarray:
    """(num_nodes, k) bool: leaf j lies in the subtree of node v.

    Host-side, once per base synopsis. Children are stored at higher
    indices than their parent (heap and KD builders both guarantee this),
    so one reverse sweep suffices.
    """
    left = np.asarray(tree.left)
    right = np.asarray(tree.right)
    leaf_id = np.asarray(tree.leaf_id)
    num_nodes = left.shape[0]
    mat = np.zeros((num_nodes, k), dtype=bool)
    for v in range(num_nodes - 1, -1, -1):
        lid = int(leaf_id[v])
        if 0 <= lid < k:
            mat[v, lid] = True
        for ch in (int(left[v]), int(right[v])):
            if ch >= 0:
                assert ch > v, "child stored before parent"
                mat[v] |= mat[ch]
    return jnp.asarray(mat)


@jax.jit
def _merge_arrays(base: Synopsis, state, subtree: jnp.ndarray):
    """Device-only combine; returns the replaced array fields."""
    delta = state.delta_agg                                    # (k, 5)
    base_leaf = base.leaf_agg.astype(jnp.float32)
    leaf_agg = jnp.concatenate(
        [base_leaf[:, 0:3] + delta[:, 0:3],
         jnp.minimum(base_leaf[:, 3:4], delta[:, 3:4]),
         jnp.maximum(base_leaf[:, 4:5], delta[:, 4:5])], axis=1)

    # lift the leaf delta onto every tree node through the subtree mask
    subf = subtree.astype(jnp.float32)                         # (V, k)
    # HIGHEST: at the default precision a TPU matmul rounds the f32 sums
    # to bf16, and the lifted node aggregates stop being exact.
    d_sums = jnp.matmul(subf, delta[:, 0:3],
                        precision=jax.lax.Precision.HIGHEST)   # (V, 3)
    d_min = jnp.min(jnp.where(subtree, delta[:, 3][None], POS_BIG), axis=1)
    d_max = jnp.max(jnp.where(subtree, delta[:, 4][None], NEG_BIG), axis=1)
    base_tree = base.tree.agg.astype(jnp.float32)
    tree_agg = jnp.concatenate(
        [base_tree[:, 0:3] + d_sums,
         jnp.minimum(base_tree[:, 3:4], d_min[:, None]),
         jnp.maximum(base_tree[:, 4:5], d_max[:, None])], axis=1)

    # node boxes: union of current leaf boxes over each subtree
    d = state.leaf_lo.shape[1]
    t_lo = [jnp.min(jnp.where(subtree, state.leaf_lo[:, j][None], jnp.inf),
                    axis=1) for j in range(d)]
    t_hi = [jnp.max(jnp.where(subtree, state.leaf_hi[:, j][None], -jnp.inf),
                    axis=1) for j in range(d)]
    tree_lo = jnp.minimum(base.tree.lo, jnp.stack(t_lo, axis=1))
    tree_hi = jnp.maximum(base.tree.hi, jnp.stack(t_hi, axis=1))
    return leaf_agg, tree_agg, tree_lo, tree_hi


def merge_synopsis(base: Synopsis, state, subtree: jnp.ndarray, *,
                   total_rows) -> Synopsis:
    """Serving synopsis = base ⊕ delta (no host transfer of O(K) state).

    The merged sample arrays ARE the live reservoir, so downstream interval
    estimation (``answer(..., ci=level)`` through ``repro.uncertainty``)
    computes delta-stratum variances from the reservoir's current moments
    and sample counts — no separate moment snapshot is needed.
    """
    leaf_agg, tree_agg, tree_lo, tree_hi = _merge_arrays(base, state, subtree)
    return dataclasses.replace(
        base,
        leaf_lo=state.leaf_lo, leaf_hi=state.leaf_hi,
        leaf_agg=leaf_agg, n_rows=leaf_agg[:, AGG_COUNT],
        sample_c=state.sample_c, sample_a=state.sample_a,
        sample_valid=state.sample_valid,
        k_per_leaf=state.k_per_leaf,
        tree=dataclasses.replace(base.tree, agg=tree_agg, lo=tree_lo,
                                 hi=tree_hi),
        # device scalar: the merged synopsis keeps the base treedef, so
        # prepared AOT executables survive the ingest (DESIGN.md §8)
        total_rows=jnp.asarray(total_rows, jnp.float32))


@jax.jit
def reservoir_moments(state) -> jnp.ndarray:
    """(k, 3) f32 per-stratum live-reservoir moments [n, mean, var].

    The uncertainty subsystem's streaming diagnostics: the per-stratum
    sample mean/variance the interval composition will see when serving
    from the delta-merged state (masked over valid reservoir slots)."""
    valid = state.sample_valid.astype(jnp.float32)           # (k, s)
    n = jnp.sum(valid, axis=1)
    nn = jnp.maximum(n, 1.0)
    a = state.sample_a.astype(jnp.float32)
    mean = jnp.sum(valid * a, axis=1) / nn
    var = jnp.maximum(jnp.sum(valid * a * a, axis=1) / nn - mean ** 2, 0.0)
    return jnp.stack([n, mean, var], axis=-1)


__all__ = ["subtree_leaf_matrix", "merge_synopsis", "reservoir_moments"]
