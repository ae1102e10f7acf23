"""Data-parallel maintenance of the partition catalog (DESIGN.md §14 x §11).

Every :class:`~repro.partitions.PartitionCatalog` field is a mergeable
summary, so keeping the catalog current under sharded ingest costs the
same O(P) collective pattern the synopsis state uses: each shard runs the
vectorized :func:`~repro.partitions.partition_stats` pass over its row
block, then additive fields psum, boxes/extremes pmin/pmax. The result is
replicated — identical (up to f32 addition order) to running the stats
pass on one host over the concatenated rows, which is what the
device-count-invariance test pins.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..partitions.catalog import PartitionCatalog, partition_stats
from .mesh import Mesh, P, SHARD_AXIS, data_mesh, num_shards, shard_map


@partial(jax.jit, static_argnames=("num_partitions", "bins", "mesh"))
def _catalog_shard_merge(c_blk, a_blk, pid_blk, mask, bin_lo, bin_hi,
                         num_partitions, bins, mesh):
    def shard_fn(c, a, pid, m, blo, bhi):
        cat = partition_stats(c[0], a[0], pid[0], num_partitions,
                              bins=bins, bin_lo=blo, bin_hi=bhi, mask=m[0])
        ax = SHARD_AXIS
        m_agg = jnp.concatenate(
            [jax.lax.psum(cat.m_agg[:, 0:3], ax),
             jax.lax.pmin(cat.m_agg[:, 3:4], ax),
             jax.lax.pmax(cat.m_agg[:, 4:5], ax)], axis=1)
        return dataclasses.replace(
            cat,
            n=jax.lax.psum(cat.n, ax),
            col_lo=jax.lax.pmin(cat.col_lo, ax),
            col_hi=jax.lax.pmax(cat.col_hi, ax),
            col_sum=jax.lax.psum(cat.col_sum, ax),
            col_sumsq=jax.lax.psum(cat.col_sumsq, ax),
            hist=jax.lax.psum(cat.hist, ax),
            m_agg=m_agg)

    spec = P(SHARD_AXIS)
    return shard_map(shard_fn, mesh=mesh,
                     in_specs=(spec, spec, spec, spec, P(), P()),
                     out_specs=P())(
        c_blk, a_blk, pid_blk, mask, bin_lo, bin_hi)


def catalog_delta_sharded(c, a, pid, num_partitions: int, *, bins: int,
                          bin_lo, bin_hi, mesh: Mesh | None = None
                          ) -> PartitionCatalog:
    """Catalog delta of one ingest batch, computed data-parallel.

    ``c`` (B, d) rows, ``a`` (B,) measures, ``pid`` (B,) partition ids —
    rows are dealt out over the mesh's shard axis, each shard sketches its
    block, and the blocks merge collectively. Fold the returned delta into
    the running catalog with
    :func:`~repro.partitions.combine_catalogs`; the fixed ``bin_lo``/
    ``bin_hi`` edges are what keep that fold pointwise.
    """
    mesh = mesh or data_mesh()
    n_shards = num_shards(mesh)
    c = jnp.asarray(c, jnp.float32)
    if c.ndim == 1:
        c = c[:, None]
    a = jnp.asarray(a, jnp.float32).reshape(-1)
    pid = jnp.asarray(pid, jnp.int32).reshape(-1)
    b = a.shape[0]
    bs = -(-b // n_shards)
    pad = n_shards * bs - b
    if pad:
        c = jnp.concatenate([c, jnp.repeat(c[-1:], pad, axis=0)], axis=0)
        a = jnp.concatenate([a, jnp.repeat(a[-1:], pad)], axis=0)
        pid = jnp.concatenate([pid, jnp.repeat(pid[-1:], pad)], axis=0)
    mask = (jnp.arange(n_shards * bs) < b).reshape(n_shards, bs)
    return _catalog_shard_merge(
        c.reshape(n_shards, bs, -1), a.reshape(n_shards, bs),
        pid.reshape(n_shards, bs), mask,
        jnp.asarray(bin_lo, jnp.float32), jnp.asarray(bin_hi, jnp.float32),
        int(num_partitions), int(bins), mesh)


__all__ = ["catalog_delta_sharded"]
