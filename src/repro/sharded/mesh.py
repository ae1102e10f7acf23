"""Mesh plumbing for the sharded synopsis layer (DESIGN.md §11).

One data-parallel axis (``"shards"``) spanning every visible device; the
leading axis of every :class:`~repro.streaming.ingest.StreamState` field in
the sharded state is laid out along it, so each device owns one shard's
strata samples, delta summaries, and boxes. Helpers here keep the
host-side batch plumbing (row splitting, padding, per-shard PRNG keys)
out of the ingest hot path.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

SHARD_AXIS = "shards"


def data_mesh(n_dev: int | None = None) -> Mesh:
    """1-D mesh over the first ``n_dev`` devices (default: all visible)."""
    devices = jax.devices()
    if n_dev is not None:
        devices = devices[:n_dev]
    return Mesh(np.array(devices).reshape(-1), (SHARD_AXIS,))


def num_shards(mesh: Mesh) -> int:
    return mesh.shape[SHARD_AXIS]


def shard_leading(mesh: Mesh, tree):
    """Place every array in ``tree`` with its leading axis split over the
    shard axis (the canonical sharded-state layout)."""
    def place(x):
        spec = P(SHARD_AXIS, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(place, tree)


def split_rows(c: jnp.ndarray, a: jnp.ndarray, n_shards: int
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(B, d) rows -> per-shard (D, Bs, d) blocks + (D, Bs) validity mask.

    Rows are dealt out round-robin (row r to shard r mod D). Each shard
    keeps its own reservoir slice of every stratum, and the merged sample
    is uniform only if every shard sees the same share of each stratum's
    rows; batches that arrive ordered by a predicate column (time-ordered
    appends) would give contiguous blocks very unequal shares. A ragged
    tail is padded with the last real row (masked out downstream, so the
    values never matter — repeating a real row keeps every padded
    coordinate inside the data's support, which keeps routing shapes
    trivially valid).
    """
    b = a.shape[0]
    bs = -(-b // n_shards)                     # ceil
    pad = n_shards * bs - b
    if pad:
        c = jnp.concatenate([c, jnp.repeat(c[-1:], pad, axis=0)], axis=0)
        a = jnp.concatenate([a, jnp.repeat(a[-1:], pad)], axis=0)
    mask = (jnp.arange(n_shards * bs) < b).reshape(bs, n_shards).T
    return (jnp.swapaxes(c.reshape(bs, n_shards, -1), 0, 1),
            a.reshape(bs, n_shards).T, mask)


__all__ = ["Mesh", "P", "shard_map", "SHARD_AXIS", "data_mesh",
           "num_shards", "shard_leading", "split_rows"]
