"""O(k) collective merge of the sharded state (DESIGN.md §11).

The PASS aggregates are mergeable summaries, so the cross-device combine
is one ``psum`` of the (k, 3) additive columns, one ``pmin``/``pmax`` pair
for extremes and boxes, and a tiled ``all_gather`` that reassembles the
per-shard reservoir slices into the (k, S) serving arrays — a few
kilobytes total, independent of the row count. The gathered global
:class:`StreamState` then moves to the mesh's first device and flows
through the *single-device* delta-merge
(:func:`repro.streaming.delta.merge_synopsis`), so the serving epilogue —
tree lift, fixed-structure contractions, prepared AOT executables — is
byte-for-byte the same program regardless of the shard count.

Shard i's reservoir slice lands at slots ``[i*ss, (i+1)*ss)`` of every
stratum (the inverse of ``init_sharded_state``'s split), so the merged
sample shape (k, S) — and with it every downstream treedef and compiled
executable — is independent of how many devices produced it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.types import Synopsis
from ..streaming.delta import merge_synopsis
from ..streaming.ingest import StreamState
from .mesh import Mesh, P, SHARD_AXIS, shard_map


@partial(jax.jit, static_argnames=("mesh",))
def _gather_state(state: StreamState, mesh: Mesh) -> StreamState:
    """Sharded (D, ...) state -> replicated global StreamState."""
    def shard_fn(lo, hi, delta, sc, sa, sv, kpl, seen, oob):
        ax = SHARD_AXIS
        sums = jax.lax.psum(delta[0, :, 0:3], ax)
        dmin = jax.lax.pmin(delta[0, :, 3], ax)
        dmax = jax.lax.pmax(delta[0, :, 4], ax)
        return StreamState(
            leaf_lo=jax.lax.pmin(lo[0], ax),
            leaf_hi=jax.lax.pmax(hi[0], ax),
            delta_agg=jnp.concatenate(
                [sums, dmin[:, None], dmax[:, None]], axis=1),
            sample_c=jax.lax.all_gather(sc[0], ax, axis=1, tiled=True),
            sample_a=jax.lax.all_gather(sa[0], ax, axis=1, tiled=True),
            sample_valid=jax.lax.all_gather(sv[0], ax, axis=1, tiled=True),
            k_per_leaf=jax.lax.psum(kpl[0], ax),
            seen=jax.lax.psum(seen[0], ax),
            oob=jax.lax.psum(oob[0], ax))

    spec = P(SHARD_AXIS)
    # check_vma=False: lax.all_gather types its result as varying over the
    # gathered axis (the invariant-typed gather is not public API), so the
    # checker cannot infer the sample slices replicated; every output here
    # is genuinely replicated — tiled gathers and full-axis reductions only.
    return shard_map(shard_fn, mesh=mesh, in_specs=(spec,) * 9,
                     out_specs=P(), check_vma=False)(
        state.leaf_lo, state.leaf_hi, state.delta_agg, state.sample_c,
        state.sample_a, state.sample_valid, state.k_per_leaf, state.seen,
        state.oob)


def merge_sharded(base: Synopsis, state: StreamState, subtree: jnp.ndarray,
                  *, total_rows, mesh: Mesh) -> Synopsis:
    """Serving synopsis = base ⊕ (collectively merged sharded delta), on
    the mesh's first device.

    The gathered state is replicated over the mesh; serving it there would
    make every serving program span all of the mesh's devices, and a
    Pallas (Mosaic) kernel cannot be partitioned automatically, even over
    replicated operands. One device holds the merged synopsis (O(k + S)
    values) and serves it; the state stays sharded."""
    dev = mesh.devices.flat[0]
    base, gathered, subtree = jax.device_put(
        (base, _gather_state(state, mesh), subtree), dev)
    return merge_synopsis(base, gathered, subtree, total_rows=total_rows)


__all__ = ["merge_sharded"]
