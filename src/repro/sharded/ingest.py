"""Data-parallel streaming ingest over a device mesh (DESIGN.md §11).

The sharded state is one :class:`~repro.streaming.ingest.StreamState`
whose every field carries a leading shard axis laid out over the mesh's
``"shards"`` axis: each device owns one shard's delta aggregates, leaf
boxes, and — crucially — its *own* Vitter reservoir slice of every
stratum. A streamed batch is dealt into per-shard row blocks on the host
and ingested under one ``shard_map``: routing, segment_reduce, box
expansion, and reservoir replacement all run shard-locally with **zero
collectives in the hot path**. Rows are never gathered to one device; the
only cross-device traffic is the O(k) merge at serve time
(:mod:`repro.sharded.merge`).

Two jitted steps share the single-device state transition
(``_apply_routed``):

* ``_sharded_ingest_step`` — live-box routing (the streaming rule), for
  serving-phase ingest on an already-built base.
* ``_sharded_build_step`` — routing against a *static* replicated cut
  skeleton (1-D thresholds / stretched KD tiling boxes). Because the
  skeleton never moves, the row -> leaf assignment is independent of the
  shard count, which is what makes the data-parallel build's per-leaf
  aggregates bit-stable across 1/2/4/... devices on integer-valued data
  (tests/test_sharded.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

import time

import numpy as np

from ..core.types import Synopsis, AGG_COUNT
from ..kernels.registry import get_backend
from ..streaming.ingest import (StreamState, _ingest_core, _apply_routed,
                                empty_delta_agg, quarantine_mask)
from ..testing import faults as _faults
from .mesh import (Mesh, P, SHARD_AXIS, shard_map, data_mesh, num_shards,
                   shard_leading, split_rows)

# Containment policy for failed shard dispatches: retry with exponential
# backoff, then drop the batch and count it (tests patch these down).
DISPATCH_RETRIES = 4
DISPATCH_BACKOFF_S = 0.001


def init_sharded_state(base: Synopsis, n_shards: int) -> StreamState:
    """Stacked (D, ...) per-shard delta states anchored on one base.

    Boxes and the (empty) delta replicate per shard; the base's stratified
    sample splits into D contiguous slot blocks — shard i owns slots
    ``[i*ss, (i+1)*ss)`` of every stratum, the exact inverse of the tiled
    ``all_gather`` that reassembles them at merge time. The slot axis is
    padded (invalid) up to a multiple of D first, so every shard gets the
    same reservoir capacity. Because a freshly built base's validity is a
    per-stratum prefix, each shard's block validity is itself a prefix and
    the fill-pointer semantics of the single-device reservoir carry over
    unchanged. The Vitter denominator ``seen`` splits as
    ``kpl_shard + fair_share(seen - kpl)`` so every shard satisfies
    ``seen >= filled`` and the shard total equals the base count exactly.
    """
    D = n_shards
    k, d = base.num_leaves, base.d
    sc = jnp.asarray(base.sample_c, jnp.float32)
    sa = jnp.asarray(base.sample_a, jnp.float32)
    sv = jnp.asarray(base.sample_valid, bool)
    s = sc.shape[1]
    pad = (-s) % D
    if pad:
        sc = jnp.pad(sc, ((0, 0), (0, pad), (0, 0)))
        sa = jnp.pad(sa, ((0, 0), (0, pad)))
        sv = jnp.pad(sv, ((0, 0), (0, pad)))
    ss = (s + pad) // D
    sc = sc.reshape(k, D, ss, d).transpose(1, 0, 2, 3)
    sa = sa.reshape(k, D, ss).transpose(1, 0, 2)
    sv = sv.reshape(k, D, ss).transpose(1, 0, 2)

    kpl_g = jnp.asarray(base.k_per_leaf, jnp.int32)           # (k,)
    block = jnp.arange(D, dtype=jnp.int32)[:, None]           # (D, 1)
    kpl = jnp.clip(kpl_g[None, :] - block * ss, 0, ss)        # (D, k)
    seen_g = jnp.asarray(base.leaf_agg, jnp.float32)[:, AGG_COUNT] \
        .astype(jnp.int32)
    extra = jnp.maximum(seen_g - kpl_g, 0)                    # (k,)
    extra_i = extra[None, :] // D + (block < (extra[None, :] % D))
    return StreamState(
        leaf_lo=jnp.broadcast_to(jnp.asarray(base.leaf_lo, jnp.float32),
                                 (D, k, d)),
        leaf_hi=jnp.broadcast_to(jnp.asarray(base.leaf_hi, jnp.float32),
                                 (D, k, d)),
        delta_agg=jnp.broadcast_to(empty_delta_agg(k), (D, k, 5)),
        sample_c=sc, sample_a=sa, sample_valid=sv,
        k_per_leaf=kpl.astype(jnp.int32),
        seen=(kpl + extra_i).astype(jnp.int32),
        oob=jnp.zeros((D,), jnp.int32),
        quarantined=jnp.zeros((D,), jnp.int32))


@partial(jax.jit, static_argnames=("backend_name", "mesh"))
def _sharded_ingest_step(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                         keys: jax.Array, mask: jnp.ndarray,
                         qlo: jnp.ndarray, qhi: jnp.ndarray,
                         backend_name: str, mesh: Mesh) -> StreamState:
    """Streaming-phase step: live per-shard box routing, no collectives.
    ``qlo``/``qhi`` are the replicated (d,) quarantine box (+/-inf when
    only the non-finite checks apply)."""
    def shard_fn(st, cb, ab, kb, mb, ql, qh):
        st0 = jax.tree_util.tree_map(lambda x: x[0], st)
        u = jax.random.uniform(kb[0], (ab.shape[1],), jnp.float32)
        new = _ingest_core(st0, cb[0], ab[0], u, backend_name, mask=mb[0],
                           qlo=ql, qhi=qh)
        return jax.tree_util.tree_map(lambda x: x[None], new)

    spec = P(SHARD_AXIS)
    # check_vma=False: Pallas kernel bodies carry no varying-manual-axes
    # types, so an in-kernel constant (the one-hot's broadcasted_iota)
    # stays unvarying and the checker refuses to compare it with the
    # varying block operands. Nothing here is claimed replicated anyway
    # (all out_specs are sharded).
    return shard_map(shard_fn, mesh=mesh,
                     in_specs=(spec, spec, spec, spec, spec, P(), P()),
                     out_specs=spec, check_vma=False)(state, c, a, keys, mask,
                                                      qlo, qhi)


@partial(jax.jit, static_argnames=("backend_name", "mesh"))
def _sharded_build_step(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                        keys: jax.Array, mask: jnp.ndarray,
                        route_lo: jnp.ndarray, route_hi: jnp.ndarray,
                        qlo: jnp.ndarray, qhi: jnp.ndarray,
                        backend_name: str, mesh: Mesh) -> StreamState:
    """Build-phase step: route against the replicated static cut skeleton.

    1-D skeletons are threshold intervals (``searchsorted``, ties at a cut
    go to the upper leaf, matching the host builders' assignment rule);
    KD skeletons are tiling boxes with outer faces stretched to +/-BIG, so
    every row is contained (distance 0) and ``route_multid``'s
    lowest-leaf-id tie-break makes the assignment deterministic — in both
    cases independent of the shard count and of ingestion order.
    """
    def shard_fn(st, cb, ab, kb, mb, rlo, rhi, ql, qh):
        st0 = jax.tree_util.tree_map(lambda x: x[0], st)
        cb0, ab0, mb0 = cb[0], ab[0], mb[0]
        bad = quarantine_mask(cb0, ab0, ql, qh)
        n_quar = jnp.sum(bad & mb0).astype(jnp.int32)
        mb0 = mb0 & ~bad
        cb0 = jnp.where(bad[:, None], 0.0, cb0)   # keep routing NaN-free
        u = jax.random.uniform(kb[0], (ab0.shape[0],), jnp.float32)
        if cb0.shape[1] == 1:
            thr = rlo[1:, 0]
            leaf = jnp.searchsorted(thr, cb0[:, 0], side="right"
                                    ).astype(jnp.int32)
            dsel = jnp.zeros(cb0.shape[0], jnp.float32)
        else:
            leaf, dsel = get_backend(backend_name).route_multid(rlo, rhi, cb0)
        new = _apply_routed(st0, cb0, ab0, u, leaf, dsel, backend_name, mb0,
                            n_quar=n_quar)
        return jax.tree_util.tree_map(lambda x: x[None], new)

    spec = P(SHARD_AXIS)
    # check_vma=False: same Pallas kernel-body caveat as
    # _sharded_ingest_step.
    return shard_map(shard_fn, mesh=mesh,
                     in_specs=(spec, spec, spec, spec, spec, P(), P(),
                               P(), P()),
                     out_specs=spec, check_vma=False)(state, c, a, keys, mask,
                                                      route_lo, route_hi,
                                                      qlo, qhi)


class ShardedIngestor:
    """Data-parallel drop-in for :class:`StreamingIngestor` (DESIGN.md §11).

    Same front-end contract — ``ingest()``, ``as_synopsis()``, ``epoch``,
    drift signals — so :class:`~repro.api.PassEngine` and
    :class:`~repro.streaming.policy.DriftPolicy` consume it unchanged. The
    difference is physical: state lives sharded over ``mesh``'s
    ``"shards"`` axis and ``as_synopsis()`` runs the O(k) collective merge
    (psum/pmin/pmax + one tiled reservoir all_gather) instead of a local
    combine. ``route_boxes`` switches routing to a static cut skeleton
    (the build phase); ``commit()`` folds the merged result in as the new
    immutable base and returns to live-box streaming.
    """

    def __init__(self, base: Synopsis, *, mesh: Mesh | None = None,
                 seed: int = 0, key: jax.Array | None = None,
                 backend: str | None = None,
                 route_boxes: tuple | None = None,
                 quarantine_box: tuple | None = None):
        from ..streaming.delta import subtree_leaf_matrix
        self.mesh = mesh if mesh is not None else data_mesh()
        self.n_shards = num_shards(self.mesh)
        self.base = base
        self._subtree = subtree_leaf_matrix(base.tree, base.num_leaves)
        self._backend = get_backend(backend).name
        self._key = key if key is not None else jax.random.PRNGKey(seed)
        self.state = shard_leading(self.mesh,
                                   init_sharded_state(base, self.n_shards))
        self._route = None
        if route_boxes is not None:
            self._route = (jnp.asarray(route_boxes[0], jnp.float32),
                           jnp.asarray(route_boxes[1], jnp.float32))
        # Quarantine box as replicated (d,) arrays; +/-inf = non-finite
        # checks only (the shard_map step always takes box operands, so
        # toggling the box never retraces).
        if quarantine_box is not None:
            self._qlo = jnp.reshape(
                jnp.asarray(quarantine_box[0], jnp.float32), (-1,))
            self._qhi = jnp.reshape(
                jnp.asarray(quarantine_box[1], jnp.float32), (-1,))
        else:
            self._qlo = jnp.full((base.d,), -jnp.inf, jnp.float32)
            self._qhi = jnp.full((base.d,), jnp.inf, jnp.float32)
        self.n_stream = 0
        self._base_rows = int(base.total_rows)
        self._epoch = 0
        self._merged: Synopsis | None = None
        self._fault_stats = {"dispatch_retries": 0, "dropped_batches": 0,
                             "poisoned_batches": 0}

    @property
    def epoch(self) -> int:
        """Monotone merge epoch (see ``StreamingIngestor.epoch``)."""
        return self._epoch

    @property
    def shard_capacity(self) -> int:
        """Per-shard reservoir slots per stratum."""
        return self.state.sample_a.shape[-1]

    # -- ingestion -----------------------------------------------------------
    def ingest(self, c_rows, a_vals) -> "ShardedIngestor":
        """Deal a (B, d) batch into per-shard blocks and ingest in one
        ``shard_map`` step. Each shard consumes its own threefry subkey, so
        a seeded sharded run is deterministic (for a fixed shard count —
        different meshes draw different reservoirs, which is why the
        cross-device-count invariants are on aggregates, not samples)."""
        inj = _faults.active()
        if inj is not None:
            c_rows, a_vals, poisoned = inj.poison_batch(
                np.asarray(c_rows, np.float32), np.asarray(a_vals, np.float32))
            self._fault_stats["poisoned_batches"] += int(poisoned)
        c = jnp.asarray(c_rows, jnp.float32)
        if c.ndim == 1:
            c = jnp.reshape(c, (-1, 1))
        a = jnp.reshape(jnp.asarray(a_vals, jnp.float32), (-1,))
        b = a.shape[0]
        csh, ash, mask = split_rows(c, a, self.n_shards)
        # The PRNG split happens before dispatch, so a retried dispatch
        # consumes the exact same per-shard subkeys — a transient shard
        # failure that recovers is bit-identical to a clean run.
        keys = jax.random.split(self._key, self.n_shards + 1)
        self._key = keys[0]
        new_state = self._dispatch(csh, ash, keys[1:], mask, inj)
        if new_state is None:                  # dropped after max retries
            self._fault_stats["dropped_batches"] += 1
            return self
        self.state = new_state
        self.n_stream += b
        self._epoch += 1
        self._merged = None
        return self

    def _dispatch(self, csh, ash, keys, mask, inj):
        """One sharded step with the fault hook: retry with exponential
        backoff on :class:`~repro.testing.faults.InjectedFault`, give up
        (drop the batch, keep serving) after ``DISPATCH_RETRIES``."""
        for attempt in range(DISPATCH_RETRIES + 1):
            try:
                if inj is not None and inj.shard_dispatch_fails(attempt):
                    raise _faults.InjectedFault(
                        f"shard dispatch (attempt {attempt})")
                if self._route is None:
                    return _sharded_ingest_step(
                        self.state, csh, ash, keys, mask, self._qlo,
                        self._qhi, self._backend, self.mesh)
                return _sharded_build_step(
                    self.state, csh, ash, keys, mask, self._route[0],
                    self._route[1], self._qlo, self._qhi, self._backend,
                    self.mesh)
            except _faults.InjectedFault:
                if attempt >= DISPATCH_RETRIES:
                    return None
                self._fault_stats["dispatch_retries"] += 1
                time.sleep(DISPATCH_BACKOFF_S * (2 ** attempt))
        return None

    def fault_stats(self) -> dict:
        """Containment counters (dispatch retries, dropped/poisoned
        batches) for ``engine.stats()['faults']``."""
        return dict(self._fault_stats)

    # -- drift signals -------------------------------------------------------
    @property
    def n_oob(self) -> int:
        return int(jnp.sum(self.state.oob))

    @property
    def n_quarantined(self) -> int:
        """Rows rejected by ingest validation, summed over shards."""
        return int(jnp.sum(self.state.quarantined))

    @property
    def total_rows(self) -> int:
        return self._base_rows + self.n_stream - self.n_quarantined

    def staleness(self) -> float:
        return self.n_stream / max(self.total_rows, 1)

    def oob_frac(self) -> float:
        return self.n_oob / max(self.n_stream, 1)

    # -- serving -------------------------------------------------------------
    def as_synopsis(self) -> Synopsis:
        """Collectively merged serving synopsis (cached until next ingest)."""
        if self._merged is None:
            from .merge import merge_sharded
            self._merged = merge_sharded(self.base, self.state,
                                         self._subtree,
                                         total_rows=self.total_rows,
                                         mesh=self.mesh)
        return self._merged

    def commit(self) -> Synopsis:
        """Fold the merged state in as the new immutable base.

        Ends the build phase: the delta zeroes, per-shard boxes snap to the
        merged (global) boxes so all shards route identically again, the
        static route skeleton is dropped, and subsequent ``ingest()`` calls
        stream against live boxes. The per-shard reservoirs are kept
        in place — the merged base's sample arrays are exactly their tiled
        concatenation, so nothing moves. The served synopsis is unchanged
        bit-for-bit (base' ⊕ 0 == base ⊕ delta), so the epoch does not
        bump and prepared queries stay pinned.
        """
        merged = self.as_synopsis()
        D, k, d = self.n_shards, self.base.num_leaves, self.base.d
        self.base = merged
        self.state = shard_leading(self.mesh, dataclasses.replace(
            self.state,
            leaf_lo=jnp.broadcast_to(jnp.asarray(merged.leaf_lo, jnp.float32),
                                     (D, k, d)),
            leaf_hi=jnp.broadcast_to(jnp.asarray(merged.leaf_hi, jnp.float32),
                                     (D, k, d)),
            delta_agg=jnp.broadcast_to(empty_delta_agg(k), (D, k, 5)),
            oob=jnp.zeros((D,), jnp.int32)))
        self._route = None
        self.n_stream = 0
        self._base_rows = int(merged.total_rows)
        self._merged = merged
        return merged


__all__ = ["ShardedIngestor", "init_sharded_state"]
