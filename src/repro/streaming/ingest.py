"""Vectorized batched inserts (paper §4.5 at batch granularity).

A batch of B rows is ingested in one device pass:

1. **Route** — every row is classified against every leaf box at once
   (the same containment-else-nearest rule as the per-row
   ``UpdatableSynopsis._route``, computed as an L1 box distance matrix);
   routing uses the *batch-entry* boxes, i.e. boxes expand between batches,
   not between rows of one batch (micro-batch epoch semantics, DESIGN.md §6).
2. **Aggregate** — the value column's per-leaf [SUM, SUMSQ, COUNT, MIN,
   MAX] delta comes from one registry-dispatched ``segment_reduce`` call
   (``pallas | jnp | ref``, row block auto-sized to the batch); the leaf
   bounding boxes are not mergeable aggregates (they only grow), so box
   expansion is two scatter-extremes per coordinate dimension.
3. **Reservoir** — batched Vitter replacement. Per row: its within-batch
   rank ``occ`` inside its leaf (stable-sort cumcount), the stratum's
   running ``seen`` count, and one pre-drawn uniform decide fill-vs-replace
   exactly as the sequential algorithm would; conflicting writers to the
   same (leaf, slot) are resolved last-row-wins by a single scatter-max of
   row indices followed by one gather.

``ingest_batch_reference`` is the sequential per-row oracle with identical
semantics (same routing snapshot, same uniform consumption, f32
arithmetic); the batched path bit-matches it whenever f32 accumulation is
exact (integer-valued aggregates), and matches to float tolerance
otherwise — see tests/test_streaming.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation, annotate_function

from ..core.types import Synopsis, AGG_COUNT
from ..kernels import route as _route
from ..kernels.ref import NEG_BIG, POS_BIG
from ..kernels.registry import get_backend

# Profiler span names; the benchmark's trace reduction reads them by name.
SPAN_INGEST_BATCH = "repro.ingest.batch"
SPAN_INGEST_MERGE = "repro.ingest.merge"


@partial(jax.tree_util.register_dataclass,
         data_fields=["leaf_lo", "leaf_hi", "delta_agg",
                      "sample_c", "sample_a", "sample_valid",
                      "k_per_leaf", "seen", "oob", "quarantined"],
         meta_fields=[])
@dataclasses.dataclass
class StreamState:
    """Device-resident mutable part of a streaming synopsis.

    ``delta_agg`` holds the aggregates of *streamed rows only* (mergeable
    summary, combined with the immutable base at serve time); the sample
    arrays are the live reservoir (they start as the base's stratified
    sample and are replaced in place); ``seen`` is the Vitter denominator
    (base row count + streamed rows per stratum). ``oob`` accumulates the
    out-of-box drift counter on device so the hot loop never dispatches an
    eager op or blocks on a host readback; ``quarantined`` counts rejected
    rows (non-finite measure/coordinates, or outside the quarantine box)
    the same way.
    """
    leaf_lo: jax.Array       # (k, d) f32 current boxes (base U streamed)
    leaf_hi: jax.Array       # (k, d) f32
    delta_agg: jax.Array     # (k, 5) f32 [sum, sumsq, count, min, max]
    sample_c: jax.Array      # (k, s, d) f32
    sample_a: jax.Array      # (k, s) f32
    sample_valid: jax.Array  # (k, s) bool
    k_per_leaf: jax.Array    # (k,) int32 filled slots
    seen: jax.Array          # (k,) int32 rows ever routed to the stratum
    oob: jax.Array           # () int32 streamed rows outside every box
    quarantined: jax.Array | None = None  # () int32 rejected rows


def empty_delta_agg(k: int) -> jnp.ndarray:
    """(k, 5) identity element of the mergeable-summary combine."""
    agg = jnp.zeros((k, 5), jnp.float32)
    return agg.at[:, 3].set(POS_BIG).at[:, 4].set(NEG_BIG)


def _route_dist(leaf_lo, leaf_hi, c):
    """(B, k) dense L1 box distance matrix — the d > 1 routing oracle.

    Kept as the test/reference entry; the formulation lives in
    ``kernels/route.py`` (per-dim ``max(lo-c, c-hi, 0)`` accumulation,
    inverted empty boxes map to an unreachable huge distance by
    themselves), where the registry backends share it: the ``pallas``
    backend replaces the dense matrix with a leaf-tile streaming kernel
    carrying an online (min, argmin) pair — same work, O(tile) memory.
    """
    return _route.dist_matrix(leaf_lo, leaf_hi, c)


def _route_1d(leaf_lo, leaf_hi, c):
    """O(B log k) 1-D routing, equivalent to ``argmin(_route_dist(...))``.

    1-D PASS leaves are intervals in ascending leaf-id order that are
    disjoint *or touching* (equal-depth cuts on duplicate-valued data make
    ``hi[i] == lo[i+1]``, and a run of duplicates can even produce
    degenerate ``[v, v]`` leaves); streaming expansion preserves this:
    within one batch, rows in the gap between boxes i and i+1 route to i
    iff they are strictly below the gap midpoint, so box i can only grow
    up to (not past) where box i+1 grows down to.

    A contained row may therefore lie in *several* touching boxes, and the
    dense argmin picks the lowest leaf id — reproduced here as the first
    box (in sorted == id order, every searchsorted and the argsort being
    stable) whose hi reaches the coordinate. A non-contained row's nearest
    box is the better of (a) the *first* box carrying the largest hi below
    the row — degenerate ``[v, v]`` runs make that hi non-unique, and the
    lowest index must win, exactly like argmin — and (b) the first box
    whose lo exceeds the row; ``<=`` prefers (a) on gap-midpoint ties.
    Empty leaves (inverted at +/-inf or +/-BIG) sort past every finite
    coordinate and are masked out of the hi searches.

    Returns (leaf ids (B,) int32, selected distance (B,) f32) with the
    distance values bit-identical to the dense formulation's.
    """
    lo = leaf_lo[:, 0]
    hi = leaf_hi[:, 0]
    k = lo.shape[0]
    order = jnp.argsort(lo, stable=True)
    lo_s = lo[order]
    hi_s = hi[order]
    # empty boxes (lo > hi) must not break hi's monotonicity nor win the
    # containment search
    hi_eff = jnp.where(lo_s > hi_s, jnp.inf, hi_s)
    cj = c[:, 0]
    # lowest-index box containing c, when one exists
    jc = jnp.clip(jnp.searchsorted(hi_eff, cj, side="left"),
                  0, k - 1).astype(jnp.int32)
    contained = (lo_s[jc] <= cj) & (cj <= hi_s[jc])
    # otherwise: (a) first box sharing the largest hi below c ...
    jl = jnp.searchsorted(hi_eff, hi_eff[jnp.maximum(jc - 1, 0)],
                          side="left").astype(jnp.int32)
    # ... vs (b) first box with lo above c
    ju = jnp.clip(jnp.searchsorted(lo_s, cj, side="right"),
                  0, k - 1).astype(jnp.int32)
    d_l = jnp.maximum(jnp.maximum(lo_s[jl] - cj, cj - hi_s[jl]), 0.0)
    d_u = jnp.maximum(jnp.maximum(lo_s[ju] - cj, cj - hi_s[ju]), 0.0)
    take_l = d_l <= d_u
    sel = jnp.where(contained, jc, jnp.where(take_l, jl, ju))
    dist = jnp.where(contained, 0.0, jnp.where(take_l, d_l, d_u))
    return order[sel].astype(jnp.int32), dist


def quarantine_mask(c: jnp.ndarray, a: jnp.ndarray,
                    qlo: jnp.ndarray | None = None,
                    qhi: jnp.ndarray | None = None) -> jnp.ndarray:
    """(B,) bool mask of rows that must be quarantined: non-finite measure
    or coordinates always; coordinates outside the per-dimension
    ``[qlo, qhi]`` quarantine box when one is given. A NaN/Inf measure
    poisons every downstream moment (SUM/SUMSQ go NaN and never recover),
    so these rows are counted and dropped instead of ingested."""
    bad = ~jnp.isfinite(a) | ~jnp.all(jnp.isfinite(c), axis=1)
    if qlo is not None:
        bad = bad | jnp.any((c < qlo[None, :]) | (c > qhi[None, :]), axis=1)
    return bad


def _batch_occupancy(leaf: jnp.ndarray) -> jnp.ndarray:
    """Within-batch rank of each row inside its leaf group (0-based)."""
    b = leaf.shape[0]
    order = jnp.argsort(leaf, stable=True)
    sl = leaf[order]
    idx = jnp.arange(b, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones(1, bool), sl[1:] != sl[:-1]])
    start = jax.lax.cummax(jnp.where(is_start, idx, -1))
    occ_sorted = idx - start
    return jnp.zeros(b, jnp.int32).at[order].set(occ_sorted)


def _ingest_core(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                 u: jnp.ndarray, backend_name: str,
                 mask: jnp.ndarray | None = None,
                 qlo: jnp.ndarray | None = None,
                 qhi: jnp.ndarray | None = None) -> StreamState:
    """One ingested batch -> new state (pure; all counters device-side).

    ``mask`` (B,) bool marks real rows; ``False`` rows are padding (the
    sharded ingest pads ragged batches up to a multiple of the shard
    count) and must be complete no-ops: they are routed (fixed shapes) but
    contribute nothing to aggregates, boxes, counters, or the reservoir.
    Quarantined rows (:func:`quarantine_mask`) reuse the exact same no-op
    machinery, plus a bump of the device-resident ``quarantined`` counter.
    """
    be = get_backend(backend_name)
    b, d = c.shape
    if mask is None:
        mask = jnp.ones(b, dtype=bool)
    bad = quarantine_mask(c, a, qlo, qhi)
    n_quar = jnp.sum(bad & mask).astype(jnp.int32)
    mask = mask & ~bad
    # NaN coordinates would make the routing comparisons unordered; any
    # in-range leaf id works for a masked-out row, so route from zeros.
    c_route = jnp.where(bad[:, None], 0.0, c)

    # 1. route (one pass against batch-entry boxes); 1-D dodges the dense
    #    (B, k) distance matrix entirely — see _route_1d; d > 1 dispatches
    #    through the registry (`pallas` streams leaf tiles with an online
    #    (min, argmin) pair, `jnp`/`ref` use the dense oracle)
    if d == 1:
        leaf, dsel = _route_1d(state.leaf_lo, state.leaf_hi, c_route)
    else:
        leaf, dsel = be.route_multid(state.leaf_lo, state.leaf_hi, c_route)
    return _apply_routed(state, c, a, u, leaf, dsel, backend_name, mask,
                         n_quar=n_quar)


def _apply_routed(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                  u: jnp.ndarray, leaf: jnp.ndarray, dsel: jnp.ndarray,
                  backend_name: str,
                  mask: jnp.ndarray | None = None,
                  n_quar: jnp.ndarray | None = None) -> StreamState:
    """Aggregate + box-expansion + reservoir update for pre-routed rows.

    Split out of :func:`_ingest_core` so alternative routing policies (the
    sharded build path routes against a *static* cut skeleton instead of
    the live boxes — ``repro.sharded.build``) reuse the exact same state
    transition.
    """
    be = get_backend(backend_name)
    b, d = c.shape
    k, cap = state.sample_a.shape
    if mask is None:
        mask = jnp.ones(b, dtype=bool)
    oob = jnp.sum((dsel > 0.0) & mask)

    # 2. per-leaf aggregate delta through the registry-dispatched
    #    segment_reduce kernel (padding rows carry seg id -1, which every
    #    backend drops); leaf-box expansion is two scatter extremes per
    #    dimension (boxes are not mergeable aggregates — they only grow) —
    #    padding rows scatter +/-inf sentinels, a min/max no-op
    leaf_or_pad = jnp.where(mask, leaf, -1)
    agg_b = be.segment_reduce(a.astype(jnp.float32), leaf_or_pad, k, bn=None)
    new_lo = state.leaf_lo
    new_hi = state.leaf_hi
    c_lo = jnp.where(mask[:, None], c, jnp.inf)
    c_hi = jnp.where(mask[:, None], c, -jnp.inf)
    for j in range(d):
        new_lo = new_lo.at[leaf, j].min(c_lo[:, j])
        new_hi = new_hi.at[leaf, j].max(c_hi[:, j])

    delta = state.delta_agg
    new_delta = jnp.concatenate(
        [delta[:, 0:3] + agg_b[:, 0:3],
         jnp.minimum(delta[:, 3:4], agg_b[:, 3:4]),
         jnp.maximum(delta[:, 4:5], agg_b[:, 4:5])], axis=1)

    # 3. batched Vitter reservoir (padding rows group under sentinel id k,
    #    so real rows' within-leaf ranks are unaffected, and their slot is
    #    forced to -1 so they never claim a reservoir write)
    counts = agg_b[:, 2].astype(jnp.int32)                     # (k,)
    occ = _batch_occupancy(jnp.where(mask, leaf, k))           # (B,)
    seen_at = state.seen[leaf] + occ + 1
    fill_pos = state.k_per_leaf[leaf] + occ
    j_draw = jnp.floor(u.astype(jnp.float32)
                       * seen_at.astype(jnp.float32)).astype(jnp.int32)
    slot = jnp.where(fill_pos < cap, fill_pos,
                     jnp.where(j_draw < cap, j_draw, -1))
    slot = jnp.where(mask, slot, -1)
    key = jnp.where(slot >= 0, leaf * cap + slot, k * cap)
    rows = jnp.arange(b, dtype=jnp.int32)
    winner = (jnp.full(k * cap + 1, -1, jnp.int32).at[key].max(rows)
              )[:k * cap].reshape(k, cap)
    take = winner >= 0
    wclip = jnp.maximum(winner, 0)
    new_sa = jnp.where(take, a.astype(jnp.float32)[wclip], state.sample_a)
    new_sc = jnp.where(take[..., None], c[wclip], state.sample_c)
    new_sv = state.sample_valid | take

    quar0 = (state.quarantined if state.quarantined is not None
             else jnp.zeros((), jnp.int32))
    if n_quar is None:
        n_quar = jnp.zeros((), jnp.int32)
    return StreamState(
        leaf_lo=new_lo, leaf_hi=new_hi, delta_agg=new_delta,
        sample_c=new_sc, sample_a=new_sa, sample_valid=new_sv,
        k_per_leaf=jnp.minimum(state.k_per_leaf + counts, cap),
        seen=state.seen + counts,
        oob=state.oob + oob.astype(jnp.int32),
        quarantined=quar0 + n_quar)


@partial(jax.jit, static_argnames=("backend_name",))
def _ingest_step(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                 u: jnp.ndarray, backend_name: str,
                 qlo: jnp.ndarray | None = None,
                 qhi: jnp.ndarray | None = None) -> StreamState:
    """Explicit-uniforms entry (tests / oracle replay)."""
    return _ingest_core(state, c, a, u, backend_name, qlo=qlo, qhi=qhi)


@partial(jax.jit, static_argnames=("backend_name",))
def _ingest_step_keyed(state: StreamState, c: jnp.ndarray, a: jnp.ndarray,
                       key: jax.Array, backend_name: str,
                       qlo: jnp.ndarray | None = None,
                       qhi: jnp.ndarray | None = None) -> StreamState:
    """PRNG-key entry: the reservoir-replacement uniforms are drawn from
    ``key`` *inside* the jitted step (threefry is bit-stable across jax
    versions, so a seeded ingest sequence is reproducible everywhere —
    unlike the host numpy Generator this replaces)."""
    u = jax.random.uniform(key, (a.shape[0],), jnp.float32)
    return _ingest_core(state, c, a, u, backend_name, qlo=qlo, qhi=qhi)


def init_state(base: Synopsis) -> StreamState:
    """Fresh delta state anchored on an immutable base synopsis."""
    k = base.num_leaves
    return StreamState(
        leaf_lo=jnp.asarray(base.leaf_lo, jnp.float32),
        leaf_hi=jnp.asarray(base.leaf_hi, jnp.float32),
        delta_agg=empty_delta_agg(k),
        sample_c=jnp.asarray(base.sample_c, jnp.float32),
        sample_a=jnp.asarray(base.sample_a, jnp.float32),
        sample_valid=jnp.asarray(base.sample_valid, bool),
        k_per_leaf=jnp.asarray(base.k_per_leaf, jnp.int32),
        seen=jnp.asarray(base.leaf_agg, jnp.float32)[:, AGG_COUNT]
        .astype(jnp.int32),
        oob=jnp.zeros((), jnp.int32),
        quarantined=jnp.zeros((), jnp.int32))


class StreamingIngestor:
    """Batched streaming front end over an immutable base synopsis.

    ``ingest()`` is the vectorized hot path; ``as_synopsis()`` delta-merges
    base + stream state into a serving-ready :class:`Synopsis` (cached until
    the next ingest — the engine's ``answer()``/``artifacts()`` accept the
    ingestor directly). Drift signals: :meth:`staleness` (fraction of rows
    streamed since the base build) and :meth:`oob_frac` (fraction of
    streamed rows outside every box, i.e. new value territory).
    """

    def __init__(self, base: Synopsis, *, seed: int = 0,
                 key: jax.Array | None = None, backend: str | None = None,
                 quarantine_box: tuple | None = None):
        from .delta import subtree_leaf_matrix
        self.base = base
        self.state = init_state(base)
        self._subtree = subtree_leaf_matrix(base.tree, base.num_leaves)
        self._backend = get_backend(backend).name
        # Quarantine box: NaN/Inf rows are always rejected; an explicit
        # (lo, hi) additionally rejects coordinates outside it.
        self._qlo = self._qhi = None
        if quarantine_box is not None:
            self._qlo = jnp.reshape(
                jnp.asarray(quarantine_box[0], jnp.float32), (-1,))
            self._qhi = jnp.reshape(
                jnp.asarray(quarantine_box[1], jnp.float32), (-1,))
        # Explicit PRNG key threaded through reservoir replacement: each
        # ingest() splits off a per-batch subkey, so a seeded sequence is
        # deterministic across hosts and jax versions (threefry-stable).
        self._key = key if key is not None else jax.random.PRNGKey(seed)
        self.n_stream = 0
        self._base_rows = int(base.total_rows)   # host copy for drift math
        self._epoch = 0
        self._merged: Synopsis | None = None

    @property
    def epoch(self) -> int:
        """Monotone delta-merge epoch: bumps on every ingested batch, so
        serving layers (``repro.api.PassEngine``) can invalidate prepared
        artifacts pinned to a stale merge."""
        return self._epoch

    # -- ingestion -----------------------------------------------------------
    @partial(annotate_function, name=SPAN_INGEST_BATCH)
    def ingest(self, c_rows, a_vals, u=None) -> "StreamingIngestor":
        """Ingest a (B, d) coordinate batch + (B,) value batch.

        The wrapper stays sync-free: everything per-batch — including the
        reservoir uniforms, drawn from the threaded PRNG key when ``u`` is
        not supplied — happens inside one jitted step (reuse a fixed batch
        size to hit the jit cache).
        """
        from ..testing import faults as _faults
        inj = _faults.active()
        if inj is not None:
            c_rows, a_vals, _ = inj.poison_batch(
                np.asarray(c_rows, np.float32), np.asarray(a_vals, np.float32))
        c = jnp.asarray(c_rows, jnp.float32)
        if c.ndim == 1:
            c = jnp.reshape(c, (-1, 1))
        a = jnp.reshape(jnp.asarray(a_vals, jnp.float32), (-1,))
        b = a.shape[0]
        if u is None:
            self._key, sub = jax.random.split(self._key)
            self.state = _ingest_step_keyed(self.state, c, a, sub,
                                            self._backend,
                                            self._qlo, self._qhi)
        else:
            u = jnp.asarray(u, jnp.float32)
            self.state = _ingest_step(self.state, c, a, u, self._backend,
                                      self._qlo, self._qhi)
        self.n_stream += b
        self._epoch += 1
        self._merged = None
        return self

    # -- drift signals -------------------------------------------------------
    @property
    def n_oob(self) -> int:
        return int(self.state.oob)

    @property
    def n_quarantined(self) -> int:
        """Rows rejected by ingest validation (host readback; cheap, but
        only touch it off the hot path — serve/telemetry time)."""
        return int(self.state.quarantined)

    @property
    def total_rows(self) -> int:
        """Current served row count (base + streamed), as a host int.
        Quarantined rows never reached the aggregates, so they are not
        part of the served population."""
        return self._base_rows + self.n_stream - self.n_quarantined

    def staleness(self) -> float:
        """Fraction of rows streamed since the base build (§4.5)."""
        return self.n_stream / max(self.total_rows, 1)

    def oob_frac(self) -> float:
        """Fraction of streamed rows that fell outside every leaf box."""
        return self.n_oob / max(self.n_stream, 1)

    # -- serving -------------------------------------------------------------
    def as_synopsis(self) -> Synopsis:
        """Delta-merged serving synopsis (cached; device-only combine)."""
        if self._merged is None:
            from .delta import merge_synopsis
            # total_rows reads the quarantine counter back to the host.
            with TraceAnnotation(SPAN_INGEST_MERGE):
                self._merged = merge_synopsis(self.base, self.state,
                                              self._subtree,
                                              total_rows=self.total_rows)
        return self._merged


def ingest_batch_reference(state: StreamState, c_rows, a_vals, u,
                           qlo=None, qhi=None) -> StreamState:
    """Sequential per-row oracle for one ingested batch (host, f32).

    Same semantics as the vectorized ``_ingest_step``: routing against the
    batch-entry boxes, one pre-drawn uniform per row, last-writer-wins on
    reservoir slots (trivially true sequentially), quarantined rows total
    no-ops that still occupy their batch position (u[i] stays theirs).
    Returns the new state as a numpy-backed ``StreamState``.
    """
    c = np.asarray(c_rows, np.float32)
    if c.ndim == 1:
        c = c[:, None]
    a = np.asarray(a_vals, np.float32).reshape(-1)
    u = np.asarray(u, np.float32).reshape(-1)

    lo = np.asarray(state.leaf_lo, np.float32).copy()
    hi = np.asarray(state.leaf_hi, np.float32).copy()
    delta = np.asarray(state.delta_agg, np.float32).copy()
    sc = np.asarray(state.sample_c, np.float32).copy()
    sa = np.asarray(state.sample_a, np.float32).copy()
    sv = np.asarray(state.sample_valid, bool).copy()
    kpl = np.asarray(state.k_per_leaf, np.int32).copy()
    seen = np.asarray(state.seen, np.int32).copy()
    cap = sa.shape[1]

    # batch-entry routing snapshot
    lo0, hi0 = lo.copy(), hi.copy()
    oob = int(np.asarray(state.oob))
    quar = (int(np.asarray(state.quarantined))
            if state.quarantined is not None else 0)
    for i in range(a.shape[0]):
        bad = not (np.isfinite(a[i]) and np.all(np.isfinite(c[i])))
        if qlo is not None:
            bad = bad or bool(np.any((c[i] < np.asarray(qlo, np.float32))
                                     | (c[i] > np.asarray(qhi, np.float32))))
        if bad:
            quar += 1
            continue
        dist = np.sum(np.maximum(np.maximum(lo0 - c[i], c[i] - hi0),
                                 np.float32(0.0)), axis=-1)
        leaf = int(np.argmin(dist))
        oob += int(dist[leaf] > 0.0)

        delta[leaf, 0] += a[i]
        delta[leaf, 1] += a[i] * a[i]
        delta[leaf, 2] += np.float32(1.0)
        delta[leaf, 3] = min(delta[leaf, 3], a[i])
        delta[leaf, 4] = max(delta[leaf, 4], a[i])
        lo[leaf] = np.minimum(lo[leaf], c[i])
        hi[leaf] = np.maximum(hi[leaf], c[i])

        seen[leaf] += 1
        if kpl[leaf] < cap:
            slot = int(kpl[leaf])
            kpl[leaf] += 1
        else:
            j = int(np.float32(u[i]) * np.float32(seen[leaf]))
            slot = j if j < cap else -1
        if slot >= 0:
            sc[leaf, slot] = c[i]
            sa[leaf, slot] = a[i]
            sv[leaf, slot] = True
    return StreamState(leaf_lo=lo, leaf_hi=hi, delta_agg=delta, sample_c=sc,
                       sample_a=sa, sample_valid=sv, k_per_leaf=kpl,
                       seen=seen, oob=np.int32(oob),
                       quarantined=np.int32(quar))


__all__ = ["StreamState", "StreamingIngestor", "ingest_batch_reference",
           "init_state", "empty_delta_agg", "quarantine_mask"]
