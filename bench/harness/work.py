"""The work the sample-moment pass has to do, counted from shapes, and the
trace ops that do it.

The count is a lower bound that reads the same whatever implements the
pass: only strata that a query overlaps partially need their samples
looked at (a covered stratum is answered from its exact aggregate, a
disjoint one contributes nothing).
"""
from __future__ import annotations

import numpy as np

F32 = 4
# Ops of the moment kernel carry the name of the jitted function that
# holds its pallas_call in their metadata.
MOMENT_KERNEL = "stratified_moments"


def partial_mask(qlo, qhi, leaf_lo, leaf_hi) -> np.ndarray:
    """(Q, k) True where a query overlaps a nonempty leaf box partially:
    neither covers it nor misses it (float32 comparisons, as served)."""
    qlo = np.asarray(qlo, np.float32)[:, None, :]
    qhi = np.asarray(qhi, np.float32)[:, None, :]
    lo = np.asarray(leaf_lo, np.float32)[None]
    hi = np.asarray(leaf_hi, np.float32)[None]
    nonempty = np.all(lo <= hi, axis=-1)
    cover = np.all((qlo <= lo) & (hi <= qhi), axis=-1)
    disjoint = np.any((qhi < lo) | (qlo > hi), axis=-1)
    return nonempty & ~cover & ~disjoint


def moment_pass_work(qlo, qhi, leaf_lo, leaf_hi, samples_per_leaf
                     ) -> tuple[float, float]:
    """(operations, bytes) one dispatch of these queries needs at least."""
    d = np.asarray(leaf_lo).shape[1]
    n = np.asarray(samples_per_leaf, np.float64)
    part = partial_mask(qlo, qhi, leaf_lo, leaf_hi)
    pairs = float(np.sum(part @ n))                  # (query, sample) pairs
    ops = pairs * (2 * d + 3 * 2)
    touched = np.any(part, axis=0)
    nbytes = (float(np.sum(n[touched])) * (d + 1) * F32
              + part.shape[0] * 2 * d * F32
              + float(np.sum(part)) * 3 * F32)
    return ops, nbytes


def least_seconds(run, peaks: dict) -> float | None:
    """Sum over the window's dispatches of the larger of operations over
    peak FLOP/s and bytes over peak bandwidth. Requests that shared a
    dispatch share its pulled result arrays, which tells them apart."""
    syn = run.engine.resolve()
    leaf_lo, leaf_hi = np.asarray(syn.leaf_lo), np.asarray(syn.leaf_hi)
    n = np.asarray(syn.k_per_leaf)
    kind = run.kinds[0]
    groups: dict[int, list] = {}
    for r in run.requests:
        if r.result is None or r.t_done > run.t_closed:
            continue
        base = r.result[kind].estimate.base
        groups.setdefault(id(base), []).append(r)
    if not groups:
        return None
    total = 0.0
    for reqs in groups.values():
        bounds = [run.query_bounds(r) for r in reqs]
        qlo = np.concatenate([b[0] for b in bounds])
        qhi = np.concatenate([b[1] for b in bounds])
        ops, nbytes = moment_pass_work(qlo, qhi, leaf_lo, leaf_hi, n)
        total += max(ops / peaks["bf16_flop_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total


def moment_ops(trace) -> list:
    """The trace's ops of the moment kernel: those whose name or metadata
    names it."""
    out = []
    for o in trace.ops:
        if MOMENT_KERNEL in o.name or any(
                isinstance(v, str) and MOMENT_KERNEL in v
                for v in o.meta.values()):
            out.append(o)
    return out
