"""The comparison that decides ``correct``.

Every answered request is held to the configuration's guarantees against
the benchmark's float64 reference (``reference/f64.py``), over the rows as
they were handed to the system: the base table, plus, in an ingest cell,
every stream batch acknowledged before the request was submitted (and at
most those handed to ``ingest`` before it was answered).

Numbers compared, each against the limit in the configuration's ``check``
section (``correct`` is true when every number is at most its limit; a
number whose limit is null is printed and not compared):

* ``unanswered``: admitted requests that raised or never resolved within a
  minute past the close. A shed request is a refusal, not a wrong answer.
* ``bound_miss``: (query, kind) answers whose truth lies outside the hard
  bounds ``[lower, upper]`` (paper §2.3), beyond float32 rounding of a sum
  over the leaves. A request that holds another request's rows, or misses
  an acknowledged stream batch, falls outside them.
* ``ci_miss``: the share of non-point 95% intervals that miss the truth.
* ``stale_reads``, in an ingest cell: whole-table reads whose exact count
  names a state of the stream outside those the guarantee allows, most
  often one that misses a batch acknowledged before the read was
  submitted.
* ``covered_err_ulp``: over the covered probe (predicates that cut no leaf,
  answered exactly by the aggregate tree), the largest distance of the
  estimate or an interval end from the truth, in units of 2^-24 of the
  truth.
"""
from __future__ import annotations

import numpy as np

ULP = 2.0 ** -24


def _rounding(k: int) -> float:
    """Relative float32 rounding of a sum over up to k leaf aggregates."""
    return (2 * k + 4) * ULP


class Truth:
    """Exact answers of predicate batches over the base rows plus the first
    ``j`` stream batches (pool batch ``i % P`` for the ``i``-th)."""

    def __init__(self, c, a, stream=None):
        from bench.reference import f64
        self.base = f64.Table(c, a)
        self.batches = []
        if stream is not None:
            for p in range(stream.pool_batches):
                bc, ba = stream.batch(p)
                self.batches.append(f64.Table(bc, ba))

    def parts(self, lo, hi):
        """(base sum, base count, per-pool-batch sums (P, Q), counts)."""
        s, n = self.base.sum_count(lo, hi)
        ps = np.zeros((len(self.batches), s.size))
        pn = np.zeros_like(ps)
        for p, t in enumerate(self.batches):
            ps[p], pn[p] = t.sum_count(lo, hi)
        return s, n, ps, pn

    def at(self, parts, j: int, sl=slice(None)) -> dict:
        s, n, ps, pn = parts
        s, n = s[sl].copy(), n[sl].copy()
        P = len(self.batches)
        if P and j:
            times = np.full(P, j // P)
            times[: j % P] += 1
            s += times @ ps[:, sl]
            n += times @ pn[:, sl]
        return {"sum": s, "count": n, "avg": s / np.maximum(n, 1)}


def _state_counts(truth: Truth, bounds, last: int) -> np.ndarray:
    """Exact counts of one predicate over the base rows plus the first j
    stream batches, for j = 0 .. ``last`` + 1."""
    s, n, ps, pn = truth.parts(*bounds)
    P = pn.shape[0]
    per = pn[np.arange(last + 1) % P, 0]
    return n[0] + np.concatenate([[0.0], np.cumsum(per)])


def _kinds_mask(kind: str, truth: dict) -> np.ndarray:
    # AVG of an empty selection has no defined value
    return truth["count"] > 0 if kind == "avg" else np.ones(
        truth["count"].shape, bool)


def bound_misses(res: dict, truth: dict, kinds, tol_rel: float) -> int:
    miss = 0
    for kind in kinds:
        r, t = res[kind], truth[kind]
        m = _kinds_mask(kind, truth)
        lo = np.asarray(r.lower, np.float64)
        hi = np.asarray(r.upper, np.float64)
        tol = tol_rel * np.maximum(np.maximum(np.abs(lo), np.abs(hi)),
                                   np.abs(t)) + 1e-30
        bad = ~np.isfinite(lo) | ~np.isfinite(hi) | (t < lo - tol) \
            | (t > hi + tol)
        miss += int(np.sum(bad & m))
    return miss


def interval_scores(res: dict, truth: dict, kinds, tol_rel: float):
    """(scored, missed) non-point intervals."""
    scored = missed = 0
    for kind in kinds:
        r, t = res[kind], truth[kind]
        m = _kinds_mask(kind, truth)
        lo = np.asarray(r.ci_lo, np.float64)
        hi = np.asarray(r.ci_hi, np.float64)
        tol = tol_rel * np.abs(t) + 1e-30
        wide = (hi > lo) & m
        scored += int(np.sum(wide))
        missed += int(np.sum(wide & ((t < lo - tol) | (t > hi + tol))))
    return scored, missed


def covered_error_ulp(res: dict, truth: dict, kinds) -> float:
    worst = 0.0
    for kind in kinds:
        r, t = res[kind], truth[kind]
        m = _kinds_mask(kind, truth)
        if not np.any(m):
            continue
        d = np.zeros(t.shape)
        for field in ("estimate", "ci_lo", "ci_hi"):
            v = np.asarray(getattr(r, field), np.float64)
            d = np.maximum(d, np.where(np.isfinite(v), np.abs(v - t), np.inf))
        worst = max(worst, float(np.max(d[m] / np.maximum(np.abs(t[m]), 1.0)
                                        / ULP)))
    return worst


def compare(run) -> tuple[dict, list[str]]:
    """({number: (value, limit)}, lines of detail) for a finished run."""
    cfg = run.config["check"]
    limits = cfg["limits"]
    kinds = run.kinds
    tol = _rounding(run.sizes.k)
    truth = Truth(run.c, run.a, run.stream)
    answered = [r for r in run.requests if r.result is not None]
    lines = [f"check: compared {len(answered)} window requests "
             f"({sum(r.qidx.size for r in answered)} queries) and "
             f"{len(run.probes)} probe requests"]

    unanswered = sum(1 for r in run.requests + run.probes
                     if not r.shed and (r.t_done is None or r.error))
    bmiss = scored = missed = 0
    outside = {"earlier": 0, "later": 0}
    if answered:
        bounds = [run.query_bounds(r) for r in answered]
        lo = np.concatenate([b[0] for b in bounds])
        hi = np.concatenate([b[1] for b in bounds])
        parts = truth.parts(lo, hi)
        off = 0
        for r in answered:
            sl = slice(off, off + r.qidx.size)
            off += r.qidx.size

            def fit(j, r=r, sl=sl):
                t = truth.at(parts, j, sl)
                return (bound_misses(r.result, t, kinds, tol),
                        *interval_scores(r.result, t, kinds, tol)[::-1])

            if run.stream is None:
                best = fit(0)
            else:
                # the served state is one of those the guarantee allows;
                # score the answer against the one it fits best
                j0, j1 = r.acked_at_submit, r.dispatched_at_done
                best = min((fit(j) for j in range(j0, j1 + 1)),
                           key=lambda f: (f[0], f[1]))
                # the state one batch before and one after the allowed
                # ones: an answer served from a state that misses an
                # acknowledged batch fits the one before better, while
                # the one after, which no answer can have been served
                # from, counts how often that happens by chance
                for j, name in ((j0 - 1, "earlier"), (j1 + 1, "later")):
                    if j >= 0 and fit(j)[:2] < best[:2]:
                        outside[name] += 1
            bmiss += best[0]
            missed += best[1]
            scored += best[2]
    ci_miss = missed / scored if scored else 0.0
    if run.stream is not None:
        lines.append(f"check: answers fitting a state outside the allowed "
                     f"ones better: one batch earlier {outside['earlier']}, "
                     f"one batch later {outside['later']} (chance)")

    stale = None
    if run.stream is not None:
        stale = 0
        whole = [r for r in run.requests if r.whole and r.result is not None]
        if whole:
            counts = _state_counts(truth, run.query_bounds(whole[0]),
                                   max(r.dispatched_at_done for r in whole))
            for r in whole:
                got = float(np.asarray(r.result["count"].estimate)[0])
                j = int(np.argmin(np.abs(counts - got)))
                stale += not r.acked_at_submit <= j <= r.dispatched_at_done
        lines.append(f"check: {len(whole)} whole-table reads, {stale} "
                     f"served from a state outside the allowed ones")

    cov_err = 0.0
    probes = [r for r in run.probes if r.result is not None]
    if probes:
        j = run.stream.dispatched if run.stream is not None else 0
        for r in probes:
            lo, hi = run.query_bounds(r)
            t = truth.at(truth.parts(lo, hi), j)
            cov_err = max(cov_err, covered_error_ulp(r.result, t, kinds))
            bmiss += bound_misses(r.result, t, kinds, tol)
    lines.append(f"check: {scored} non-point intervals scored, "
                 f"{missed} missed; {sum(r.qidx.size for r in probes)} "
                 f"covered probe queries")
    values = {"unanswered": unanswered, "bound_miss": bmiss,
              "ci_miss": ci_miss, "stale_reads": stale,
              "covered_err_ulp": cov_err}
    values = {k: v for k, v in values.items() if v is not None}
    numbers = {k: (float(v), float(limits[k])) for k, v in values.items()
               if limits[k] is not None}
    lines += [f"check: {k}={float(v)!r} is not compared (no limit)"
              for k, v in values.items() if limits[k] is None]
    return numbers, lines
