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

import time

import numpy as np

ULP = 2.0 ** -24


def _rounding(k: int) -> float:
    """Relative float32 rounding of a sum over up to k leaf aggregates."""
    return (2 * k + 4) * ULP


class IndexMismatch(RuntimeError):
    """The reference's index disagrees with the plain scan."""


class Truth:
    """Exact answers of predicate batches over the base rows plus the first
    ``j`` stream batches (pool batch ``i % P`` for the ``i``-th).

    With more than one predicate column the answers come from an index
    (``f64.Grid``); every call re-answers a subset of its predicates,
    drawn from ``seed``, by the plain scan, and raises ``IndexMismatch``
    unless every count is equal and every sum within ``SELF_CHECK_RTOL``."""

    SELF_CHECKED = 32
    SELF_CHECK_RTOL = 1e-12

    def __init__(self, c, a, stream=None, seed: int = 0):
        from bench.reference import f64
        self.tables = [(f64.Table(c, a), c, a)]
        if stream is not None:
            for p in range(stream.pool_batches):
                bc, ba = stream.batch(p)
                self.tables.append((f64.Table(bc, ba), bc, ba))
        self.rng = np.random.default_rng(seed)
        self.self_checked = 0

    def parts(self, lo, hi):
        """(base sum, base count, per-pool-batch sums (P, Q), counts); each
        distinct predicate is answered once."""
        lo, hi = np.asarray(lo), np.asarray(hi)
        key = np.ascontiguousarray(np.concatenate([lo, hi], axis=1))
        rows = key.view(np.dtype((np.void, key.dtype.itemsize
                                  * key.shape[1]))).ravel()
        _, first, back = np.unique(rows, return_index=True,
                                   return_inverse=True)
        lo, hi = lo[first], hi[first]
        out = [t.sum_count(lo, hi) for t, _c, _a in self.tables]
        if lo.shape[1] > 1:
            self._self_check(lo, hi, out)
        s, n = out[0]
        ps = np.array([o[0] for o in out[1:]]).reshape(-1, s.size)
        pn = np.array([o[1] for o in out[1:]]).reshape(-1, s.size)
        return s[back], n[back], ps[:, back], pn[:, back]

    def _self_check(self, lo, hi, out) -> None:
        from bench.reference import f64
        sub = np.sort(self.rng.choice(lo.shape[0], min(
            self.SELF_CHECKED, lo.shape[0]), replace=False))
        for (_t, c, a), (s, n) in zip(self.tables, out):
            want = f64.ground_truth_kinds(c, a, lo[sub], hi[sub])
            bad = (n[sub] != want["count"]) | (
                np.abs(s[sub] - want["sum"])
                > self.SELF_CHECK_RTOL * np.abs(want["sum"]))
            if np.any(bad):
                q = sub[np.flatnonzero(bad)[0]]
                raise IndexMismatch(
                    f"reference index disagrees with the plain scan on "
                    f"{int(bad.sum())} of {sub.size} predicates; first: "
                    f"lo={lo[q].tolist()} hi={hi[q].tolist()} index "
                    f"(sum {s[q]!r}, count {n[q]!r}) scan (sum "
                    f"{want['sum'][bad][0]!r}, count "
                    f"{want['count'][bad][0]!r})")
        self.self_checked += sub.size

    def at(self, parts, j: int, sl=slice(None)) -> dict:
        s, n, ps, pn = parts
        s, n = s[sl].copy(), n[sl].copy()
        P = len(self.tables) - 1
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
    t0 = time.perf_counter()
    truth = Truth(run.c, run.a, run.stream, seed=run.seed)
    t_index = time.perf_counter() - t0
    answered = [r for r in run.requests if r.result is not None]
    probes = [r for r in run.probes if r.result is not None]
    lines = [f"check: compared {len(answered)} window requests "
             f"({sum(r.qidx.size for r in answered)} queries) and "
             f"{len(run.probes)} probe requests"]

    unanswered = sum(1 for r in run.requests + run.probes
                     if not r.shed and (r.t_done is None or r.error))
    # one pass of the reference over every query compared, window first
    bounds = [run.query_bounds(r) for r in answered + probes]
    slices, off = [], 0
    for b in bounds:
        slices.append(slice(off, off + b[0].shape[0]))
        off += b[0].shape[0]
    if bounds:
        parts = truth.parts(np.concatenate([b[0] for b in bounds]),
                            np.concatenate([b[1] for b in bounds]))
    lines.append(f"check: reference answered {off} queries in "
                 f"{time.perf_counter() - t0!r} s (index {t_index!r} s); "
                 f"{truth.self_checked} re-answered by the plain scan")
    bmiss = scored = missed = 0
    outside = {"earlier": 0, "later": 0}
    for r, sl in zip(answered, slices):

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
    j = run.stream.dispatched if run.stream is not None else 0
    for r, sl in zip(probes, slices[len(answered):]):
        t = truth.at(parts, j, sl)
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
