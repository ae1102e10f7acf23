"""The benchmark's own float64 reference: exact SUM, COUNT and AVG of range
predicates ``lo <= c <= hi`` over the rows as they were handed to the
system (the float32 predicate bounds widened to float64).

``ground_truth_kinds`` is a copy of the program's host scan
(``repro.core.query.ground_truth_kinds``), kept as the plain statement of
the semantics. ``Table`` gives the same answers from an index built once:
for one predicate column, one sort and prefix sums; for more, a ``Grid``
of quantile bins with exact prefix sums over the bins a box covers whole
and a scan of the rows in the bins it cuts. It imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np

CELLS = 1 << 24      # grid cells at most (3 prefix arrays of 8 bytes each)


def ground_truth_kinds(c, a, lo, hi, chunk: int = 262144) -> dict:
    """{"sum", "count", "avg"} (Q,) float64 by a chunked scan of every row."""
    c = np.asarray(c, dtype=np.float64)
    c2 = c[:, None] if c.ndim == 1 else c
    a = np.asarray(a, dtype=np.float64).reshape(-1)
    q_lo = np.asarray(lo, dtype=np.float64)
    q_hi = np.asarray(hi, dtype=np.float64)
    Q = q_lo.shape[0]
    s = np.zeros(Q)
    cnt = np.zeros(Q)
    for start in range(0, c2.shape[0], chunk):
        cc = c2[start:start + chunk]
        aa = a[start:start + chunk]
        pred = np.ones((Q, cc.shape[0]), dtype=bool)
        for j in range(cc.shape[1]):
            cj = cc[:, j]
            pred &= (q_lo[:, j:j + 1] <= cj) & (cj <= q_hi[:, j:j + 1])
        s += pred @ aa
        cnt += pred.sum(axis=1)
    return {"sum": s, "count": cnt, "avg": s / np.maximum(cnt, 1)}


class Table:
    """Rows (c, a) indexed once; answers SUM and COUNT of a predicate batch
    exactly as :func:`ground_truth_kinds`: counts equal, sums to float64
    rounding. One predicate column: rows sorted by it and prefix sums."""

    def __init__(self, c, a):
        c = np.asarray(c, dtype=np.float64)
        c = c[:, None] if c.ndim == 1 else c
        a = np.asarray(a, dtype=np.float64).reshape(-1)
        self.dims = c.shape[1]
        if self.dims > 1:
            self.grid = Grid(c, a)
            return
        order = np.argsort(c[:, 0], kind="stable")
        self.c = c[order]
        self.a = a[order]
        self.key = np.ascontiguousarray(self.c[:, 0])
        self.prefix = np.concatenate([[0.0], np.cumsum(self.a)])

    def sum_count(self, lo, hi) -> tuple[np.ndarray, np.ndarray]:
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        if self.dims > 1:
            return self.grid.sum_count(lo, hi)
        i = np.searchsorted(self.key, lo[:, 0], side="left")
        j = np.searchsorted(self.key, hi[:, 0], side="right")
        j = np.maximum(i, j)
        return self.prefix[j] - self.prefix[i], (j - i).astype(np.float64)


class Grid:
    """d > 1 predicate columns. Each column is cut into bins at its
    quantiles; a bin is a run of values, ``bin_min[j][b]`` to
    ``bin_max[j][b]``, both data values, disjoint from the next. A box
    covers some bins of each column whole (a range ``F``) and cuts at
    most two more (``I`` is ``F`` and those). Rows in the ``F`` box are
    summed from exact prefix sums over the grid of bins; every other row
    of the ``I`` box lies in exactly one slab: the first column ``j`` in
    which its bin is cut, with the columns before ``j`` in ``F``. Each
    slab is scanned with the predicate, in a copy of the rows sorted by
    (bin of column ``j``, value of column ``k``), ``k`` = ``j + 1`` mod d,
    so a cut bin's rows in range in column ``k`` are one slice.

    Sums are exact in the prefix sums: each value is split into a
    multiple of ``unit`` (summed as integers, exactly) and a remainder
    under ``unit / 2`` (summed in float64, whose rounding is far below the
    float64 rounding of the result)."""

    def __init__(self, c, a):
        n, d = c.shape
        self.d = d
        per = _bins_per_column(min(CELLS, 4 * n), d)
        self.bin_min, self.bin_max, bins, orders = [], [], [], []
        for j in range(d):
            order = np.argsort(c[:, j], kind="stable")
            s = c[order, j]
            edges = np.unique(s[(np.arange(per) * n) // per])
            b = np.empty(n, np.int16)            # per <= 4096 bins
            b[order] = np.searchsorted(edges, s, side="right") - 1
            last = np.searchsorted(s, edges[1:], side="left") - 1
            self.bin_min.append(edges)
            self.bin_max.append(np.append(s[last], s[-1]))
            bins.append(b)
            orders.append(order)
        shape = tuple(e.size for e in self.bin_min)
        cell = np.ravel_multi_index(bins, shape)

        scale = float(np.max(np.abs(a)))
        bits = 52 - int(np.ceil(np.log2(n + 1)))
        self.unit = 2.0 ** (int(np.ceil(np.log2(scale))) - bits) \
            if scale > 0 else 1.0
        whole = np.rint(a / self.unit)
        rest = a - whole * self.unit          # exact: unit >= ulp(a)
        # every partial sum of ``whole`` is an integer under 2**52: exact
        size = int(np.prod(shape))
        self.p_count = _prefix(np.bincount(cell, minlength=size), shape)
        self.p_whole = _prefix(np.bincount(
            cell, weights=whole, minlength=size).astype(np.int64), shape)
        self.p_rest = _prefix(np.bincount(cell, weights=rest,
                                          minlength=size), shape)

        self.copies = []
        for j in range(d):
            k = (j + 1) % d
            by_k = orders[k]                     # then stably by bin of j
            order = by_k[np.argsort(bins[j][by_k], kind="stable")]
            off = np.searchsorted(bins[j][order], np.arange(shape[j] + 1))
            self.copies.append((k, off, [np.ascontiguousarray(c[order, i])
                                         for i in range(d)], a[order]))

    def ranges(self, lo, hi):
        """Per column (Q,) bin ranges: ``F`` = [f0, f1) covered whole,
        ``I`` = [i0, i1) meeting the box."""
        f0, f1, i0, i1 = [], [], [], []
        for j in range(self.d):
            bmin, bmax = self.bin_min[j], self.bin_max[j]
            f0.append(np.searchsorted(bmin, lo[:, j], side="left"))
            f1.append(np.searchsorted(bmax, hi[:, j], side="right"))
            i0.append(np.searchsorted(bmax, lo[:, j], side="left"))
            i1.append(np.searchsorted(bmin, hi[:, j], side="right"))
        return [np.stack(x, axis=1) for x in (f0, f1, i0, i1)]

    def covered(self, f0, f1):
        """(sum, count) of the rows in each query's ``F`` box."""
        empty = np.any(f0 >= f1, axis=1)
        n = np.zeros(f0.shape[0], np.int64)
        whole = np.zeros(f0.shape[0], np.int64)
        rest = np.zeros(f0.shape[0])
        for corner in range(1 << self.d):
            pick = [(corner >> j) & 1 for j in range(self.d)]
            idx = tuple(np.where(empty, 0, f1[:, j] if p else f0[:, j])
                        for j, p in enumerate(pick))
            sign = 1 if (self.d - sum(pick)) % 2 == 0 else -1
            n += sign * self.p_count[idx]
            whole += sign * self.p_whole[idx]
            rest += sign * self.p_rest[idx]
        s = np.where(n > 0, whole * self.unit + rest, 0.0)
        return s, n.astype(np.float64)

    def cut(self, lo, hi, f0, f1, i0, i1) -> tuple[float, int]:
        """(sum, count) of one query's rows outside its ``F`` box, by a
        scan of its slabs."""
        s, n = 0.0, 0
        low, high = lo.copy(), hi.copy()
        for j in range(self.d):
            if j and f0[j - 1] >= f1[j - 1]:
                break              # no row has its earlier bins in F
            if f0[j] < f1[j]:
                cut = [b for b, keep in ((i0[j], i0[j] < f0[j]),
                                         (i1[j] - 1, i1[j] > f1[j])) if keep]
            else:
                cut = range(i0[j], i1[j])
            k, off, cols, a = self.copies[j]
            for b in cut:
                ck = cols[k][off[b]:off[b + 1]]
                u = off[b] + np.searchsorted(ck, low[k], side="left")
                v = off[b] + np.searchsorted(ck, high[k], side="right")
                if v <= u:
                    continue
                m = None
                for i in range(self.d):
                    if i != k:
                        x = cols[i][u:v]
                        t = (low[i] <= x) & (x <= high[i])
                        m = t if m is None else m & t
                s += float(a[u:v] @ m)
                n += int(np.count_nonzero(m))
            if f0[j] < f1[j]:      # later slabs keep this column in F
                low[j] = self.bin_min[j][f0[j]]
                high[j] = self.bin_max[j][f1[j] - 1]
        return s, n

    def sum_count(self, lo, hi) -> tuple[np.ndarray, np.ndarray]:
        f0, f1, i0, i1 = self.ranges(lo, hi)
        s, n = self.covered(f0, f1)
        some = ~np.any(lo > hi, axis=1) & np.all(i0 < i1, axis=1)
        todo = np.flatnonzero(some & np.any((i0 < f0) | (i1 > f1)
                                            | (f0 >= f1), axis=1))
        for q in todo:
            sq, nq = self.cut(lo[q], hi[q], f0[q], f1[q], i0[q], i1[q])
            s[q] += sq
            n[q] += nq
        return s, n


def _bins_per_column(cells: int, d: int) -> int:
    per = 2
    while (per + 1) ** d <= cells:
        per += 1
    return per


def _prefix(per_cell, shape) -> np.ndarray:
    """Inclusive prefix sums over the grid, with a zero row in front along
    every axis: entry ``[b0, .., bd]`` sums the cells below it."""
    p = np.asarray(per_cell).reshape(shape)
    for ax in range(len(shape)):
        p = np.cumsum(p, axis=ax)
    return np.pad(p, [(1, 0)] * len(shape))
