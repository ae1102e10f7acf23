"""The benchmark's own float64 reference: exact SUM, COUNT and AVG of range
predicates ``lo <= c <= hi`` over the rows as they were handed to the
system (the float32 predicate bounds widened to float64).

``ground_truth_kinds`` is a copy of the program's host scan
(``repro.core.query.ground_truth_kinds``), kept as the plain statement of
the semantics. ``Table`` gives the same answers from one sort: prefix sums
over the first column, and a scan of the rows in that column's range for
the others. It imports nothing of the program.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


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
    """Rows (c, a) sorted once by the first predicate column; answers SUM
    and COUNT of a predicate batch exactly as :func:`ground_truth_kinds`."""

    def __init__(self, c, a):
        c = np.asarray(c, dtype=np.float64)
        c = c[:, None] if c.ndim == 1 else c
        a = np.asarray(a, dtype=np.float64).reshape(-1)
        order = np.argsort(c[:, 0], kind="stable")
        self.c = c[order]
        self.a = a[order]
        self.key = np.ascontiguousarray(self.c[:, 0])
        self.prefix = np.concatenate([[0.0], np.cumsum(self.a)])

    def sum_count(self, lo, hi) -> tuple[np.ndarray, np.ndarray]:
        lo = np.asarray(lo, np.float64)
        hi = np.asarray(hi, np.float64)
        i = np.searchsorted(self.key, lo[:, 0], side="left")
        j = np.searchsorted(self.key, hi[:, 0], side="right")
        j = np.maximum(i, j)
        if self.c.shape[1] == 1:
            return self.prefix[j] - self.prefix[i], (j - i).astype(np.float64)

        def one(q):
            cc = self.c[i[q]:j[q], 1:]
            m = np.all((lo[q, 1:] <= cc) & (cc <= hi[q, 1:]), axis=1)
            return self.a[i[q]:j[q]][m].sum(), float(m.sum())

        # numpy releases the interpreter lock in these scans
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            out = list(pool.map(one, range(lo.shape[0])))
        return (np.array([o[0] for o in out], np.float64).reshape(-1),
                np.array([o[1] for o in out], np.float64).reshape(-1))

