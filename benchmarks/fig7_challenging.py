"""Paper Figure 7: ADP vs EQ on 'challenging' queries drawn from the
max-variance region of each real dataset (via the discretization oracle)."""
from __future__ import annotations

from repro.core import build_synopsis
from repro.core.query import challenging_queries
from . import common


def run(B: int = 64, rate: float = 0.005):
    rows = []
    for ds in common.DATASETS:
        c, a = common.dataset(ds)
        K = max(int(rate * len(a)), 200)
        adp, _ = build_synopsis(c, a, k=B, sample_budget=K, kind="sum",
                                method="adp")
        eq, _ = build_synopsis(c, a, k=B, sample_budget=K, kind="sum",
                               method="eq")
        qs = challenging_queries(c, a, common.NQ, seed=7)
        row = {"dataset": ds}
        for lbl, syn in (("EQ", eq), ("ADP", adp)):
            err, res, gt = common.median_err(syn, qs, c, a, "sum")
            row[lbl] = f"{err*100:.3f}%"
            row[lbl + "_ci"] = f"{common.median_ci(res, gt)*100:.2f}%"
        rows.append(row)
    return common.emit(rows, "fig7")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
