"""Paper Figures 4+5: median relative error and CI ratio of random SUM
queries vs sample rate (fixed 64 partitions)."""
from __future__ import annotations

from repro.core import build_synopsis, random_queries
from repro.core.baselines import stratified_synopsis, uniform_synopsis
from . import common


def run(B: int = 64):
    rows = []
    for ds in common.DATASETS:
        c, a = common.dataset(ds)
        qs = random_queries(c, common.NQ, seed=17)
        for rate in (0.001, 0.002, 0.005, 0.01, 0.02):
            K = max(int(rate * len(a)), 100)
            us, _ = uniform_synopsis(c, a, K)
            st, _ = stratified_synopsis(c, a, B, K)
            ps, _ = build_synopsis(c, a, k=B, sample_budget=K, kind="sum",
                                   method="adp")
            row = {"dataset": ds, "rate": rate}
            for name, syn, kw in (("US", us, {"use_aggregates": False}),
                                  ("ST", st, {"use_aggregates": False}),
                                  ("PASS", ps, {})):
                err, res, gt = common.median_err(syn, qs, c, a, "sum", **kw)
                row[name] = f"{err*100:.3f}%"
                row[name + "_ci"] = f"{common.median_ci(res, gt)*100:.2f}%"
            rows.append(row)
    return common.emit(rows, "fig4_5")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
