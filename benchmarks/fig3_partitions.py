"""Paper Figure 3: median relative error of random SUM queries vs the number
of partitions (fixed sample rate)."""
from __future__ import annotations

from repro.core import build_synopsis, random_queries
from repro.core.baselines import stratified_synopsis, uniform_synopsis
from . import common


def run(rate: float = 0.005):
    rows = []
    for ds in common.DATASETS:
        c, a = common.dataset(ds)
        K = max(int(rate * len(a)), 200)
        qs = random_queries(c, common.NQ, seed=13)
        us, _ = uniform_synopsis(c, a, K)
        us_err, _, _ = common.median_err(us, qs, c, a, "sum",
                                         use_aggregates=False)
        for k in (8, 16, 32, 64, 128):
            ps, _ = build_synopsis(c, a, k=k, sample_budget=K, kind="sum",
                                   method="adp")
            st, _ = stratified_synopsis(c, a, k, K)
            p_err, _, _ = common.median_err(ps, qs, c, a, "sum")
            s_err, _, _ = common.median_err(st, qs, c, a, "sum",
                                            use_aggregates=False)
            rows.append({"dataset": ds, "k": k,
                         "US": f"{us_err*100:.3f}%",
                         "ST": f"{s_err*100:.3f}%",
                         "PASS": f"{p_err*100:.3f}%"})
    return common.emit(rows, "fig3")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
