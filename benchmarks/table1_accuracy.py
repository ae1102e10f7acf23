"""Paper Table 1: accuracy of US / ST / AQP++ / PASS-ESS / PASS-BSS{2x,10x}
on the three datasets for COUNT / SUM / AVG, controlling query latency.

ESS vs BSS accounting (paper §5.1.4): US/ST process their whole K-sample
synopsis per query. PASS skips to ~2 partial strata per 1-D query, so at
equal per-query work (ESS) it may hold K/2 samples per stratum; at bounded
storage (BSS-Nx) its total samples are capped at N * K.
"""
from __future__ import annotations

from repro.core import build_synopsis, random_queries
from repro.core.baselines import (uniform_synopsis, stratified_synopsis,
                                  aqppp_synopsis)
from . import common


def run(rate: float = 0.005, B: int = 64):
    rows = []
    for ds in common.DATASETS:
        c, a = common.dataset(ds)
        n = len(a)
        K = max(int(rate * n), 200)
        qs = random_queries(c, common.NQ, seed=11)
        us, _ = uniform_synopsis(c, a, K)
        st, _ = stratified_synopsis(c, a, B, K)
        ap = aqppp_synopsis(c, a, B, K)
        # ESS: per-query work for PASS is 2 strata -> K/2 samples per stratum
        ess, _ = build_synopsis(c, a, k=B, sample_budget=B * K // 2,
                                kind="sum", method="adp")
        bss2, _ = build_synopsis(c, a, k=B, sample_budget=2 * K,
                                 kind="sum", method="adp")
        bss10, _ = build_synopsis(c, a, k=B, sample_budget=10 * K,
                                  kind="sum", method="adp")
        for kind in ("count", "sum", "avg"):
            row = {"dataset": ds, "kind": kind}
            for name, syn, kw in (
                    ("US", us, {"use_aggregates": False}),
                    ("ST", st, {"use_aggregates": False}),
                    ("PASS-ESS", ess, {}),
                    ("PASS-BSS2x", bss2, {}),
                    ("PASS-BSS10x", bss10, {})):
                err, _, _ = common.median_err(syn, qs, c, a, kind, **kw)
                row[name] = f"{err * 100:.3f}%"
            err, _, _ = common.median_err(ap, qs, c, a, kind)
            row["AQP++"] = f"{err * 100:.3f}%"
            rows.append(row)
    return common.emit(rows, "table1")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
