"""Paper Table 3: preprocessing cost, query latency and accuracy vs k."""
from __future__ import annotations

import time

from repro.core import build_synopsis, answer, random_queries
from . import common


def run(rate: float = 0.005):
    c, a = common.dataset("nyc_taxi")
    K = max(int(rate * len(a)), 200)
    qs = random_queries(c, min(common.NQ, 200), seed=29)
    rows = []
    for k in (4, 8, 16, 32, 64, 128):
        t0 = time.perf_counter()
        syn, rep = build_synopsis(c, a, k=k, sample_budget=K, kind="sum",
                                  method="adp")
        build_s = time.perf_counter() - t0
        _, lat = common.timed(lambda: answer(syn, qs, kind="sum"
                                             ).estimate.block_until_ready())
        err, _, _ = common.median_err(syn, qs, c, a, "sum")
        rows.append({"k": k, "build_s": f"{build_s:.2f}",
                     "latency_ms_per_query": f"{lat*1000/qs.num_queries:.3f}",
                     "median_rel_err": f"{err*100:.3f}%"})
    return common.emit(rows, "table3")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
