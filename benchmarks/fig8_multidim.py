"""Paper Figure 8: KD-PASS vs KD-US on multi-dimensional query templates
(NYC-taxi-like), plus KD-PASS skip rate per dimension."""
from __future__ import annotations

import numpy as np

from repro.core import build_synopsis, random_queries
from repro.core.baselines import aqppp_synopsis
from repro.core.estimators import skip_rate
from repro.data import synthetic
from . import common


def run(max_leaves: int = 64, rate: float = 0.02, max_dim: int = 4):
    rows = []
    for d in range(2, max_dim + 1):
        c, a = synthetic.nyc_taxi(scale=min(common.SCALE, 0.02), dims=d)
        K = max(int(rate * len(a)), 200)
        kd, _ = build_synopsis(c, a, k=max_leaves, sample_budget=K,
                               kind="sum", method="kd",
                               allocation="proportional")
        kdus = aqppp_synopsis(c, a, max_leaves, K, method="kd")
        qs = random_queries(c, min(common.NQ, 200), seed=19,
                            min_frac=0.3, max_frac=0.8)
        p_err, p_res, gt = common.median_err(kd, qs, c, a, "sum")
        u_err, u_res, _ = common.median_err(kdus, qs, c, a, "sum")
        sr = float(np.median(np.asarray(skip_rate(kd, qs))))
        rows.append({"dims": d,
                     "KD-US": f"{u_err*100:.3f}%",
                     "KD-PASS": f"{p_err*100:.3f}%",
                     "KD-US_ci": f"{common.median_ci(u_res, gt)*100:.2f}%",
                     "KD-PASS_ci": f"{common.median_ci(p_res, gt)*100:.2f}%",
                     "skip_rate": f"{sr*100:.1f}%"})
    return common.emit(rows, "fig8")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
