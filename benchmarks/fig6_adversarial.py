"""Paper Figure 6: ADP vs EQ partitioning on the adversarial dataset
(875k zeros + normal tail), random and tail-focused queries."""
from __future__ import annotations

from repro.core import build_synopsis, random_queries
from . import common


def run(B: int = 64, rate: float = 0.005):
    c, a = common.dataset("adversarial")
    K = max(int(rate * len(a)), 200)
    adp, _ = build_synopsis(c, a, k=B, sample_budget=K, kind="sum",
                            method="adp")
    eq, _ = build_synopsis(c, a, k=B, sample_budget=K, kind="sum",
                           method="eq")
    tail_lo = c[len(c) - len(c) // 8]
    workloads = {"random": random_queries(c, common.NQ, seed=5),
                 "tail": random_queries(c[c >= tail_lo], common.NQ, seed=6)}
    rows = []
    for wname, qs in workloads.items():
        row = {"workload": wname}
        for lbl, syn in (("EQ", eq), ("ADP", adp)):
            err, res, gt = common.median_err(syn, qs, c, a, "sum")
            row[lbl] = f"{err*100:.3f}%"
            row[lbl + "_ci"] = f"{common.median_ci(res, gt)*100:.2f}%"
        rows.append(row)
    return common.emit(rows, "fig6")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
