"""Quickstart: build a PASS synopsis once, serve many queries through the
`PassEngine` facade.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import PassEngine, ServingConfig
from repro.core import (build_synopsis, ground_truth, random_queries,
                        relative_error, ci_ratio)
from repro.data import synthetic


def main():
    # ~380k taxi-like rows: predicate = pickup time, aggregate = distance.
    c, a = synthetic.nyc_taxi(scale=0.05)
    print(f"dataset: {len(a):,} rows")

    # Budgets (paper §3.1): k leaf partitions (construction budget tau_c),
    # 0.5% stratified samples (query-latency budget tau_q).
    syn, report = build_synopsis(c, a, k=64, sample_rate=0.005,
                                 kind="sum", method="adp")
    print(f"built PASS synopsis in {report.seconds_total:.2f}s "
          f"(k={report.k}, samples={report.total_samples})")

    # Configure once, serve many: every kind below comes from ONE shared
    # classification + moment pass per batch.
    kinds = ("sum", "count", "avg", "min", "max")
    eng = PassEngine(syn, serving=ServingConfig(kinds=kinds))

    queries = random_queries(c, 500, seed=0)
    res = eng.answer(queries)
    for kind in kinds:
        gt = ground_truth(c, a, queries, kind=kind)
        keep = np.abs(gt) > 1e-9
        err = np.median(relative_error(res[kind], gt)[keep])
        print(f"{kind:6s} median rel err {err*100:6.3f}%", end="")
        if kind in ("sum", "count", "avg"):
            ci = np.median(ci_ratio(res[kind], gt)[keep])
            inside = np.mean((np.asarray(res[kind].lower) <= gt)
                             & (gt <= np.asarray(res[kind].upper)))
            print(f"   CI ratio {ci*100:5.2f}%   hard-bound containment "
                  f"{inside*100:.1f}%")
        else:
            print()

    # Steady-state serving: pin the batch shape once, then every call
    # skips the per-call Python re-setup entirely.
    prepared = eng.prepare(queries)
    prepared(queries)                      # second call AOT-compiles
    again = prepared(random_queries(c, 500, seed=1))
    print(f"prepared handle answered {len(np.asarray(again['sum'].estimate))} "
          f"queries; engine stats: {eng.stats()}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
