"""CI calibration: empirical coverage vs nominal level (paper §5, the
reliability claim behind Fig. 1's "trustworthy intervals" pitch).

For each trial a fresh stratified sample is drawn (new build seed) and a
query workload is answered with calibrated intervals
(``PassEngine(syn, ci=CIConfig(level)).answer(qs)``); coverage is the
fraction of queries whose ground truth lands inside [lo, hi]. Compared
estimators:

* ``pass``       — PASS synopsis: exact-covered strata contribute zero
  variance, sampled strata CLT + small-n Bernstein fallback, the
  per-stratum delta budget (``delta_budget="stratum"``);
* ``pass_union`` — same engine, ``delta_budget="union"``: the fallback
  failure probability is split across the *actually-fallback* strata of
  each query (delta/n_fb), tightening Bernstein half-widths when few
  strata fall back. Sweep outcome (2026-08, defaults + a fallback-heavy
  samples_per_leaf=8 point): union coverage is indistinguishable from
  stratum (CLT cells dominate the default config; the fallback-heavy
  config saturates at 100% either way) and does not clear >= nominal on
  sum/avg at the default config (94.2-94.4% vs 95%), so the engine
  default REMAINS ``delta_budget="stratum"``; union stays selectable;
* ``uniform``    — single-stratum uniform sample with plain CLT intervals
  and no exact shortcut (``use_aggregates=False``): the baseline whose
  intervals the paper calls unreliable at small effective sample sizes.

Coverage is reported per selectivity bucket (small-selectivity queries are
where the uniform CLT under-covers) and overall, for each requested kind
and level. The PASS build is wall-clock timed as the build-path smoke.

Run: PYTHONPATH=src python -m benchmarks.fig_ci_calibration [out.json]
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.api import PassEngine, ServingConfig, CIConfig
from repro.core import build_synopsis, ground_truth, random_queries

SEL_BUCKETS = ((0.0, 0.02), (0.02, 0.1), (0.1, 1.01))
KINDS = ("sum", "count", "avg")


def _coverage(lo, hi, truth):
    return (np.asarray(lo, np.float64) <= truth) \
        & (truth <= np.asarray(hi, np.float64))


def run(n=100_000, k=64, samples_per_leaf=64, Q=200, trials=8,
        levels=(0.95,), kinds=KINDS, seed=0, backend=None, verbose=True):
    """Returns (metrics dict, table rows). Coverage keys:
    ``ci_cal_{method}_{kind}_cov{level%}`` in [0, 1]."""
    rng = np.random.default_rng(seed)
    c = np.sort(rng.uniform(0, 100, n))
    a = rng.lognormal(0, 1, n) * (1 + np.sin(c / 5))
    budget = k * samples_per_leaf

    qs = random_queries(c, Q, seed=seed + 1, min_frac=0.005, max_frac=0.4)
    truth = {kind: ground_truth(c, a, qs, kind=kind) for kind in kinds}
    sel = (truth["count"] if "count" in truth
           else ground_truth(c, a, qs, kind="count")) / n

    build_ms = []
    hits = {}        # (method, kind, level) -> (trials, Q) bool
    for t in range(trials):
        t0 = time.perf_counter()
        syn, _ = build_synopsis(c, a, k=k, sample_budget=budget,
                                method="eq", seed=seed + 10 + t)
        build_ms.append((time.perf_counter() - t0) * 1e3)
        uni, _ = build_synopsis(c, a, k=1, sample_budget=budget,
                                method="eq", seed=seed + 10 + t)
        eng_p = PassEngine(syn, serving=ServingConfig(kinds=tuple(kinds),
                                                      backend=backend))
        eng_u = PassEngine(uni, serving=ServingConfig(
            kinds=tuple(kinds), backend=backend, use_aggregates=False))
        for level in levels:
            res_p = eng_p.answer(qs, ci=CIConfig(level=level,
                                                 delta_budget="stratum"))
            res_pu = eng_p.answer(qs, ci=CIConfig(level=level,
                                                  delta_budget="union"))
            res_u = eng_u.answer(qs, ci=CIConfig(level=level))
            for kind in kinds:
                for method, res in (("pass", res_p), ("pass_union", res_pu),
                                    ("uniform", res_u)):
                    _, lo, hi = res[kind].interval()
                    hits.setdefault((method, kind, level), []).append(
                        _coverage(lo, hi, truth[kind]))

    metrics = {"ci_cal_build_synopsis_ms": float(np.median(build_ms))}
    rows = []
    for (method, kind, level), h in sorted(hits.items()):
        h = np.asarray(h)                               # (trials, Q)
        overall = float(h.mean())
        metrics[f"ci_cal_{method}_{kind}_cov{int(round(level * 100))}"] = \
            overall
        row = {"method": method, "kind": kind, "level": level,
               "coverage": overall, "buckets": {}}
        for blo, bhi in SEL_BUCKETS:
            m = (sel >= blo) & (sel < bhi)
            if m.any():
                row["buckets"][f"sel[{blo:g},{bhi:g})"] = \
                    float(h[:, m].mean())
        rows.append(row)

    if verbose:
        print(f"CI calibration: n={n}, k={k}, {samples_per_leaf}/leaf, "
              f"Q={Q}, trials={trials}")
        print(f"  build_synopsis median: {metrics['ci_cal_build_synopsis_ms']:.1f} ms")
        for row in rows:
            buckets = "  ".join(f"{b}={v * 100:5.1f}%"
                                for b, v in row["buckets"].items())
            print(f"  {row['method']:8s} {row['kind']:6s} "
                  f"nominal={row['level'] * 100:4.1f}%  "
                  f"coverage={row['coverage'] * 100:5.1f}%  {buckets}")
    return metrics, rows


def tiny_config() -> dict:
    """CI-sized run (bench_smoke)."""
    return dict(n=20_000, k=32, samples_per_leaf=48, Q=96, trials=3,
                levels=(0.95,))


def main(out_path: str | None = None) -> None:
    metrics, rows = run()
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"metrics": metrics, "table": rows}, f, indent=2,
                      sort_keys=True)
        print(f"wrote {out_path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(*sys.argv[1:2])
