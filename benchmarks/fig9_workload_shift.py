"""Paper Figure 9: workload shift — a KD-PASS synopsis built for the 2-D
template answers 1-D..4-D templates that share attributes. Extended with
the §4.5 *data* shift scenario: rows keep streaming after the build
(distribution drift), served via the streaming subsystem's delta-merge and
re-optimized when the drift policy trips."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import build_synopsis, random_queries, ground_truth, \
    relative_error, answer
from repro.core.types import QueryBatch
from repro.core.estimators import skip_rate
from repro.data import synthetic
from repro.streaming import StreamingIngestor, DriftPolicy
from . import common


def run(max_leaves: int = 64, rate: float = 0.02, max_dim: int = 4):
    # Build once on the 4-D table but with partitioning driven by dims 0-1
    # (the 2-D template); query templates use the first t dims.
    c, a = synthetic.nyc_taxi(scale=min(common.SCALE, 0.02), dims=max_dim)
    K = max(int(rate * len(a)), 200)
    kd2, _ = build_synopsis(c[:, :2], a, k=max_leaves, sample_budget=K,
                            kind="sum", method="kd")
    rows = []
    for t in range(1, max_dim + 1):
        qs_t = random_queries(c[:, :t], min(common.NQ, 200), seed=23,
                              min_frac=0.05, max_frac=0.5)
        # lift the t-dim template onto the synopsis' 2 predicate columns:
        # unconstrained shared dims become +-inf bounds.
        lo = np.full((qs_t.lo.shape[0], 2), -np.inf, np.float32)
        hi = np.full((qs_t.lo.shape[0], 2), np.inf, np.float32)
        shared = min(t, 2)
        lo[:, :shared] = np.asarray(qs_t.lo)[:, :shared]
        hi[:, :shared] = np.asarray(qs_t.hi)[:, :shared]
        qs2 = QueryBatch(jnp.asarray(lo), jnp.asarray(hi))
        err, res, gt = common.median_err(kd2, qs2, c[:, :2], a, "sum")
        sr = float(np.median(np.asarray(skip_rate(kd2, qs2))))
        rows.append({"template_dims": t, "shared_attrs": shared,
                     "KD-PASS(2D synopsis)": f"{err*100:.3f}%",
                     "skip_rate": f"{sr*100:.1f}%"})
    return common.emit(rows, "fig9")


def run_streaming(max_leaves: int = 64, rate: float = 0.02,
                  drift_frac: float = 0.4, batch: int = 2048, seed: int = 0):
    """Data drift under continuous ingest (1-D): frozen synopsis vs
    delta-merged stream vs drift-triggered re-optimization."""
    c4, a = synthetic.nyc_taxi(scale=min(common.SCALE, 0.02), dims=1)
    c = np.asarray(c4).reshape(-1)
    a = np.asarray(a)
    rng = np.random.default_rng(seed)
    n_drift = int(drift_frac * len(a))
    assert n_drift >= batch, \
        (f"scale too small for the streaming scenario: {n_drift} drift rows "
         f"< one batch of {batch}; raise REPRO_BENCH_SCALE or lower batch")
    # drifted regime: the predicate support shifts past the observed range
    span = c.max() - c.min()
    c_new = rng.uniform(c.max(), c.max() + 0.5 * span, n_drift)
    a_new = rng.lognormal(np.log(np.abs(a).mean() + 1e-9) + 0.5, 1.0,
                          n_drift)
    K = max(int(rate * len(a)), 200)
    syn, _ = build_synopsis(c, a, k=max_leaves, sample_budget=K, kind="sum")

    ing = StreamingIngestor(syn, seed=seed + 1)
    for i in range(0, n_drift - batch + 1, batch):
        ing.ingest(c_new[i:i + batch], a_new[i:i + batch])
    streamed = (n_drift // batch) * batch
    c_all = np.concatenate([c, c_new[:streamed]])
    a_all = np.concatenate([a, a_new[:streamed]])
    qs = random_queries(c_all, min(common.NQ, 200), seed=29,
                        min_frac=0.05, max_frac=0.5)
    gt = ground_truth(c_all, a_all, qs, kind="sum")
    keep = np.abs(gt) > 1e-9
    # queries whose range reaches the drifted regime are where freshness
    # matters; the old-region queries are unaffected by construction
    drift_q = (np.asarray(qs.hi).reshape(-1) > c.max())[keep]

    def med(src):
        res = answer(src, qs, kind="sum")
        rel = relative_error(res, gt)[keep]
        return (float(np.median(rel)), float(np.median(rel[drift_q])))

    pol = DriftPolicy(staleness_threshold=0.2, oob_threshold=0.05)
    ing2, report = pol.maybe_reoptimize(ing, c_all, a_all, seed=seed + 2)
    assert report is not None, "drift policy should have triggered"
    rows = []
    for mode, src, stale in (
            ("frozen base (no ingest)", syn, "-"),
            ("delta-merged stream", ing, f"{ing.staleness():.2f}"),
            ("re-optimized (dp_monotone_jnp)", ing2,
             f"{ing2.staleness():.2f}")):
        e_all, e_drift = med(src)
        rows.append({"serving_mode": mode,
                     "median_rel_err": f"{e_all*100:.3f}%",
                     "median_rel_err_drift_queries": f"{e_drift*100:.3f}%",
                     "staleness": stale})
    return common.emit(rows, "fig9_streaming")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
    run_streaming()
