"""Workload shift (paper §5.4.1) and data shift (§4.5): a KD-PASS synopsis
built for a 2-D query template keeps helping when the workload drifts to
1-D/3-D/4-D templates that share attributes — and when the *data* drifts,
the streaming subsystem keeps serving fresh answers via batched ingest +
delta-merge, re-optimizing the partition on device once the drift policy
trips.

    PYTHONPATH=src python examples/workload_shift.py
"""
import numpy as np
import jax.numpy as jnp

from repro.api import PassEngine, ServingConfig
from repro.core import (build_synopsis, ground_truth, random_queries,
                        relative_error)
from repro.core.estimators import skip_rate
from repro.core.types import QueryBatch
from repro.data import synthetic
from repro.streaming import StreamingIngestor, DriftPolicy


def main():
    c, a = synthetic.nyc_taxi(scale=0.01, dims=4)
    print(f"dataset: {len(a):,} rows x 4 predicate columns")
    # Synopsis optimized for the 2-D template (pickup time x dropoff time).
    syn, rep = build_synopsis(c[:, :2], a, k=128, sample_rate=0.01,
                              kind="sum", method="kd")
    print(f"KD-PASS built for the 2-D template in {rep.seconds_total:.2f}s")

    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum",)))
    for t in (1, 2, 3, 4):
        qs_t = random_queries(c[:, :t], 200, seed=42 + t,
                              min_frac=0.1, max_frac=0.5)
        shared = min(t, 2)
        lo = np.full((200, 2), -np.inf, np.float32)
        hi = np.full((200, 2), np.inf, np.float32)
        lo[:, :shared] = np.asarray(qs_t.lo)[:, :shared]
        hi[:, :shared] = np.asarray(qs_t.hi)[:, :shared]
        qs2 = QueryBatch(jnp.asarray(lo), jnp.asarray(hi))
        res = eng.answer(qs2)["sum"]
        gt = ground_truth(c[:, :2], a, qs2, kind="sum")
        keep = np.abs(gt) > 1e-9
        err = np.median(relative_error(res, gt)[keep])
        sr = float(np.median(np.asarray(skip_rate(syn, qs2))))
        print(f"Q{t} template ({shared} shared attrs): median rel err "
              f"{err*100:6.3f}%   skip rate {sr*100:5.1f}%")

    streaming_demo()


def streaming_demo():
    """Continuous ingest + delta-merge serving + drift-triggered reopt."""
    print("\n-- data shift: continuous ingest (streaming subsystem) --")
    c4, a = synthetic.nyc_taxi(scale=0.01, dims=1)
    c = np.asarray(c4).reshape(-1)
    a = np.asarray(a)
    syn, _ = build_synopsis(c, a, k=64, sample_rate=0.02, kind="sum")
    rng = np.random.default_rng(7)
    n_new = len(a) // 2
    c_new = rng.uniform(c.max(), c.max() * 1.5, n_new)  # new territory
    a_new = rng.lognormal(1.5, 1.0, n_new)

    ing = StreamingIngestor(syn, seed=1)
    batch = 2048
    for i in range(0, n_new - batch + 1, batch):
        ing.ingest(c_new[i:i + batch], a_new[i:i + batch])
    streamed = (n_new // batch) * batch
    print(f"streamed {streamed:,} rows in {streamed // batch} vectorized "
          f"batches; staleness {ing.staleness():.2f}, "
          f"out-of-box {ing.oob_frac():.2f}")

    c_all = np.concatenate([c, c_new[:streamed]])
    a_all = np.concatenate([a, a_new[:streamed]])
    qs = random_queries(c_all, 200, seed=9, min_frac=0.05, max_frac=0.4)
    gt = ground_truth(c_all, a_all, qs, kind="sum")
    keep = np.abs(gt) > 1e-9
    drift_q = (np.asarray(qs.hi).reshape(-1) > c.max())[keep]

    def med(src, label):
        res = PassEngine(src).answer(qs)["sum"]
        rel = relative_error(res, gt)[keep]
        print(f"  {label:34s} median rel err {np.median(rel)*100:6.3f}% "
              f"(drift-touching queries {np.median(rel[drift_q])*100:6.3f}%)")

    med(syn, "frozen base (stale)")
    # One engine serves the live stream; replace_source() swaps in the
    # re-optimized ingestor and invalidates every prepared plan.
    live = PassEngine(ing)
    rel = relative_error(live.answer(qs)["sum"], gt)[keep]
    print(f"  {'delta-merged stream':34s} median rel err "
          f"{np.median(rel)*100:6.3f}% "
          f"(drift-touching queries {np.median(rel[drift_q])*100:6.3f}%)")
    pol = DriftPolicy(staleness_threshold=0.2)
    ing2, report = pol.maybe_reoptimize(ing, c_all, a_all)
    assert report is not None
    live.replace_source(ing2)
    rel = relative_error(live.answer(qs)["sum"], gt)[keep]
    print(f"  {'re-optimized (dp_monotone_jnp)':34s} median rel err "
          f"{np.median(rel)*100:6.3f}% "
          f"(drift-touching queries {np.median(rel[drift_q])*100:6.3f}%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
