"""AQP serving: batched approximate queries against a PASS synopsis through
the layered engine (plan/execute/assemble), with the distributed shard_map
paths when multiple devices exist.

This is the end-to-end *serve* driver (deliverable b): a synopsis is built
offline, then a stream of query batches is answered with latency stats,
hard bounds, and ESS/skip-rate accounting — the paper's full query
processing pipeline (§3.3). Each request asks for several aggregate kinds
at once (`--kinds sum,count,avg`); the engine answers all of them from one
shared classification + moment pass per batch.

    PYTHONPATH=src python examples/aqp_service.py [--batches 20]
    # multi-device serving demo:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/aqp_service.py --distributed
"""
import argparse
import time

import numpy as np
import jax

from repro.api import PassEngine, ServingConfig
from repro.core import build_synopsis, ground_truth, random_queries
from repro.core.estimators import ess, skip_rate
from repro.core import distributed as dist
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--kinds", type=str, default="sum,count,avg",
                    help="comma-separated aggregate kinds per request")
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()
    kinds = tuple(args.kinds.split(","))

    c, a = synthetic.nyc_taxi(scale=0.05)
    syn, rep = build_synopsis(c, a, k=128, sample_rate=0.01, kind="sum")
    print(f"[service] synopsis ready ({rep.seconds_total:.2f}s build, "
          f"k={rep.k}, {rep.total_samples} samples, "
          f"{syn.storage_floats()*4/2**20:.2f} MiB)")

    mesh = None
    if args.distributed and len(jax.devices()) > 1:
        n = len(jax.devices())
        mesh = jax.make_mesh((n,), ("data",))
        print(f"[service] distributed mode over {n} devices")
        if kinds != ("sum",):
            print("[service] note: the sharded serving path answers SUM "
                  f"only; ignoring --kinds {args.kinds}")
            kinds = ("sum",)

    # Configure once, serve many: the engine pins a prepared plan per batch
    # shape, so the steady-state loop below never re-does Python-side setup.
    eng = PassEngine(syn, serving=ServingConfig(kinds=kinds))
    prepared = eng.prepare((args.batch_size, syn.d))
    warm = random_queries(c, args.batch_size, seed=99)
    jax.block_until_ready(prepared(warm))       # jit compile
    jax.block_until_ready(prepared(warm))       # AOT-compile the entry

    lat, errs = [], {kd: [] for kd in kinds}
    for b in range(args.batches):
        qs = random_queries(c, args.batch_size, seed=100 + b)
        t0 = time.perf_counter()
        if mesh is not None:
            est, ci, lo, hi = dist.serve_queries_sharded(mesh, syn, qs,
                                                         kind="sum")
            est.block_until_ready()
            res = {"sum": np.asarray(est)}
        else:
            out = prepared(qs)
            jax.block_until_ready(out)
            res = {kd: np.asarray(out[kd].estimate) for kd in kinds}
        dt = time.perf_counter() - t0
        lat.append(dt)
        for kd, est in res.items():
            gt = ground_truth(c, a, qs, kind=kd)
            keep = np.abs(gt) > 1e-9
            errs[kd].append(np.median(np.abs(est - gt)[keep]
                                      / np.abs(gt)[keep]))
    qs = random_queries(c, args.batch_size, seed=0)
    e = np.asarray(ess(syn, qs))
    s = np.asarray(skip_rate(syn, qs))
    served = len(kinds) if mesh is None else 1
    print(f"[service] {args.batches} batches x {args.batch_size} queries "
          f"x {served} aggregate kind(s)/request")
    print(f"[service] median latency/batch {np.median(lat)*1000:.2f} ms "
          f"({np.median(lat)/args.batch_size*1e6:.1f} us/query, steady-state;"
          " one classification + one moment pass per batch)")
    for kd, ee in errs.items():
        if ee:
            print(f"[service] median rel err [{kd}] {np.median(ee)*100:.3f}%")
    print(f"[service] mean ESS {e.mean():.1f} samples/query, "
          f"mean skip rate {s.mean()*100:.1f}%")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
