"""Streaming ingest throughput: batched vectorized inserts vs the legacy
per-row ``UpdatableSynopsis.insert`` loop (ISSUE 2 acceptance: >= 20x on
100k rows on the same host), plus delta-merge serving latency.

Run: PYTHONPATH=src python -m benchmarks.bench_streaming_ingest
Tiny CI config: REPRO_BENCH_TINY=1 (also used by bench_smoke).
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax

from repro.api import PassEngine, ServingConfig
from repro.core import build_synopsis, random_queries
from repro.core.updates import UpdatableSynopsis
from repro.streaming import StreamingIngestor


def run(n_base: int = 200_000, k: int = 256, n_stream: int = 100_000,
        batch: int = 4096, loop_rows: int | None = None, q_serve: int = 256,
        seed: int = 0) -> dict:
    """Returns a flat metric dict (consumed by bench_smoke/BENCH_pr.json)."""
    rng = np.random.default_rng(seed)
    c = np.sort(rng.uniform(0, 100, n_base))
    a = rng.lognormal(0, 1, n_base)
    syn, _ = build_synopsis(c, a, k=k, sample_rate=0.01, method="eq")
    c_new = rng.uniform(0, 100, n_stream).astype(np.float32)
    a_new = rng.lognormal(0, 1, n_stream).astype(np.float32)

    # batched vectorized ingest (compile outside the timed region; best of
    # 3 full-stream passes to shed scheduler noise)
    StreamingIngestor(syn, seed=1).ingest(c_new[:batch], a_new[:batch])
    rows_batched = (n_stream // batch) * batch
    t_batched = float("inf")
    for _ in range(3):
        ing = StreamingIngestor(syn, seed=1)
        t0 = time.perf_counter()
        for i in range(0, n_stream - batch + 1, batch):
            ing.ingest(c_new[i:i + batch], a_new[i:i + batch])
        jax.block_until_ready(ing.state.delta_agg)
        t_batched = min(t_batched, time.perf_counter() - t0)

    # legacy per-row loop on the same host over the same rows (row count
    # overridable for the tiny CI config)
    if loop_rows is None:
        loop_rows = n_stream
    upd = UpdatableSynopsis(syn, seed=1)
    t0 = time.perf_counter()
    upd.insert_batch(c_new[:loop_rows], a_new[:loop_rows])
    t_loop = time.perf_counter() - t0

    us_batched = t_batched / rows_batched * 1e6
    us_loop = t_loop / loop_rows * 1e6
    speedup = us_loop / us_batched

    # delta-merge serving: answer a query batch straight from the ingestor
    qs = random_queries(c, q_serve, seed=2)
    eng = PassEngine(ing, serving=ServingConfig(kinds=("sum", "count",
                                                       "avg")))
    eng.answer(qs)
    eng.answer(qs)             # 2nd call AOT-compiles the prepared entry
    # Timed: one epoch bump (as every ingest() performs) so the prepared
    # plan re-pins the delta merge — the steady-state ingest-then-serve
    # path: device-only base+delta combine + the compiled answer.
    ing._merged = None
    ing._epoch += 1
    t0 = time.perf_counter()
    res = eng.answer(qs)
    jax.block_until_ready(res["sum"].estimate)
    t_serve = time.perf_counter() - t0

    metrics = {
        "stream_batched_us_per_row": us_batched,
        "stream_per_row_us_per_row": us_loop,
        "stream_speedup_x": speedup,
        "stream_rows": float(rows_batched),
        "delta_merge_serve_ms": t_serve * 1e3,
    }
    print(f"streaming ingest: n_base={n_base:,} k={k} "
          f"stream={rows_batched:,} rows batch={batch}")
    print(f"  batched vectorized   {us_batched:8.2f} us/row "
          f"({rows_batched / t_batched / 1e6:.2f} M rows/s)")
    print(f"  per-row legacy loop  {us_loop:8.2f} us/row "
          f"(measured on {loop_rows:,} rows)")
    print(f"  speedup: {speedup:.1f}x")
    print(f"  delta-merge serve (3 kinds, Q={q_serve}, incl. merge): "
          f"{t_serve * 1e3:.2f} ms")
    return metrics


def tiny_config() -> dict:
    return dict(n_base=20_000, k=64, n_stream=16_384, batch=2048,
                loop_rows=4000, q_serve=64)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(**(tiny_config() if os.environ.get("REPRO_BENCH_TINY") else {}))
