"""Distributed synopsis benchmarks: psum merge + sharded-ingest scale curve.

Two cases feed ``BENCH_pr.json``:

* **psum merge** — the multi-device aggregate path
  (``core.distributed.build_leaf_aggregates``): rows shard over a mesh,
  each device reduces its shard with segment_reduce, one O(k) ``psum``/
  ``pmax`` merges the mergeable summaries. Tracks shard_map + collective
  overhead even on a 1-device host.
* **sharded-ingest scale curve** — the full data-parallel streaming path
  (``repro.sharded.ShardedIngestor``) over ``data_mesh(n)`` for n = 1/2/4
  in one process (on the CPU, forced host devices via
  ``--xla_force_host_platform_device_count``), reporting rows/sec per
  device count and the gated ``sharded_ingest_scaleup_x`` =
  rate(D_max)/rate(1). On a multi-core host this shows real weak scaling
  (target >= 1.5x at 4 devices); on the 1-core CI runner forced host
  devices time-slice one core, so the envelope baseline gates against
  collapse (serialization pathologies, per-shard recompiles), not against
  the multi-core target.

Run: PYTHONPATH=src python -m benchmarks.bench_distributed
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import distributed as dist
from repro.kernels import ops as kops


def _bench(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(n_rows: int = 1_000_000, k: int = 256, seed: int = 0) -> dict:
    """Returns a flat metric dict (consumed by bench_smoke/BENCH_pr.json)."""
    devices = jax.devices()
    n_dev = len(devices)
    n = (n_rows // n_dev) * n_dev                 # rows must tile the mesh
    rng = np.random.default_rng(seed)
    values = jnp.asarray(rng.lognormal(0, 1, n), jnp.float32)
    assign = jnp.asarray(rng.integers(0, k, n), jnp.int32)

    mesh = jax.make_mesh((n_dev,), ("data",))
    merged_fn = jax.jit(lambda v, a: dist.build_leaf_aggregates(
        mesh, v, a, k))
    local_fn = jax.jit(lambda v, a: kops.segment_reduce_op(v, a, k))

    t_merged = _bench(merged_fn, values, assign)
    t_local = _bench(local_fn, values, assign)

    # correctness cross-check: the psum merge must reproduce the
    # single-device reduce (SUM/SUMSQ/COUNT add, MIN/MAX combine)
    got = np.asarray(merged_fn(values, assign))
    want = np.asarray(local_fn(values, assign))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-2)

    metrics = {
        "dist_psum_merge_ms": t_merged * 1e3,
        "dist_local_reduce_ms": t_local * 1e3,
        "dist_devices_rows": float(n_dev),
    }
    print(f"distributed psum merge: n={n:,} rows, k={k}, "
          f"{n_dev} device(s)")
    print(f"  sharded build_leaf_aggregates {t_merged * 1e3:8.2f} ms "
          f"({n / t_merged / 1e6:.1f} M rows/s)")
    print(f"  single-device segment_reduce  {t_local * 1e3:8.2f} ms")
    return metrics


def tiny_config() -> dict:
    """CI-sized run (bench_smoke)."""
    return dict(n_rows=200_000, k=64)


# --------------------------------------------------------------------------
# Sharded-ingest weak-scaling curve (one process, one mesh per device count)
# --------------------------------------------------------------------------

def _ingest_rate(mesh, c, a, k: int, batch: int, seed: int) -> float:
    """Build a sharded synopsis over ``mesh``, then time steady-state
    streaming ingest. Returns rows/s."""
    from repro.sharded import build_synopsis_sharded
    ing, _ = build_synopsis_sharded(c, a, k=k, sample_budget=8 * k,
                                    seed=seed, batch_rows=batch, mesh=mesh)
    rng = np.random.default_rng(seed + 1)
    cb = rng.normal(size=batch).astype(np.float32)
    ab = rng.lognormal(0, 1, batch).astype(np.float32)
    ing.ingest(cb, ab)                              # warmup / compile
    jax.block_until_ready(ing.state.delta_agg)
    reps = 6
    t0 = time.perf_counter()
    for _ in range(reps):
        ing.ingest(cb, ab)
    jax.block_until_ready(ing.state.delta_agg)
    return reps * batch / (time.perf_counter() - t0)


def run_scale(n_rows: int = 400_000, k: int = 64, batch: int = 65_536,
              device_counts: tuple = (1, 2, 4), seed: int = 0) -> dict:
    """The scale curve over ``data_mesh(n)`` for each of ``device_counts``,
    all in this one process (a child process could not reach a chip this
    process already holds). Forced host devices
    (``--xla_force_host_platform_device_count``) give the CPU several; a
    count the process cannot see is an error, not a shorter curve."""
    from repro.sharded import data_mesh
    counts = sorted(set(device_counts))
    if counts[0] != 1 or counts[-1] > len(jax.devices()):
        raise ValueError(
            f"scale curve over device counts {counts} needs D=1 and "
            f"{counts[-1]} visible devices, found {len(jax.devices())} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N on "
            "the CPU)")
    rng = np.random.default_rng(seed)
    c = rng.normal(size=n_rows).astype(np.float32)
    a = rng.lognormal(0, 1, n_rows).astype(np.float32)
    rates = {nd: _ingest_rate(data_mesh(nd), c, a, k, batch, seed)
             for nd in counts}
    d_max = max(counts)
    metrics = {"sharded_ingest_scaleup_x": rates[d_max] / rates[1]}
    for nd in counts:
        metrics[f"sharded_ingest_mrows_per_s_d{nd}"] = rates[nd] / 1e6
    print(f"sharded ingest scale curve (n={n_rows:,} build rows, k={k}, "
          f"batch={batch:,}):")
    for nd in counts:
        print(f"  D={nd}: {rates[nd] / 1e6:7.3f} M rows/s "
              f"({rates[nd] / rates[1]:.2f}x vs D=1)")
    print(f"  scale-up at D={d_max}: {metrics['sharded_ingest_scaleup_x']:.2f}x")
    return metrics


def tiny_scale_config() -> dict:
    """CI-sized scale curve (bench_smoke)."""
    return dict(n_rows=60_000, k=32, batch=16_384)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("REPRO_BENCH_TINY"):
        run(**tiny_config())
        run_scale(**tiny_scale_config())
    else:
        run()
        run_scale()
