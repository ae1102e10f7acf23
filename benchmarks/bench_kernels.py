"""Microbenchmarks of the three PASS kernel ops across registered backends.

Each op is dispatched through the backend registry with per-call selection
(`backend=` kwarg): the `jnp` broadcast formulation and the `ref`
kernel-convention oracle run on CPU; `pallas` is skipped off-TPU by default
(interpret mode executes the kernel body per grid step in Python — the
bodies are validated under interpret=True in tests/test_kernels.py).
Pass --pallas to include it anyway.
"""
from __future__ import annotations

import sys

import numpy as np
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.registry import available_backends
from . import common


def run(backends=("jnp", "ref")):
    rng = np.random.default_rng(0)
    rows = []
    N, k = 1 << 20, 256
    v = jnp.asarray(rng.normal(0, 1, N), jnp.float32)
    ids = jnp.asarray(rng.integers(0, k, N), jnp.int32)
    S, Q, d = 1 << 16, 512, 2
    c = jnp.asarray(rng.uniform(-1, 1, (S, d)), jnp.float32)
    av = jnp.asarray(rng.normal(0, 1, S), jnp.float32)
    leaf = jnp.asarray(rng.integers(0, k, S), jnp.int32)
    qlo = jnp.asarray(rng.uniform(-1, 0, (Q, d)), jnp.float32)
    qhi = qlo + 0.5
    lo = jnp.asarray(rng.uniform(-1, 0.5, (k, d)), jnp.float32)
    hi = lo + 0.2
    agg = jnp.asarray(rng.normal(0, 1, (k, 5)), jnp.float32)

    for be in backends:
        assert be in available_backends(), (be, available_backends())
        _, t = common.timed(lambda: ops.segment_reduce_op(
            v, ids, k, backend=be).block_until_ready())
        rows.append({"kernel": "segment_reduce", "backend": be,
                     "shape": f"N={N},k={k}",
                     "us_per_call": f"{t*1e6:.0f}",
                     "rows_per_s": f"{N/t/1e6:.0f}M"})
        _, t = common.timed(lambda: ops.stratified_moments_op(
            c, av, leaf, qlo, qhi, k, backend=be).block_until_ready())
        rows.append({"kernel": "stratified_moments", "backend": be,
                     "shape": f"S={S},Q={Q},k={k}",
                     "us_per_call": f"{t*1e6:.0f}",
                     "qsamples_per_s": f"{Q*S/t/1e9:.1f}G"})
        _, t = common.timed(lambda: ops.query_eval_op(
            lo, hi, agg, qlo, qhi, backend=be)[1].block_until_ready())
        rows.append({"kernel": "query_eval", "backend": be,
                     "shape": f"Q={Q},k={k}",
                     "us_per_call": f"{t*1e6:.0f}"})
        # fused bootstrap replicate moments (synopsis-shaped samples)
        ks, ss, R = 64, 64, 16
        scs = jnp.asarray(rng.uniform(-1, 1, (ks, ss, d)), jnp.float32)
        sas = jnp.asarray(rng.normal(0, 1, (ks, ss)), jnp.float32)
        svs = jnp.asarray(rng.random((ks, ss)) < 0.9)
        W = jnp.asarray(rng.poisson(1.0, (R, ks, ss)), jnp.float32)
        _, t = common.timed(lambda: ops.bootstrap_moments_op(
            scs, sas, svs, W, qlo, qhi, backend=be).block_until_ready())
        rows.append({"kernel": "bootstrap_moments", "backend": be,
                     "shape": f"R={R},Q={Q},k={ks},s={ss}",
                     "us_per_call": f"{t*1e6:.0f}",
                     "repqsamples_per_s": f"{R*Q*ks*ss/t/1e9:.1f}G"})
        # multi-D batch routing (streaming ingest hot path)
        B = 1 << 14
        rlo = jnp.asarray(rng.uniform(-1, 1, (k, d)), jnp.float32)
        rhi = rlo + 0.2
        rows_c = jnp.asarray(rng.uniform(-1.2, 1.2, (B, d)), jnp.float32)
        _, t = common.timed(lambda: ops.route_multid_op(
            rlo, rhi, rows_c, backend=be)[0].block_until_ready())
        rows.append({"kernel": "route_multid", "backend": be,
                     "shape": f"B={B},k={k}",
                     "us_per_call": f"{t*1e6:.0f}",
                     "rows_per_s": f"{B/t/1e6:.1f}M"})
    return common.emit(rows, "kernels")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    bes = ("jnp", "ref", "pallas") if "--pallas" in sys.argv else ("jnp", "ref")
    run(bes)
