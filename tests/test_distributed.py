"""Distributed PASS build/serve correctness on a multi-device host mesh.

Runs in a subprocess so the 8 fake XLA devices don't leak into the rest of
the test session (jax locks device count at first init).
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("REPRO_KERNEL_BACKEND", "jnp")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import build_synopsis, answer, random_queries
    from repro.core import distributed as dist
    from repro.core.types import QueryBatch

    assert len(jax.devices()) == 8
    # make_mesh's default Explicit axes: the distributed entry points must
    # accept the meshes users build this way
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Explicit,) * 2)

    rng = np.random.default_rng(0)
    n, k = 65536, 32
    c = np.sort(rng.uniform(0, 100, n))
    a = rng.lognormal(0, 1, n)
    syn, _ = build_synopsis(c, a, k=k, sample_rate=0.02, method="eq")

    # 1) distributed build == host aggregates
    assign = np.searchsorted(np.asarray(syn.leaf_hi)[:-1, 0], c,
                             side="left").astype(np.int32)
    # use the synopsis' own leaf assignment via box membership instead:
    lo = np.asarray(syn.leaf_lo)[:, 0]; hi = np.asarray(syn.leaf_hi)[:, 0]
    assign = np.clip(np.searchsorted(lo, c, side="right") - 1, 0, k - 1)
    agg = dist.build_leaf_aggregates(mesh, jnp.asarray(a, jnp.float32),
                                     jnp.asarray(assign), k,
                                     data_axes=("data", "model"))
    host = np.zeros((k, 5))
    for i in range(k):
        rows = a[assign == i]
        host[i] = ([rows.sum(), (rows**2).sum(), rows.size, rows.min(),
                    rows.max()] if rows.size else [0, 0, 0, 3e38, -3e38])
    np.testing.assert_allclose(np.asarray(agg)[:, :3], host[:, :3], rtol=2e-4)
    np.testing.assert_allclose(np.asarray(agg)[:, 3:], host[:, 3:], rtol=1e-5)
    print("BUILD_OK")

    # 2) shard_queries serving == replicated answers
    qs = random_queries(c, 64, seed=1)
    est, ci, lob, upb = dist.serve_queries_sharded(mesh, syn, qs, kind="sum")
    ref = answer(syn, qs, kind="sum")
    np.testing.assert_allclose(np.asarray(est), np.asarray(ref.estimate),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ci), np.asarray(ref.ci_half),
                               rtol=1e-4, atol=1e-3)
    print("SERVE_Q_OK")

    # 3) shard_samples serving == replicated answers (sum/count)
    est2, ci2 = dist.serve_samples_sharded(mesh, syn, qs, kind="sum")
    np.testing.assert_allclose(np.asarray(est2), np.asarray(ref.estimate),
                               rtol=1e-4, atol=1e-2)
    print("SERVE_S_OK")

    # 4) ragged Q (13 queries over 8 devices): padded internally, padding
    # rows sliced off — results match the replicated path exactly
    qs13 = random_queries(c, 13, seed=2)
    est3, ci3, lo3, hi3 = dist.serve_queries_sharded(mesh, syn, qs13,
                                                     kind="sum")
    ref13 = answer(syn, qs13, kind="sum")
    assert est3.shape == (13,) and ci3.shape == (13,)
    np.testing.assert_allclose(np.asarray(est3), np.asarray(ref13.estimate),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ci3), np.asarray(ref13.ci_half),
                               rtol=1e-4, atol=1e-3)
    print("SERVE_RAGGED_OK")
""")


def test_distributed_pass_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, cwd=REPO,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    for tag in ("BUILD_OK", "SERVE_Q_OK", "SERVE_S_OK", "SERVE_RAGGED_OK"):
        assert tag in r.stdout
