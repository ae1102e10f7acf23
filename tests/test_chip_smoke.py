"""`chip_smoke.py` off the chip: it refuses to run, and its phases run.

The smoke itself needs a TPU. Here its phases run at a tiny size on the
CPU, so a change that breaks the path it drives fails a CPU test instead
of a chip run. At this size the statistical checks (median error,
coverage) are not meaningful and are left out; every other check of the
smoke -- covered answers equal to the f64 reference at f32 rounding, the
AOT compile, coalescer bit-identity, device-count bit-stability -- must
hold.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATISTICAL = ("median relative error", "interval coverage",
               "answers agree across D")

TINY = dict(scale=0.01, k=64, samples=4096, queries=100, covered=16,
            boot_queries=32, n_boot=16, batch=4096, batches=3, tenants=8)


def _smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def _structural_failures(chk):
    return [f for f in chk.failed if not any(s in f for s in STATISTICAL)]


@pytest.mark.parametrize("env,reason", [
    ({"JAX_PLATFORMS": "cpu"}, "no TPU"),
    ({"JAX_PLATFORMS": "cpu", "REPRO_KERNEL_BACKEND": "jnp"},
     "REPRO_KERNEL_BACKEND is set")])
def test_refuses_without_tpu_or_with_backend_override(env, reason):
    base = {k: v for k, v in os.environ.items()
            if k != "REPRO_KERNEL_BACKEND"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=dict(base, **env), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert reason in r.stderr


def test_one_chip_phases_run_at_tiny_size():
    cs = _smoke()
    chk = cs.Checks()
    cs.run_one_chip(chk, cs.Sizes(**TINY), seed=0)
    assert _structural_failures(chk) == []


_SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["REPRO_KERNEL_BACKEND"] = {backend!r}
    sys.path.insert(0, {repo!r})
    if {backend!r} == "pallas":
        # On a TPU, Mosaic refuses a Pallas kernel lowered into a program
        # that spans several devices outside shard_map. Interpret mode on
        # the CPU lowers it anyway, so refuse the same programs here.
        from jax._src import sharding_impls
        from jax._src.interpreters import mlir
        from jax._src.pallas import pallas_call as pc
        entry = mlir._lowerings[pc.pallas_call_p]

        def guarded(ctx, *args, **kw):
            ax = ctx.module_context.axis_context
            if (isinstance(ax, sharding_impls.ShardingContext)
                    and ax.num_devices != 1):
                raise NotImplementedError(
                    "Pallas kernel in a program over "
                    f"{{ax.num_devices}} devices outside shard_map")
            return entry.rule(ctx, *args, **kw)

        mlir._lowerings[pc.pallas_call_p] = mlir.LoweringRuleEntry(
            guarded, entry.inline)
    import chip_smoke as cs
    chk = cs.Checks()
    cs.run_sharded(chk, cs.Sizes(**{tiny!r}), seed=0, n_chips=4)
    print("FAILED", [f for f in chk.failed
                     if not any(s in f for s in {stat!r})])
""")


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_sharded_phase_runs_on_four_host_devices(backend):
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = _SHARDED.format(repo=REPO, tiny=TINY, stat=STATISTICAL,
                             backend=backend)
    r = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FAILED []" in r.stdout, r.stdout[-3000:]
