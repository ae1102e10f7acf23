"""Ahead-of-time compiles of every Pallas kernel for a described v5e chip.

Interpret mode runs the kernel bodies but not the TPU compiler, which is
what refuses a block that breaks the (8, 128) tiling or a kernel that
needs more scoped VMEM than the chip has. These tests hand the ``pallas``
backend's adapters abstract shapes placed on one chip of a described
``v5e:2x2`` topology and compile them, at the widths ``chip_smoke.py``
serves: Q=2048 queries, k=1024 leaves, S=1,048,576 samples, ingest batches
of 262,144 rows, and the bootstrap at R=200 replicates over 256 queries.

The topology is described inside a module fixture, never at import, so
only the test worker that runs this file loads the TPU compiler.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import backends

Q, K, S_PER_LEAF, BATCH = 2048, 1024, 1024, 262_144
BOOT_R, BOOT_Q = 200, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """compile(fn, *shapes) -> HLO text of ``fn`` compiled for one v5e.

    The adapters pick interpret mode from the process's own backend (the
    CPU here); the test steers them to the compiled kernels. A compile for
    a described chip cannot be read back from the persistent cache, so the
    cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(backends, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


PALLAS = backends.PallasBackend()
F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


def _samples(d):
    return [((K, S_PER_LEAF, d), F32), ((K, S_PER_LEAF), F32),
            ((K, S_PER_LEAF), BOOL)]


@pytest.mark.parametrize("d", [1, 3])
def test_query_eval_compiles(tpu_compile, d):
    hlo = tpu_compile(PALLAS.query_eval, ((K, d), F32), ((K, d), F32),
                      ((K, 5), F32), ((Q, d), F32), ((Q, d), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("d", [1, 3])
def test_stratified_moments_compiles(tpu_compile, d):
    hlo = tpu_compile(PALLAS.stratified_moments, *_samples(d),
                      ((Q, d), F32), ((Q, d), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("d", [1, 3])
def test_stratified_weighted_moments_compiles(tpu_compile, d):
    hlo = tpu_compile(PALLAS.weighted_moments, *_samples(d),
                      ((K, S_PER_LEAF), F32), ((Q, d), F32), ((Q, d), F32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("d", [1, 3])
def test_bootstrap_moments_compiles(tpu_compile, d):
    hlo = tpu_compile(PALLAS.bootstrap_moments, *_samples(d),
                      ((BOOT_R, K, S_PER_LEAF), F32), ((BOOT_Q, d), F32),
                      ((BOOT_Q, d), F32))
    assert "tpu_custom_call" in hlo


def test_route_multid_compiles(tpu_compile):
    hlo = tpu_compile(PALLAS.route_multid, ((K, 3), F32), ((K, 3), F32),
                      ((BATCH, 3), F32))
    assert "tpu_custom_call" in hlo


def test_segment_reduce_compiles(tpu_compile):
    hlo = tpu_compile(lambda v, ids: PALLAS.segment_reduce(v, ids, K, bn=None),
                      ((BATCH,), F32), ((BATCH,), I32))
    assert "tpu_custom_call" in hlo


def test_weighted_segment_reduce_compiles(tpu_compile):
    hlo = tpu_compile(
        lambda v, w, ids: PALLAS.weighted_segment_reduce(v, w, ids, K,
                                                         bn=None),
        ((BATCH,), F32), ((BATCH,), F32), ((BATCH,), I32))
    assert "tpu_custom_call" in hlo
