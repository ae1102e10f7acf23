"""Sharded synopsis layer: device-count invariance + single-device parity.

Multi-device cases run in subprocesses with forced host devices (jax locks
the device topology at first backend init); integer-valued aggregate
columns make f32 accumulation exact, so the invariance assertions are
bit-level, not tolerance-level. In-process cases exercise the parts that
are pure array plumbing (state splitting) or that must degenerate exactly
to the single-device streaming path on a 1-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_forced(script: str, n_devices: int) -> str:
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, cwd=REPO, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


# ---------------------------------------------------------------------------
# Device-count invariance: same data, same seeds, 1 vs 2 vs 4 devices
# ---------------------------------------------------------------------------

_INVARIANCE_SCRIPT = textwrap.dedent("""
    import os
    os.environ.setdefault("REPRO_KERNEL_BACKEND", "jnp")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.sharded import build_synopsis_sharded, reoptimize_sharded
    from repro.api import PassEngine
    from repro.core.types import QueryBatch

    def digest(*arrays):
        return b"".join(np.asarray(x).tobytes() for x in arrays).hex()

    rng = np.random.default_rng(0)
    n = 16384
    c = rng.normal(size=(n, {d})).astype(np.float32)
    a = rng.integers(0, 100, size=n).astype(np.float32)  # exact in f32

    ing, rep = build_synopsis_sharded(c, a, k=8, sample_budget=64, seed=3)
    assert rep["n_shards"] == len(jax.devices())
    syn = ing.as_synopsis()
    # bit-stable subset: exact aggregates, exact data boxes, lifted tree
    print("BUILD", digest(syn.leaf_agg, syn.leaf_lo, syn.leaf_hi,
                          syn.tree.agg, syn.tree.lo, syn.tree.hi,
                          syn.n_rows))

    # one post-commit streamed batch: every shard routes against the same
    # (global) boxes, so per-leaf aggregates stay bit-stable across D
    c2 = rng.normal(loc=0.25, size=(2048, {d})).astype(np.float32)
    a2 = rng.integers(0, 100, size=2048).astype(np.float32)
    ing.ingest(c2, a2)
    syn2 = ing.as_synopsis()
    print("STREAM", digest(syn2.leaf_agg, syn2.tree.agg))

    # serving a covering query touches only exact aggregates -> bit-stable
    eng = PassEngine(ing)
    q = QueryBatch(jnp.full((1, {d}), -50.0), jnp.full((1, {d}), 50.0))
    res = eng.answer(q)["sum"]
    print("SERVE", digest(res.estimate, res.lower, res.upper))

    # more streamed batches (per-shard boxes may drift apart) + a drift
    # re-optimization: the reservoir pool is RNG- and shard-dependent, so
    # only the *global* invariants are compared across device counts
    for i in range(3):
        lo = 0.5 * (i + 1)
        cb = rng.normal(loc=lo, size=(1024, {d})).astype(np.float32)
        ab = rng.integers(0, 100, size=1024).astype(np.float32)
        ing.ingest(cb, ab)
    syn3 = ing.as_synopsis()
    print("GLOBAL", digest(syn3.tree.agg[0], syn3.total_rows))
    if {d} == 1:
        call = np.concatenate([c[:, 0], c2[:, 0]])
        aall = np.concatenate([a, a2])
        ing4, _ = reoptimize_sharded(ing, call, aall, seed=11)
        s4 = ing4.as_synopsis()
        # exact root aggregates of the rebuilt synopsis are data-determined.
        # SUMSQ is excluded: its magnitude exceeds 2^24 here, so f32
        # accumulation rounds, and the re-opt *partitions* legitimately
        # differ per device count (reservoir RNG) — regrouped rounding is
        # not an invariance bug. SUM/COUNT/MIN/MAX stay exact.
        root = s4.tree.agg[0]
        print("REOPT", digest(root[jnp.array([0, 2, 3, 4])], s4.total_rows),
              int(s4.num_leaves))
""")


@pytest.mark.parametrize("d", [1, 2])
def test_device_count_invariance(d):
    """Build/stream/serve (and 1-D re-opt) bit-stable across 1/2/4 devices."""
    outs = {nd: _run_forced(_INVARIANCE_SCRIPT.format(d=d), nd)
            for nd in (1, 2, 4)}
    lines = {nd: dict(ln.split(" ", 1) for ln in out.splitlines()
                      if ln and ln.split(" ", 1)[0].isupper())
             for nd, out in outs.items()}
    tags = ("BUILD", "STREAM", "SERVE", "GLOBAL") + (("REOPT",) if d == 1
                                                     else ())
    for tag in tags:
        vals = {nd: lines[nd][tag] for nd in (1, 2, 4)}
        assert vals[1] == vals[2] == vals[4], \
            f"{tag} diverged across device counts (d={d}): {vals}"


# ---------------------------------------------------------------------------
# Multi-device engine integration: sharded source behind PassEngine
# ---------------------------------------------------------------------------

_ENGINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ.setdefault("REPRO_KERNEL_BACKEND", "jnp")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.api import PassEngine
    from repro.sharded import reoptimize_sharded, SHARD_AXIS
    from repro.streaming.policy import DriftPolicy
    from repro.core.types import QueryBatch

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(1)
    n = 16384
    c = rng.normal(size=n).astype(np.float32)
    a = rng.integers(0, 50, size=n).astype(np.float32)

    eng = PassEngine.from_sharded(c, a, k=16, sample_budget=128, seed=2)
    ing = eng.source

    # no dense gather of rows: every state field stays sharded over the
    # mesh axis — each device holds exactly 1/4 of the leading dim
    for f in ("sample_a", "sample_c", "delta_agg", "leaf_lo"):
        arr = getattr(ing.state, f)
        spec = arr.sharding.spec
        assert spec[0] == SHARD_AXIS, (f, spec)
        shards = arr.addressable_shards
        assert len(shards) == 4 and all(
            s.data.shape[0] == 1 for s in shards), (f, arr.shape)
    print("SHARDED_STATE_OK")

    q = QueryBatch(jnp.array([[-50.0]]), jnp.array([[50.0]]))
    prepared = eng.prepare(q)
    r1 = prepared(q)["sum"]
    assert float(r1.estimate[0]) == float(a.sum())
    print("SERVE_EXACT_OK")

    # streaming bumps the epoch; the prepared handle re-pins lazily
    c2 = rng.normal(loc=1.0, size=4096).astype(np.float32)
    a2 = rng.integers(0, 50, size=4096).astype(np.float32)
    e0 = eng.epoch
    ing.ingest(c2, a2)
    assert eng.epoch == e0 + 1
    r2 = prepared(q)["sum"]
    assert float(r2.estimate[0]) == float(a.sum() + a2.sum())
    assert eng.stats()["invalidations"] >= 1
    print("EPOCH_INVALIDATION_OK")

    # DriftPolicy duck-types the sharded ingestor; mesh-parallel rebuild
    pol = DriftPolicy(staleness_threshold=0.05, min_stream_rows=1)
    assert pol.should_reoptimize(ing)
    ing3, rep = reoptimize_sharded(
        ing, np.concatenate([c, c2]), np.concatenate([a, a2]), seed=5)
    assert rep["n_shards"] == 4
    eng.replace_source(ing3)
    r3 = eng.answer(q)["sum"]
    assert float(r3.estimate[0]) == float(a.sum() + a2.sum())
    print("REOPT_OK")
""")


def test_engine_from_sharded_multidevice():
    out = _run_forced(_ENGINE_SCRIPT, 4)
    for tag in ("SHARDED_STATE_OK", "SERVE_EXACT_OK",
                "EPOCH_INVALIDATION_OK", "REOPT_OK"):
        assert tag in out


# ---------------------------------------------------------------------------
# In-process: single-device mesh degenerates to the streaming path exactly
# ---------------------------------------------------------------------------

def test_sharded_matches_streaming_on_one_device():
    """On a 1-device mesh the sharded ingest must be bit-identical to
    StreamingIngestor: same routing, same threefry subkey consumption,
    same reservoir state, same merged synopsis."""
    import jax
    from repro.core import build_synopsis
    from repro.streaming import StreamingIngestor
    from repro.sharded import ShardedIngestor

    rng = np.random.default_rng(7)
    n = 8192
    c = rng.normal(size=n).astype(np.float32)
    a = rng.lognormal(0, 1, size=n).astype(np.float32)
    base, _ = build_synopsis(c, a, k=16, sample_budget=128)

    ref = StreamingIngestor(base, seed=9)
    sh = ShardedIngestor(base, seed=9)
    assert sh.n_shards == len(jax.devices()) == 1
    for i in range(3):
        cb = rng.normal(loc=0.2 * i, size=1024).astype(np.float32)
        ab = rng.lognormal(0, 1, size=1024).astype(np.float32)
        ref.ingest(cb, ab)
        sh.ingest(cb, ab)
    s_ref, s_sh = ref.as_synopsis(), sh.as_synopsis()
    for f in ("leaf_agg", "leaf_lo", "leaf_hi", "sample_a", "sample_c",
              "sample_valid", "k_per_leaf", "n_rows"):
        np.testing.assert_array_equal(np.asarray(getattr(s_ref, f)),
                                      np.asarray(getattr(s_sh, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(s_ref.tree.agg),
                                  np.asarray(s_sh.tree.agg))
    assert ref.n_oob == sh.n_oob
    assert float(s_ref.total_rows) == float(s_sh.total_rows)


def test_init_sharded_state_split_roundtrip():
    """The state split is the exact inverse of the merge-time tiled gather:
    reassembling shard slices along the slot axis reproduces the (padded)
    base reservoir, and per-shard counters sum to the base's."""
    from repro.core import build_synopsis
    from repro.sharded import init_sharded_state

    rng = np.random.default_rng(3)
    n = 4096
    c = rng.normal(size=n).astype(np.float32)
    a = rng.lognormal(0, 1, size=n).astype(np.float32)
    # sample cap 10 is NOT a multiple of D=4 -> exercises slot padding
    base, _ = build_synopsis(c, a, k=8, sample_budget=80)
    s = base.sample_a.shape[1]
    D = 4
    st = init_sharded_state(base, D)
    ss = st.sample_a.shape[-1]
    assert ss == -(-s // D)

    def regather(x):          # (D, k, ss, ...) -> (k, D*ss, ...)
        x = np.asarray(x)
        return np.moveaxis(x, 0, 1).reshape(
            x.shape[1], D * ss, *x.shape[3:])

    pad = D * ss - s
    sa_pad = np.pad(np.asarray(base.sample_a), ((0, 0), (0, pad)))
    sv_pad = np.pad(np.asarray(base.sample_valid), ((0, 0), (0, pad)))
    np.testing.assert_array_equal(regather(st.sample_a), sa_pad)
    np.testing.assert_array_equal(regather(st.sample_valid), sv_pad)
    np.testing.assert_array_equal(np.asarray(st.k_per_leaf).sum(0),
                                  np.asarray(base.k_per_leaf))
    seen_base = np.asarray(base.leaf_agg)[:, 2].astype(np.int64)
    np.testing.assert_array_equal(np.asarray(st.seen).sum(0), seen_base)
    # Vitter precondition on every shard: denominator >= filled slots
    assert np.all(np.asarray(st.seen) >= np.asarray(st.k_per_leaf))


def test_split_rows_deals_every_run_evenly():
    """Ordered batches (time-ordered appends) must reach every shard in
    equal shares: each shard keeps its own reservoir slice of a stratum,
    so any contiguous run of rows -- one stratum's rows in a batch sorted
    by the predicate column -- lands within one row of evenly on every
    shard, and the real rows are dealt exactly once (ragged tail
    masked)."""
    import jax.numpy as jnp
    from repro.sharded import split_rows

    b, D = 37, 4
    a = jnp.arange(b, dtype=jnp.float32)
    c = a[:, None]
    csh, ash, mask = split_rows(c, a, D)
    assert csh.shape == (D, 10, 1) and ash.shape == mask.shape == (D, 10)
    real = np.asarray(ash)[np.asarray(mask)]
    np.testing.assert_array_equal(np.sort(real), np.arange(b))
    np.testing.assert_array_equal(np.asarray(csh)[..., 0], np.asarray(ash))
    for lo, hi in ((0, 9), (5, 30), (12, 37)):
        run = np.asarray(mask) & (np.asarray(ash) >= lo) \
            & (np.asarray(ash) < hi)
        per_shard = run.sum(axis=1)
        assert per_shard.max() - per_shard.min() <= 1, (lo, hi, per_shard)


def test_build_sharded_exact_one_device():
    """Sharded build on the default (1-device) mesh: exact aggregates,
    exact boxes, full reservoirs — cross-checked against numpy."""
    from repro.sharded import build_synopsis_sharded

    rng = np.random.default_rng(5)
    n = 6000
    c = rng.normal(size=n).astype(np.float32)
    a = rng.lognormal(0, 1, size=n).astype(np.float32)
    ing, rep = build_synopsis_sharded(c, a, k=8, sample_budget=64, seed=1,
                                      batch_rows=2048)
    syn = ing.as_synopsis()
    assert float(syn.total_rows) == n
    np.testing.assert_allclose(float(syn.leaf_agg[:, 2].sum()), n)
    np.testing.assert_allclose(float(syn.leaf_agg[:, 0].sum()),
                               a.sum(), rtol=1e-6)
    assert float(syn.tree.agg[0, 3]) == a.min()
    assert float(syn.tree.agg[0, 4]) == a.max()
    # boxes are exact data bounding boxes per assigned leaf
    lo = np.asarray(syn.leaf_lo)[:, 0]
    hi = np.asarray(syn.leaf_hi)[:, 0]
    assert np.all(lo <= hi)
    assert lo.min() == c.min() and hi.max() == c.max()
    # every stratum's reservoir filled to capacity (n >> k * s_cap)
    assert np.all(np.asarray(syn.k_per_leaf) == rep["s_cap"])
    assert np.all(np.asarray(syn.sample_valid).sum(1)
                  == np.asarray(syn.k_per_leaf))
