"""Streaming subsystem: batched inserts bit-match the per-row reference
loop (jnp + pallas backends), reservoir inclusion probabilities (hypothesis
property), delta-merge vs from-scratch rebuild on the exact path, and the
drift-triggered re-optimization loop."""
import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from conftest import given, settings, st

from repro.core import build_synopsis, answer, ground_truth, random_queries
from repro.core import partition_tree as pt
from repro.core.types import QueryBatch, AGG_SUM, AGG_COUNT
from repro.core.updates import UpdatableSynopsis
from repro.streaming import (StreamingIngestor, ingest_batch_reference,
                             DriftPolicy)
from repro.streaming.ingest import (StreamState, init_state, _ingest_step,
                                    _route_1d, _route_dist)
from repro.kernels.segment_reduce import auto_block_n

STATE_FIELDS = ("leaf_lo", "leaf_hi", "delta_agg", "sample_c", "sample_a",
                "sample_valid", "k_per_leaf", "seen", "oob")


def _base(n=20000, k=16, sample_budget=64, seed=0, int_vals=True,
          val_hi=64):
    rng = np.random.default_rng(seed)
    c = np.sort(rng.uniform(0, 100, n))
    if int_vals:                       # integer values: f32 accumulation is
        a = rng.integers(1, val_hi, n).astype(np.float64)  # exact -> bit-match
    else:
        a = rng.lognormal(0, 1, n)
    syn, _ = build_synopsis(c, a, k=k, sample_budget=sample_budget,
                            method="eq")
    return syn, c, a


def _assert_states_equal(got: StreamState, want: StreamState, exact=True):
    for f in STATE_FIELDS:
        ga, wa = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        if exact or f in ("sample_valid", "k_per_leaf", "seen", "oob"):
            np.testing.assert_array_equal(ga, wa, err_msg=f)
        else:
            np.testing.assert_allclose(ga, wa, rtol=1e-5, atol=1e-4,
                                       err_msg=f)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_batched_ingest_bitmatches_per_row_reference(backend):
    """Two sequential batches (incl. out-of-range rows that expand boxes
    between batches, and full reservoirs that exercise replacement) produce
    bit-identical state to the sequential per-row oracle."""
    n, k, B = (6000, 8, 192) if backend == "pallas" else (20000, 16, 512)
    syn, _, _ = _base(n=n, k=k, sample_budget=4 * k)
    rng = np.random.default_rng(7)
    ing = StreamingIngestor(syn, seed=1, backend=backend)
    ref = init_state(syn)
    for _ in range(2):
        c_new = rng.uniform(-10, 110, B).astype(np.float32)
        a_new = rng.integers(1, 64, B).astype(np.float32)
        u = rng.random(B, dtype=np.float32)
        ing.ingest(c_new, a_new, u=u)
        ref = ingest_batch_reference(ref, c_new, a_new, u)
    _assert_states_equal(ing.state, ref, exact=True)
    assert ing.n_oob == int(np.asarray(ref.oob)) > 0
    assert ing.n_stream == 2 * B


def test_batched_ingest_float_values_match_to_tolerance():
    """With arbitrary float values the scatter accumulation may reorder
    f32 additions; everything else stays exact."""
    syn, _, _ = _base(int_vals=False)
    rng = np.random.default_rng(11)
    B = 768
    c_new = rng.uniform(0, 100, B).astype(np.float32)
    a_new = rng.lognormal(0, 1, B).astype(np.float32)
    u = rng.random(B, dtype=np.float32)
    ing = StreamingIngestor(syn, seed=1).ingest(c_new, a_new, u=u)
    ref = ingest_batch_reference(init_state(syn), c_new, a_new, u)
    _assert_states_equal(ing.state, ref, exact=False)
    # routing-determined fields stay bit-exact even for float values
    for f in ("leaf_lo", "leaf_hi", "sample_c", "sample_a"):
        np.testing.assert_array_equal(np.asarray(getattr(ing.state, f)),
                                      np.asarray(getattr(ref, f)), err_msg=f)


@settings(max_examples=12, deadline=None)
@given(cap=st.integers(min_value=1, max_value=6),
       n_ins=st.sampled_from([8, 16, 32]))
def test_reservoir_inclusion_probability(cap, n_ins):
    """Vitter property: after streaming n rows into a full reservoir of
    capacity cap that has already seen cap rows, every streamed row ends up
    retained with probability cap / (cap + n). Verified by frequency over
    T independent replica strata driven through one vectorized step."""
    T = 384
    d = 1
    # T disjoint unit strata, reservoirs pre-filled with marker value -1
    lo = np.arange(T, dtype=np.float32)[:, None]
    hi = lo + np.float32(0.9)
    state = StreamState(
        leaf_lo=jnp.asarray(lo), leaf_hi=jnp.asarray(hi),
        delta_agg=jnp.zeros((T, 5), jnp.float32)
        .at[:, 3].set(3e38).at[:, 4].set(-3e38),
        sample_c=jnp.zeros((T, cap, d), jnp.float32),
        sample_a=jnp.full((T, cap), -1.0, jnp.float32),
        sample_valid=jnp.ones((T, cap), bool),
        k_per_leaf=jnp.full(T, cap, jnp.int32),
        seen=jnp.full(T, cap, jnp.int32),
        oob=jnp.zeros((), jnp.int32))
    # row r of every replica carries value r; replicas interleaved so each
    # stratum sees its rows in order r = 0..n-1
    c = np.repeat(np.arange(T, dtype=np.float32), n_ins)[:, None] + 0.5
    a = np.tile(np.arange(n_ins, dtype=np.float32), T)
    order = np.argsort(np.tile(np.arange(n_ins), T), kind="stable")
    c, a = c[order], a[order]
    rng = np.random.default_rng(100 * cap + n_ins)       # per-example seed
    u = rng.random(T * n_ins).astype(np.float32)
    new_state = _ingest_step(state, jnp.asarray(c), jnp.asarray(a),
                             jnp.asarray(u), backend_name="jnp")
    vals = np.asarray(new_state.sample_a)                # (T, cap)
    p = cap / (cap + n_ins)
    sd = np.sqrt(T * p * (1 - p))
    for r in range(n_ins):
        freq = int((vals == r).sum())
        assert abs(freq - T * p) <= 6.0 * sd + 1e-9, (r, freq, T * p, sd)
    np.testing.assert_array_equal(np.asarray(new_state.seen), cap + n_ins)
    np.testing.assert_array_equal(np.asarray(new_state.k_per_leaf), cap)


def test_delta_merge_bitmatches_full_rebuild_on_exact_path():
    """Streamed coordinates drawn from the existing support route exactly
    like a batch rebuild; with integer values the merged leaf/tree
    aggregates and the covered-leaf (exact-path) answers are bit-identical
    to a from-scratch aggregation over base + stream."""
    # values < 8 keep every SUM/SUMSQ (incl. the tree root) below 2^24, so
    # f32 accumulation is exact in any order and bit-match is well-defined
    syn, c0, a0 = _base(n=20000, k=16, sample_budget=320, val_hi=8)
    rng = np.random.default_rng(3)
    n_s = 4000
    c_new = rng.choice(c0, n_s)                 # inside original boxes
    a_new = rng.integers(1, 8, n_s).astype(np.float64)
    ing = StreamingIngestor(syn, seed=5)
    for i in range(0, n_s, 1000):
        ing.ingest(c_new[i:i + 1000], a_new[i:i + 1000])
    merged = ing.as_synopsis()

    # from-scratch rebuild with the same row-to-leaf assignment: base rows
    # use the eq build's rank cuts; streamed rows replay the batch routing
    # (f32 boxes, batch-entry snapshots) in plain numpy
    from repro.core import dp as dp_mod
    n0, k = len(c0), syn.num_leaves
    order = np.argsort(c0, kind="stable")
    ranks = np.empty(n0, dtype=np.int64)
    ranks[order] = np.arange(n0)
    cuts = dp_mod.equal_depth_boundaries(n0, k)
    assign0 = np.searchsorted(cuts[1:-1], ranks, side="right")
    lo = np.asarray(syn.leaf_lo, np.float32).copy()
    hi = np.asarray(syn.leaf_hi, np.float32).copy()
    assign_new = np.empty(n_s, dtype=np.int64)
    for i in range(0, n_s, 1000):
        cb = c_new[i:i + 1000].astype(np.float32)
        dist = (np.maximum(lo[:, 0][None] - cb[:, None], 0)
                + np.maximum(cb[:, None] - hi[:, 0][None], 0))
        leaf = dist.argmin(axis=1)
        assign_new[i:i + 1000] = leaf
        np.minimum.at(lo[:, 0], leaf, cb)
        np.maximum.at(hi[:, 0], leaf, cb)
    c_all = np.concatenate([c0, c_new])
    a_all = np.concatenate([a0, a_new])
    assign = np.concatenate([assign0, assign_new])
    agg, blo, bhi = pt.leaf_stats(c_all, a_all, assign, k)
    tree = pt.build_tree_from_leaves(agg, blo, bhi)

    np.testing.assert_array_equal(np.asarray(merged.leaf_agg),
                                  agg.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(merged.tree.agg),
                                  tree.agg.astype(np.float32))
    assert merged.total_rows == len(a_all)

    # exact-path answers: queries covering whole runs of leaves are served
    # purely from the covered-aggregate accumulation
    boxes_lo = np.asarray(merged.leaf_lo)[:, 0]
    boxes_hi = np.asarray(merged.leaf_hi)[:, 0]
    q_lo, q_hi = [], []
    for i in range(0, syn.num_leaves - 3, 4):
        q_lo.append([boxes_lo[i]])
        q_hi.append([boxes_hi[i + 3]])
    qs = QueryBatch(jnp.asarray(q_lo, jnp.float32),
                    jnp.asarray(q_hi, jnp.float32))
    res = answer(merged, qs, kind="sum")
    want = np.array([a_all[(assign >= i) & (assign <= i + 3)].sum()
                     for i in range(0, syn.num_leaves - 3, 4)], np.float32)
    np.testing.assert_allclose(np.asarray(res.estimate), want, rtol=1e-6)
    # exact path: deterministic bounds collapse onto the estimate
    np.testing.assert_allclose(np.asarray(res.lower), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(res.upper), want, rtol=1e-6)


def test_engine_answers_ingestor_directly():
    """`answer()`/`artifacts()` consume the ingestor (delta-merge source)
    exactly as they would the merged synopsis."""
    syn, c0, a0 = _base()
    rng = np.random.default_rng(9)
    ing = StreamingIngestor(syn, seed=2).ingest(
        rng.uniform(0, 100, 512), rng.integers(1, 64, 512).astype(np.float64))
    qs = random_queries(c0, 50, seed=4, min_frac=0.1, max_frac=0.5)
    r_direct = answer(ing, qs, kinds=("sum", "count", "avg"))
    r_merged = answer(ing.as_synopsis(), qs, kinds=("sum", "count", "avg"))
    for k in r_direct:
        np.testing.assert_array_equal(np.asarray(r_direct[k].estimate),
                                      np.asarray(r_merged[k].estimate))


def test_drift_policy_triggers_and_reoptimize_adapts():
    syn, c0, a0 = _base(n=20000, k=16, sample_budget=640, int_vals=False)
    rng = np.random.default_rng(13)
    n_s = 8000
    c_drift = rng.uniform(100, 200, n_s)        # entirely new territory
    a_drift = rng.lognormal(1.0, 1.0, n_s)
    ing = StreamingIngestor(syn, seed=3)
    pol = DriftPolicy(staleness_threshold=0.2, min_stream_rows=1024)
    assert not pol.should_reoptimize(ing)
    for i in range(0, n_s, 2000):
        ing.ingest(c_drift[i:i + 2000], a_drift[i:i + 2000])
    assert ing.staleness() == pytest.approx(n_s / (20000 + n_s))
    # only the first batch routes against pre-drift boxes (batch-entry
    # snapshots), so a quarter of the stream registers as out-of-box
    assert ing.oob_frac() > 0.2
    assert pol.should_reoptimize(ing)

    c_all = np.concatenate([c0, c_drift])
    a_all = np.concatenate([a0, a_drift])
    ing2, report = pol.maybe_reoptimize(ing, c_all, a_all)
    assert report is not None
    assert ing2.n_stream == 0 and ing2.staleness() == 0.0
    # the re-optimized partition covers the drifted range
    assert float(np.asarray(ing2.base.leaf_hi).max()) >= 199.0
    assert float(np.asarray(ing2.base.tree.agg)[0, AGG_COUNT]) == len(a_all)
    qs = random_queries(c_all, 100, seed=6, min_frac=0.1, max_frac=0.5)
    gt = ground_truth(c_all, a_all, qs, kind="sum")
    res = answer(ing2, qs, kind="sum")
    keep = np.abs(gt) > 1e-9
    rel = np.abs(np.asarray(res.estimate)[keep] - gt[keep]) / np.abs(gt[keep])
    assert np.median(rel) < 0.1


def test_reoptimize_cuts_plans_on_a_bounded_pool():
    """The re-optimization DP plans on at most ``opt_samples`` of the
    reservoir pool: a systematic sample of the coordinate-sorted pool. A
    pool within the bound is used whole."""
    from repro.core import dp as dp_mod
    from repro.streaming.policy import (OPT_SAMPLES, pool_thresholds,
                                        reoptimize_cuts)
    syn, _, _ = _base(n=20000, k=16, sample_budget=640, int_vals=False)
    ing = StreamingIngestor(syn, seed=3)
    st = ing.state
    valid = np.asarray(st.sample_valid).reshape(-1)
    cs = np.asarray(st.sample_c).reshape(-1)[valid]
    as_ = np.asarray(st.sample_a).reshape(-1)[valid]
    order = np.argsort(cs, kind="stable")
    m = order.size
    assert 100 < m <= OPT_SAMPLES

    def dp_thresholds(idx):
        cuts, _ = dp_mod.dp_monotone_jnp(jnp.asarray(as_[idx]), 16)
        return np.asarray(dp_mod.cuts_to_thresholds_jnp(
            jnp.asarray(cs[idx]), cuts))

    def plan(n):
        thr, _ = pool_thresholds(st.sample_c, st.sample_a, st.sample_valid,
                                 16, opt_samples=n)
        return np.asarray(thr)

    np.testing.assert_array_equal(
        plan(100), dp_thresholds(order[(np.arange(100) * m) // 100]))
    whole = dp_thresholds(order)
    np.testing.assert_array_equal(plan(m), whole)
    np.testing.assert_array_equal(np.asarray(reoptimize_cuts(ing)[0]), whole)
    # the plan never has fewer than k + 1 samples
    assert plan(2).shape == (15,)


def test_updatable_synopsis_bridges_to_streaming():
    syn, c0, a0 = _base()
    upd = UpdatableSynopsis(syn, seed=1)
    upd.insert(np.array([50.0]), 7.0)
    ing = upd.to_streaming(seed=2)
    assert ing.total_rows == syn.total_rows + 1
    merged = ing.as_synopsis()
    assert float(np.asarray(merged.leaf_agg)[:, AGG_SUM].sum()) \
        == pytest.approx(a0.sum() + 7.0, rel=1e-5)


@pytest.mark.parametrize("method,seed,values", [
    ("eq", 0, "continuous"),
    ("adp", 1, "continuous"),
    ("eq", 2, "duplicates"),      # touching boxes: hi[i] == lo[i+1]
    ("eq", 3, "heavy-dup"),       # degenerate [v, v] leaves inside a run
])
def test_route_1d_matches_dense_argmin(method, seed, values):
    """The O(B log k) 1-D route is bit-identical to the dense (B, k)
    argmin formulation — including empty leaves, out-of-range rows, and
    rows landing exactly on boundary values shared by touching boxes
    (equal-depth cuts on duplicate-valued data)."""
    rng = np.random.default_rng(seed)
    if values == "continuous":
        c0 = np.round(rng.uniform(0, 10, 5000), 1)  # some adp duplicates
    elif values == "duplicates":
        c0 = rng.integers(0, 20, 5000).astype(np.float64)
    else:                                           # 60% of rows equal 5.0
        c0 = np.where(rng.random(5000) < 0.6, 5.0,
                      rng.integers(0, 20, 5000).astype(np.float64))
    a0 = rng.lognormal(0, 1, 5000)
    syn, _ = build_synopsis(c0, a0, k=8 if values != "continuous" else 32,
                            sample_budget=128, method=method)
    state = init_state(syn)
    # probe mix: random, exact data values (boundary hits), out-of-range
    probes = np.concatenate([rng.uniform(-2, 22, 512),
                             rng.choice(np.unique(c0), 512)])
    c = jnp.asarray(probes[:, None], jnp.float32)
    leaf_fast, dist_fast = _route_1d(state.leaf_lo, state.leaf_hi, c)
    dense = np.asarray(_route_dist(state.leaf_lo, state.leaf_hi, c))
    leaf_dense = dense.argmin(axis=1)
    np.testing.assert_array_equal(np.asarray(leaf_fast), leaf_dense)
    np.testing.assert_array_equal(
        np.asarray(dist_fast),
        np.take_along_axis(dense, leaf_dense[:, None], 1)[:, 0])


def test_route_1d_degenerate_equal_lo_boxes():
    """A duplicate run ending exactly at a leaf cut produces several
    degenerate boxes sharing the same lo (and hi); rows in the gap above
    them must route to the FIRST such box, like the dense argmin."""
    rng = np.random.default_rng(7)
    c0 = np.concatenate([np.full(1250, 5.0), rng.uniform(7, 9, 1250)])
    a0 = rng.lognormal(0, 1, 2500)
    syn, _ = build_synopsis(c0, a0, k=4, sample_budget=64, method="eq")
    state = init_state(syn)
    probes = np.concatenate([[5.0, 5.5, 6.9, 7.0, 4.0, 10.0],
                             rng.uniform(3, 11, 250)])
    c = jnp.asarray(probes[:, None], jnp.float32)
    leaf_fast, dist_fast = _route_1d(state.leaf_lo, state.leaf_hi, c)
    dense = np.asarray(_route_dist(state.leaf_lo, state.leaf_hi, c))
    np.testing.assert_array_equal(np.asarray(leaf_fast),
                                  dense.argmin(axis=1))
    np.testing.assert_array_equal(np.asarray(dist_fast), dense.min(axis=1))


def test_route_1d_fuzz_synthetic_interval_sets():
    """Direct fuzz over synthetic disjoint-or-touching interval sets with
    degenerate boxes and trailing empties."""
    rng = np.random.default_rng(11)
    for _ in range(20):
        k = int(rng.integers(2, 12))
        # build k ascending interval bounds; ~40% degenerate, some touching
        bounds = np.sort(rng.integers(0, 15, 2 * k).astype(np.float64))
        lo = bounds[0::2].copy()
        hi = bounds[1::2].copy()
        n_empty = int(rng.integers(0, 2))
        if n_empty:
            lo[-1], hi[-1] = np.inf, -np.inf
        state_lo = jnp.asarray(lo[:, None], jnp.float32)
        state_hi = jnp.asarray(hi[:, None], jnp.float32)
        probes = np.concatenate([rng.uniform(-3, 18, 64),
                                 bounds + 0.0, bounds + 0.5])
        c = jnp.asarray(probes[:, None], jnp.float32)
        leaf_fast, dist_fast = _route_1d(state_lo, state_hi, c)
        dense = np.asarray(_route_dist(state_lo, state_hi, c))
        np.testing.assert_array_equal(np.asarray(leaf_fast),
                                      dense.argmin(axis=1))
        np.testing.assert_array_equal(np.asarray(dist_fast),
                                      dense.min(axis=1))


def test_batched_ingest_bitmatch_on_duplicate_valued_data():
    """End-to-end bit-match on data whose equal-depth boxes touch, with
    streamed rows drawn from the same duplicated support (every row lands
    on a shared boundary candidate)."""
    rng = np.random.default_rng(4)
    c0 = rng.integers(0, 20, 8000).astype(np.float64)
    a0 = rng.integers(1, 8, 8000).astype(np.float64)
    syn, _ = build_synopsis(c0, a0, k=8, sample_budget=64, method="eq")
    ing = StreamingIngestor(syn, seed=1)
    ref = init_state(syn)
    for _ in range(2):
        c_new = rng.integers(-2, 24, 256).astype(np.float32)
        a_new = rng.integers(1, 8, 256).astype(np.float32)
        u = rng.random(256, dtype=np.float32)
        ing.ingest(c_new, a_new, u=u)
        ref = ingest_batch_reference(ref, c_new, a_new, u)
    _assert_states_equal(ing.state, ref, exact=True)


def test_auto_block_n():
    assert auto_block_n(1) == 1024
    assert auto_block_n(1024) == 1024
    assert auto_block_n(1025) == 2048
    assert auto_block_n(10_000) == 2048        # capped at the build default


def test_streaming_prng_key_determinism():
    """Satellite: the reservoir uniforms come from an explicit threaded jax
    PRNG key (threefry — bit-stable across hosts and jax versions), so two
    ingestors with the same seed produce bit-identical state through the
    u=None path, and an explicit key reproduces the seeded run."""
    import jax
    syn, _, _ = _base(n=10000, k=8, sample_budget=32)
    rng = np.random.default_rng(21)
    batches = [(rng.uniform(0, 100, 512).astype(np.float32),
                rng.integers(1, 64, 512).astype(np.float32))
               for _ in range(3)]
    ing1 = StreamingIngestor(syn, seed=7)
    ing2 = StreamingIngestor(syn, seed=7)
    ing3 = StreamingIngestor(syn, key=jax.random.PRNGKey(7))
    ing4 = StreamingIngestor(syn, seed=8)
    for c_new, a_new in batches:
        for ing in (ing1, ing2, ing3, ing4):
            ing.ingest(c_new, a_new)
    _assert_states_equal(ing1.state, ing2.state, exact=True)
    _assert_states_equal(ing1.state, ing3.state, exact=True)
    # a different seed must draw different replacement decisions
    assert not np.array_equal(np.asarray(ing1.state.sample_a),
                              np.asarray(ing4.state.sample_a))
    # and only the reservoir sampling differs: aggregates stay identical
    np.testing.assert_array_equal(np.asarray(ing1.state.delta_agg),
                                  np.asarray(ing4.state.delta_agg))


def test_reoptimize_neyman_rebalances_sample_budget():
    """The default 'neyman' allocation re-splits the old total reservoir
    budget toward the strata drift made large/volatile, keeping the total;
    'equal' preserves the historical uniform split."""
    rng = np.random.default_rng(21)
    k, s = 8, 64
    c0 = rng.normal(size=8000)
    a0 = rng.normal(size=8000)
    syn, _ = build_synopsis(c0, a0, k=k, sample_budget=k * s, method="eq",
                            seed=0)
    ing = StreamingIngestor(syn, seed=3)
    # drifted tail: shifted support, heavy-tailed values
    c1 = rng.normal(loc=4.0, size=6000)
    a1 = rng.gamma(2.0, 1.0, size=6000) * np.exp(rng.normal(0, 1, size=6000))
    for i in range(0, 6000, 1500):
        ing.ingest(c1[i:i + 1500], a1[i:i + 1500])
    c_all = np.concatenate([c0, c1])
    a_all = np.concatenate([a0, a1])

    from repro.streaming.policy import reoptimize
    ing_eq, _ = reoptimize(ing, c_all, a_all, allocation="equal", seed=7)
    ing_ney, rep = reoptimize(ing, c_all, a_all, seed=7)   # default neyman
    alloc_eq = np.asarray(ing_eq.base.k_per_leaf)
    alloc_ney = np.asarray(ing_ney.base.k_per_leaf)
    assert alloc_eq.sum() == alloc_ney.sum() == k * s      # budget conserved
    assert not np.array_equal(alloc_eq, alloc_ney)         # actually moved
    # slots concentrate: the most volatile stratum takes far more than the
    # uniform share, the quietest far less
    assert alloc_ney.max() > 2 * s
    assert alloc_ney.min() < s // 2
    # the rebuilt synopsis still answers sanely
    q = QueryBatch(lo=jnp.asarray([[2.0]], jnp.float32),
                   hi=jnp.asarray([[6.0]], jnp.float32))
    from repro.api import PassEngine, ServingConfig
    eng = PassEngine(ing_ney.as_synopsis(),
                     serving=ServingConfig(kinds=("sum",)))
    res = eng.answer(q)
    truth = a_all[(c_all >= 2.0) & (c_all <= 6.0)].sum()
    assert abs(float(np.asarray(res["sum"].estimate)[0]) - truth) \
        < 0.2 * abs(truth)
    with pytest.raises(ValueError, match="allocation"):
        reoptimize(ing, c_all, a_all, allocation="bogus")
