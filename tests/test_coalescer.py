"""Multi-tenant request coalescer (repro.serve): demux bit-identity
against per-tenant engine.answer (any bucketing, any arrival order,
mid-stream epoch bumps, a hypothesis property over tenant
interleavings), admission control / shedding, per-tenant accounting
through engine.stats(), the event-loop driver, and a concurrent soak
against a sharded-ingest engine (the CI multi-device leg runs it on 4
forced host devices)."""
import concurrent.futures as cf
import threading
import time

import numpy as np
import pytest
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from conftest import given, settings, st

from repro.api import (PassEngine, ServingConfig, CIConfig, CoalescerConfig)
from repro.core import build_synopsis, random_queries
from repro.core.types import QueryBatch, QueryResult
from repro.joins import build_dim_table, build_join_synopsis
from repro.serve import RequestCoalescer, TickDriver, Overloaded
from repro.serve import coalescer as coalescer_mod

ALL_KINDS = ("sum", "count", "avg", "min", "max")
FIELDS = ("estimate", "ci_half", "lower", "upper", "frac_rows_touched",
          "ci_lo", "ci_hi")


def _make(seed=0, n=12000, k=16, rate=0.02):
    rng = np.random.default_rng(seed)
    c = np.sort(rng.uniform(0, 100, n))
    a = rng.lognormal(0, 1, n) * (1 + np.sin(c / 5))
    syn, _ = build_synopsis(c, a, k=k, sample_rate=rate, method="eq",
                            seed=seed)
    return c, a, syn


def _assert_results_equal(got, want):
    assert set(got) == set(want)
    for kind in want:
        for f in FIELDS:
            g, w = getattr(got[kind], f), getattr(want[kind], f)
            if g is None or w is None:
                assert g is None and w is None, (kind, f)
                continue
            assert np.array_equal(np.asarray(g), np.asarray(w)), (kind, f)


def _fresh_answer(source, queries, serving, ci=None):
    """Per-tenant oracle: a cold engine answering this batch alone."""
    return PassEngine(source, serving=serving, ci=ci).answer(queries)


# --------------------------------------------------------------------------
# Demux bit-identity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("ci", [None, 0.95])
def test_coalesced_bit_identical_to_per_tenant_answers(ci):
    """Acceptance: every tenant's demuxed slice == its own engine.answer,
    every kind, every result field, across multiple shape classes and
    multi-request packing inside one padded dispatch."""
    c, a, syn = _make()
    kinds = ("sum", "count", "avg") if ci is not None else ALL_KINDS
    serving = ServingConfig(kinds=kinds)
    eng = PassEngine(syn, serving=serving, ci=ci)
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(8, 32)))
    sizes = [3, 5, 7, 2, 9, 11, 8, 1]
    batches = {f"t{i}": random_queries(c, q, seed=20 + i)
               for i, q in enumerate(sizes)}
    futs = {t: co.submit(t, qs) for t, qs in batches.items()}
    n_dispatch = co.tick()
    # cross-tenant coalescing actually happened: fewer device dispatches
    # than requests
    assert 0 < n_dispatch < len(sizes)
    for t, qs in batches.items():
        _assert_results_equal(futs[t].result(timeout=0),
                              _fresh_answer(syn, qs, serving, ci))
    s = co.stats()
    assert s["served"] == len(sizes)
    assert s["coalesced_rows"] == sum(sizes)
    assert s["dispatches"] == n_dispatch


def test_coalesced_bit_identical_bootstrap():
    c, a, syn = _make(seed=3, k=8, n=8000)
    serving = ServingConfig(kinds=("sum", "avg"))
    ci = CIConfig(method="bootstrap", n_boot=16)
    co = RequestCoalescer(PassEngine(syn, serving=serving, ci=ci),
                          CoalescerConfig(shape_classes=(16,)))
    batches = {t: random_queries(c, q, seed=i)
               for i, (t, q) in enumerate([("a", 4), ("b", 6), ("c", 5)])}
    futs = {t: co.submit(t, qs) for t, qs in batches.items()}
    assert co.tick() == 1                      # 15 rows -> one padded 16
    for t, qs in batches.items():
        _assert_results_equal(futs[t].result(timeout=0),
                              _fresh_answer(syn, qs, serving, ci))


def test_arrival_order_never_changes_answers():
    """Demux bit-identity holds for ANY submission order: per-query rows
    are independent, so the packing permutation must not matter."""
    c, a, syn = _make(k=8, n=6000)
    serving = ServingConfig(kinds=("sum", "avg"))
    sizes = [(f"t{i}", 2 + i) for i in range(6)]
    batches = {t: random_queries(c, q, seed=40 + q) for t, q in sizes}
    want = {t: _fresh_answer(syn, qs, serving)
            for t, qs in batches.items()}
    for perm_seed in range(3):
        order = np.random.default_rng(perm_seed).permutation(len(sizes))
        co = RequestCoalescer(PassEngine(syn, serving=serving),
                              CoalescerConfig(shape_classes=(4, 16)))
        futs = {}
        for j in order:
            t = sizes[j][0]
            futs[t] = co.submit(t, batches[t])
        co.tick()
        for t in futs:
            _assert_results_equal(futs[t].result(timeout=0), want[t])


def test_mixed_configs_bucket_apart_and_stay_correct():
    """Requests with different per-request configs never share a
    dispatch, and each still matches its own oracle."""
    c, a, syn = _make(k=8, n=6000)
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum",)))
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(8,)))
    qs = random_queries(c, 4, seed=1)
    f_plain = co.submit("a", qs)
    f_ci = co.submit("b", qs, ci=0.9)
    f_kinds = co.submit("c", qs, kinds=("count", "max"))
    assert co.tick() == 3                      # three (config) buckets
    _assert_results_equal(f_plain.result(0),
                          _fresh_answer(syn, qs, ServingConfig(("sum",))))
    _assert_results_equal(f_ci.result(0),
                          _fresh_answer(syn, qs, ServingConfig(("sum",)),
                                        ci=0.9))
    _assert_results_equal(
        f_kinds.result(0),
        _fresh_answer(syn, qs, ServingConfig(("count", "max"))))


def test_oversize_request_rounds_up_to_ladder_multiple():
    c, a, syn = _make(k=8, n=6000)
    serving = ServingConfig(kinds=("sum",))
    co = RequestCoalescer(PassEngine(syn, serving=serving),
                          CoalescerConfig(shape_classes=(4, 8)))
    qs = random_queries(c, 19, seed=9)         # > top class 8 -> padded 24
    fut = co.submit("big", qs)
    assert co.tick() == 1
    _assert_results_equal(fut.result(0), _fresh_answer(syn, qs, serving))
    assert co.stats()["padded_rows"] == 24 - 19


def test_mid_stream_epoch_bump_drains_then_serves_fresh_merge():
    """Requests dispatched before an ingest answer the old epoch; requests
    after it answer the new delta merge — each bit-identical to a
    per-tenant engine.answer against the matching state — and the bump
    forces one in-flight drain before re-pinning."""
    from repro.streaming import StreamingIngestor
    c, a, syn = _make(k=8, n=10000)
    rng = np.random.default_rng(7)
    ing = StreamingIngestor(syn, seed=3)
    serving = ServingConfig(kinds=("sum", "count"))
    eng = PassEngine(ing, serving=serving)
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(8,)))
    qs = random_queries(c, 6, seed=5, min_frac=0.2, max_frac=0.6)
    want_old = _fresh_answer(ing, qs, serving)   # epoch-0 oracle, eager
    f_old = co.submit("a", qs)
    co.tick()
    ing.ingest(rng.uniform(0, 100, 4096), rng.lognormal(0, 1, 4096))
    f_new = co.submit("a", qs)
    co.tick()
    _assert_results_equal(f_old.result(0), want_old)
    _assert_results_equal(f_new.result(0), _fresh_answer(ing, qs, serving))
    assert co.stats()["epoch_drains"] == 1
    assert not np.array_equal(
        np.asarray(f_old.result(0)["count"].estimate),
        np.asarray(f_new.result(0)["count"].estimate))


@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_property_tenant_interleavings_bit_identical(data):
    """Hypothesis property: any interleaving of tenant requests across
    any tick schedule (including a mid-stream ingest) demuxes
    bit-identically to per-tenant answers against the matching epoch."""
    from repro.streaming import StreamingIngestor
    c, a, syn = _make(seed=11, k=8, n=6000)
    serving = ServingConfig(kinds=("sum", "avg"))
    n_req = data.draw(st.integers(2, 6), label="n_req")
    sizes = [data.draw(st.integers(1, 9), label=f"q{i}")
             for i in range(n_req)]
    tenants = [data.draw(st.sampled_from(["a", "b", "c"]), label=f"t{i}")
               for i in range(n_req)]
    bump_at = data.draw(st.integers(0, n_req), label="bump_at")
    order = data.draw(st.permutations(list(range(n_req))), label="order")

    ing = StreamingIngestor(syn, seed=5)
    eng = PassEngine(ing, serving=serving)
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(4, 16)))
    futs, want = [], []
    for step, j in enumerate(order):
        if step == bump_at:
            co.tick()                           # dispatch pre-bump queue
            rng = np.random.default_rng(step)
            ing.ingest(rng.uniform(0, 100, 512),
                       rng.lognormal(0, 1, 512))
        qs = random_queries(c, sizes[j], seed=100 + j)
        futs.append(co.submit(tenants[j], qs))
        want.append(_fresh_answer(ing, qs, serving))   # eager: same epoch
    co.tick()
    for fut, w in zip(futs, want):
        _assert_results_equal(fut.result(timeout=0), w)


# --------------------------------------------------------------------------
# Admission control and accounting
# --------------------------------------------------------------------------

def test_admission_per_tenant_outstanding_sheds_typed():
    c, a, syn = _make(k=4, n=3000)
    co = RequestCoalescer(PassEngine(syn),
                          CoalescerConfig(max_outstanding=2))
    qs = random_queries(c, 4, seed=1)
    co.submit("x", qs)
    co.submit("x", qs)
    with pytest.raises(Overloaded) as ei:
        co.submit("x", qs)
    assert ei.value.reason == "tenant_outstanding"
    assert ei.value.tenant == "x" and ei.value.limit == 2
    co.submit("y", qs)                         # other tenants unaffected
    co.tick()                                  # queue drains ...
    co.submit("x", qs)                         # ... budget frees up
    co.tick()
    s = co.stats()
    assert s["shed"] == 1 and s["served"] == 4
    assert s["tenants"]["x"]["shed"] == 1
    assert s["tenants"]["x"]["requests"] == 3  # shed submissions don't count


def test_admission_global_queue_depth_sheds_typed():
    c, a, syn = _make(k=4, n=3000)
    co = RequestCoalescer(PassEngine(syn),
                          CoalescerConfig(max_queue_depth=3,
                                          max_outstanding=10))
    qs = random_queries(c, 2, seed=1)
    for t in ("a", "b", "c"):
        co.submit(t, qs)
    with pytest.raises(Overloaded) as ei:
        co.submit("d", qs)
    assert ei.value.reason == "queue_depth" and ei.value.limit == 3
    co.tick()
    co.submit("d", qs)                         # depth freed by the tick
    co.tick()


def test_accounting_through_engine_stats():
    """Per-tenant accounting (queries served), dispatch amortization and
    the queue-wait counters are reachable from engine.stats()."""
    c, a, syn = _make(k=4, n=3000)
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum",)))
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(16,)))
    for i in range(4):
        co.submit("alice", random_queries(c, 3, seed=i))
    co.submit("bob", random_queries(c, 4, seed=9))
    co.tick()
    s = eng.stats()["coalescer"]
    assert s["served"] == 5
    assert s["dispatches"] == 1                # all five shared one dispatch
    assert s["coalesced_rows"] == 16 and s["padded_rows"] == 0
    alice = s["tenants"]["alice"]
    assert alice["queries"] == 12 and alice["requests"] == 4
    assert s["queue_waits"] == 5 and s["queue_wait_ns"] > 0
    assert s["tenants"]["bob"]["queries"] == 4
    # buckets reuse ONE prepared executable: a second wave of the same
    # shapes is all plan-cache hits
    misses0 = eng.stats()["misses"]
    for i in range(3):
        co.submit("alice", random_queries(c, 5, seed=20 + i))
    co.tick()
    assert eng.stats()["misses"] == misses0


def test_queue_wait_counters_cover_every_dispatched_request():
    """queue_wait_ns sums each dispatched request's wait from submit to the
    start of its dispatch, over queue_waits requests: a dedup rider counts,
    a tier-0 answer (no dispatch) does not."""
    c, a, syn = _make(k=4, n=3000)
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum",)))
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(16,)))
    before = co.stats()
    qs = random_queries(c, 3, seed=1)
    co.submit("a", qs)
    co.submit("b", qs)                          # rides a's dispatch
    co.submit("c", random_queries(c, 4, seed=2))
    co.submit("d", random_queries(c, 2, seed=3), deadline_ms=0.0)
    time.sleep(0.005)
    co.tick()
    s = co.stats()
    assert s["dedup_hits"] == 1 and s["degraded_served"] == 1
    assert s["served"] == 4 and s["dispatches"] == 1
    assert s["queue_waits"] - before["queue_waits"] == 3
    assert s["queue_wait_ns"] - before["queue_wait_ns"] >= 3 * 5_000_000


# --------------------------------------------------------------------------
# Device-to-host pull: one transfer per dispatch
# --------------------------------------------------------------------------

def _per_field_pull(results):
    """The pull the packed one replaced: one host copy per present field."""
    return {kind: [None if (v := getattr(r, f)) is None else np.asarray(v)
                   for f in coalescer_mod._QR_FIELDS]
            for kind, r in results.items()}


def _pull_case_results(case):
    """A dispatch-shaped result dict for each layout the pull must take."""
    if case == "join":
        rng = np.random.default_rng(7)
        c = rng.normal(size=3000).astype(np.float32)
        a = rng.gamma(2.0, 1.0, size=3000).astype(np.float32)
        keys = rng.integers(0, 100, size=3000).astype(np.int32)
        dim = build_dim_table(np.arange(100, dtype=np.int32),
                              rng.normal(size=100).astype(np.float32),
                              num_partitions=4)
        jsyn, _ = build_join_synopsis(c, a, keys, dim, k=8, p_u=0.3, seed=19)
        eng = PassEngine(jsyn, ci=CIConfig(level=0.95))
        fq = QueryBatch(lo=jnp.asarray([[-1.0], [0.0], [-0.2]], jnp.float32),
                        hi=jnp.asarray([[0.5], [2.0], [0.3]], jnp.float32))
        dq = QueryBatch(lo=jnp.asarray([[-0.5], [-2.0], [-1.0]], jnp.float32),
                        hi=jnp.asarray([[2.0], [1.0], [0.0]], jnp.float32))
        return eng.answer_join(fq, dq, kinds=("sum", "count"))
    c, a, syn = _make(seed=3, k=8, n=8000)
    qs = random_queries(c, 8, seed=5)
    if case == "mixed":
        # Two dtypes and a field already on the host: one transfer per
        # dtype group, the host field passes through.
        base = PassEngine(syn, serving=ServingConfig(kinds=("sum",))
                          ).answer(qs)["sum"]
        return {"sum": base, "count": QueryResult(
            estimate=jnp.arange(8, dtype=jnp.int32),
            ci_half=np.full(8, 0.5, np.float32), lower=base.lower,
            upper=base.upper, frac_rows_touched=jnp.ones((2, 4), jnp.int32))}
    ci = {"plain": None, "clt": CIConfig(level=0.95),
          "bootstrap": CIConfig(method="bootstrap", n_boot=16)}[case]
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum", "count", "avg")),
                     ci=ci)
    return eng.answer(qs)


@pytest.mark.parametrize("case, transfers", [
    ("plain", 1), ("clt", 1), ("bootstrap", 1), ("join", 1), ("mixed", 2)])
def test_packed_pull_matches_per_field_pull(case, transfers):
    """The one-transfer pull returns, field for field, the arrays the
    per-field pull returned: same dtype, shape and bits, None in the same
    places, in one transfer per dtype of the result."""
    results = _pull_case_results(case)
    want = _per_field_pull(results)
    coalescer_mod._PULLED.transfers = 0
    got = coalescer_mod._pull_host(results)
    assert coalescer_mod._PULLED.transfers == transfers
    assert list(got) == list(want)
    for kind in want:
        assert len(got[kind]) == len(want[kind]) == len(FIELDS)
        for f, g, w in zip(FIELDS, got[kind], want[kind]):
            if w is None:
                assert g is None, (kind, f)
                continue
            assert isinstance(g, np.ndarray), (kind, f)
            assert g.dtype == w.dtype and g.shape == w.shape, (kind, f)
            assert np.array_equal(g, w) and g.tobytes() == w.tobytes(), \
                (kind, f)


def test_pull_transfers_one_per_dispatch():
    """stats()["pull_transfers"] grows by exactly one per dispatch, over
    ticks of one and of several dispatches."""
    c, a, syn = _make(k=8, n=6000)
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum", "count",
                                                       "avg")), ci=0.95)
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(4, 8)))
    assert co.stats()["pull_transfers"] == 0
    total = 0
    for tick, sizes in enumerate([[3], [2, 2, 3], [4, 4, 7, 1], [8]]):
        for i, q in enumerate(sizes):
            co.submit(f"t{i}", random_queries(c, q, seed=10 * tick + i))
        n = co.tick()
        total += n
        s = co.stats()
        assert s["dispatches"] == total
        assert s["pull_transfers"] == total
    assert total > 4                            # some tick dispatched twice


def test_request_slices_are_views_of_their_own_rows(monkeypatch):
    """Every request's result fields are zero-copy views of its own row
    range of the one host buffer the dispatch pulled."""
    c, a, syn = _make(k=8, n=6000)
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum", "avg")),
                     ci=0.95)
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(16,)))
    pulled = []
    orig = coalescer_mod._pull_host

    def spy(results):
        host = orig(results)
        pulled.append(host)
        return host

    monkeypatch.setattr(coalescer_mod, "_pull_host", spy)
    sizes = [3, 5, 1, 6]
    futs = [co.submit(f"t{i}", random_queries(c, q, seed=i))
            for i, q in enumerate(sizes)]
    assert co.tick() == 1 and len(pulled) == 1
    host = pulled[0]
    owners = {id(arr.base) for arrs in host.values()
              for arr in arrs if arr is not None}
    assert len(owners) == 1                     # one buffer, one transfer
    got = [f.result(timeout=0) for f in futs]
    for kind, arrs in host.items():
        for i, f in enumerate(FIELDS):
            full = arrs[i]
            if full is None:
                assert all(getattr(r[kind], f) is None for r in got)
                continue
            off = 0
            for j, (res, rows) in enumerate(zip(got, sizes)):
                v = getattr(res[kind], f)
                assert v.shape == (rows,)
                assert np.shares_memory(v, full)
                assert (v.__array_interface__["data"][0]
                        == full[off:].__array_interface__["data"][0])
                for other in got[j + 1:]:
                    assert not np.shares_memory(v, getattr(other[kind], f))
                off += rows


def test_coalescer_config_validation():
    with pytest.raises(ValueError, match="tick_ms"):
        CoalescerConfig(tick_ms=0).validate()
    with pytest.raises(ValueError, match="non-empty"):
        CoalescerConfig(shape_classes=()).validate()
    with pytest.raises(ValueError, match="ascending"):
        CoalescerConfig(shape_classes=(32, 8)).validate()
    with pytest.raises(ValueError, match="positive"):
        CoalescerConfig(shape_classes=(0, 8)).validate()
    with pytest.raises(ValueError, match="max_outstanding"):
        CoalescerConfig(max_outstanding=0).validate()
    with pytest.raises(ValueError, match="max_queue_depth"):
        CoalescerConfig(max_queue_depth=0).validate()
    assert CoalescerConfig(shape_classes=(4, 8)).padded_size(3) == 4
    assert CoalescerConfig(shape_classes=(4, 8)).padded_size(8) == 8
    assert CoalescerConfig(shape_classes=(4, 8)).padded_size(17) == 24
    c, a, syn = _make(k=4, n=3000)
    co = RequestCoalescer(PassEngine(syn))
    with pytest.raises(ValueError, match="non-empty"):
        co.submit("t", QueryBatch(jnp.zeros((0, 1)), jnp.zeros((0, 1))))


# --------------------------------------------------------------------------
# Event-loop driver
# --------------------------------------------------------------------------

def test_tick_driver_background_serving_and_flush_on_stop():
    c, a, syn = _make(k=8, n=6000)
    serving = ServingConfig(kinds=("sum", "count"))
    eng = PassEngine(syn, serving=serving)
    co = RequestCoalescer(eng, CoalescerConfig(tick_ms=1.0,
                                               shape_classes=(8, 32)))
    batches = {f"t{i}": random_queries(c, 3 + i, seed=i) for i in range(6)}
    want = {t: _fresh_answer(syn, qs, serving)
            for t, qs in batches.items()}
    with TickDriver(co) as driver:
        assert driver.running
        with cf.ThreadPoolExecutor(6) as ex:
            got = {t: f for t, f in
                   ((t, ex.submit(co.answer, t, qs, timeout=60))
                    for t, qs in batches.items())}
            for t in batches:
                _assert_results_equal(got[t].result(), want[t])
    assert not driver.running
    assert co.queue_depth == 0                 # stop() flushed
    assert co.stats()["served"] == 6


def test_tick_driver_double_start_raises_and_stop_idempotent():
    c, a, syn = _make(k=4, n=3000)
    co = RequestCoalescer(PassEngine(syn))
    driver = TickDriver(co).start()
    with pytest.raises(RuntimeError, match="already started"):
        driver.start()
    driver.stop()
    driver.stop()                              # no-op
    driver.start().stop()                      # restartable


# --------------------------------------------------------------------------
# Soak: concurrent tenants against a sharded-ingest engine (the CI
# multi-device leg forces 4 host devices for this)
# --------------------------------------------------------------------------

def test_soak_concurrent_tenants_sharded_ingest_engine():
    """Concurrent tenant threads + a concurrent ingest writer against a
    PassEngine.from_sharded source under the background driver: every
    request either serves or sheds typed, counters reconcile, and the
    plan-cache executable set stays bounded by the shape-class ladder."""
    rng = np.random.default_rng(0)
    n = 6000
    c = np.sort(rng.uniform(0, 100, n))
    a = rng.lognormal(0, 1, n)
    serving = ServingConfig(kinds=("sum", "count"))
    eng = PassEngine.from_sharded(c, a, k=8, sample_budget=8 * 32,
                                  serving=serving, seed=0)
    co = RequestCoalescer(eng, CoalescerConfig(
        tick_ms=1.0, shape_classes=(8, 32), max_outstanding=64,
        max_queue_depth=512))
    stop = threading.Event()
    errors = []

    def writer():
        wrng = np.random.default_rng(99)
        while not stop.is_set():
            try:
                eng.source.ingest(wrng.uniform(0, 100, 256),
                                  wrng.lognormal(0, 1, 256))
            except Exception as exc:           # pragma: no cover
                errors.append(exc)
                return
            stop.wait(0.003)

    def tenant(tid):
        trng = np.random.default_rng(tid)
        for i in range(8):
            qs = random_queries(c, int(trng.integers(1, 12)),
                                seed=tid * 100 + i)
            try:
                res = co.answer(f"tenant-{tid}", qs, timeout=60)
            except Overloaded:
                continue                       # typed shed is fine
            except Exception as exc:           # pragma: no cover
                errors.append(exc)
                return
            for kind in serving.kinds:
                est = np.asarray(res[kind].estimate)
                if est.shape != (qs.lo.shape[0],) or not np.isfinite(
                        est).all():            # pragma: no cover
                    errors.append(AssertionError((kind, est)))
                    return

    with TickDriver(co):
        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        threads = [threading.Thread(target=tenant, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        wt.join(timeout=30)
    assert not errors, errors[:3]
    s = co.stats()
    assert s["served"] + s["shed"] == s["submitted"]
    assert s["served"] >= 1 and s["queue_depth"] == 0
    assert sum(t["queries"] for t in s["tenants"].values()) \
        == s["coalesced_rows"]
    # bounded executable set: at most one plan-cache entry per ladder
    # class (+ rounded-up oversize multiples) for the single config
    assert eng.stats()["entries"] <= 4


# --------------------------------------------------------------------------
# Shed path under concurrent submitters (DESIGN.md §15)
# --------------------------------------------------------------------------

def test_concurrent_shed_counters_reconcile_and_no_stranded_futures():
    """Many threads hammering a tiny admission budget: every submit either
    returns a future or raises Overloaded; after flush() every returned
    future is resolved, per-tenant shed counts sum to the global counter,
    and submitted == served (shed requests are never queued)."""
    _, _, syn = _make()
    serving = ServingConfig(kinds=("sum",))
    eng = PassEngine(syn, serving=serving)
    co = RequestCoalescer(eng, CoalescerConfig(
        shape_classes=(8,), max_outstanding=2, max_queue_depth=6))
    futures, sheds = [], []
    lock = threading.Lock()
    barrier = threading.Barrier(6)

    def submitter(tid):
        rng = np.random.default_rng(tid)
        barrier.wait()
        for i in range(10):
            lo = rng.uniform(0, 70, (2, 1)).astype(np.float32)
            q = QueryBatch(lo=lo, hi=(lo + 10.0).astype(np.float32))
            try:
                f = co.submit(f"t{tid}", q)
                with lock:
                    futures.append(f)
            except Overloaded as exc:
                assert exc.reason in ("tenant_outstanding", "queue_depth")
                assert exc.tenant == f"t{tid}"
                with lock:
                    sheds.append(exc)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(6)]
    for t in threads:
        t.start()
    # Tick concurrently with the submitters so the queue drains and
    # admission keeps flipping between admit and shed.
    deadline = time.time() + 30
    while any(t.is_alive() for t in threads):
        co.tick()
        assert time.time() < deadline
    for t in threads:
        t.join()
    co.flush()

    assert len(futures) + len(sheds) == 60
    assert len(sheds) >= 1                      # the budget actually bit
    for f in futures:                           # nothing stranded
        assert f.done()
        assert set(f.result(timeout=0)) == {"sum"}
    s = co.stats()
    assert s["submitted"] == len(futures) == s["served"]
    assert s["shed"] == len(sheds)
    assert sum(t["shed"] for t in s["tenants"].values()) == s["shed"]
    assert all(t["outstanding"] == 0 for t in s["tenants"].values())
    assert s["queue_depth"] == 0


def test_flush_after_driverless_submits_resolves_everything():
    _, _, syn = _make()
    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum", "count")))
    co = RequestCoalescer(eng, CoalescerConfig(shape_classes=(8,)))
    qs = [random_queries(np.linspace(0, 100, 50), 3, seed=i)
          for i in range(9)]
    futs = [co.submit(f"t{i % 3}", q) for i, q in enumerate(qs)]
    assert not any(f.done() for f in futs)
    co.flush()
    assert all(f.done() for f in futs)
    s = co.stats()
    assert s["served"] == 9 and s["queue_depth"] == 0
