"""The benchmark's generators and reference: deterministic from the seed,
and equal to the program's own functions as they stood when copied."""
import _paths  # noqa: F401
import numpy as np
import pytest

from bench.data import queries, taxi
from bench.reference import f64
from bench.traffic import generator

SCALE = 0.004          # 30,800 rows


@pytest.mark.parametrize("dims", [1, 3])
def test_same_seed_same_data_and_queries(dims):
    c1, a1 = taxi.nyc_taxi(scale=SCALE, seed=5, dims=dims)
    c2, a2 = taxi.nyc_taxi(scale=SCALE, seed=5, dims=dims)
    assert np.array_equal(c1, c2) and np.array_equal(a1, a2)
    c3, _ = taxi.nyc_taxi(scale=SCALE, seed=6, dims=dims)
    assert not np.array_equal(c1, c3)
    q1 = queries.random_queries(c1, 50, seed=9)
    q2 = queries.random_queries(c1, 50, seed=9)
    assert all(np.array_equal(x, y) for x, y in zip(q1, q2))
    p1 = taxi.stream_pool(1000, 4, dims)
    p2 = taxi.stream_pool(1000, 4, dims)
    assert all(np.array_equal(x, y) for x, y in zip(p1, p2))


def test_schedules_same_seed_same_work_other_seed_same_load():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 50.0},
           "sessions": 4}
    a = generator.open_schedule(mix, 10.0, seed=1)
    b = generator.open_schedule(mix, 10.0, seed=1)
    c = generator.open_schedule(mix, 10.0, seed=2**31 + 7)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    # another seed reorders the same gaps: same count, same end
    assert a.size == c.size and a.size > 0
    assert np.isclose(a[-1], c[-1]) and a[-1] < 10.0
    assert np.all(np.diff(a) > 0)
    assert generator.shape_rows(mix) == [1]
    assert generator.closed_sessions(mix) == 4


@pytest.mark.parametrize("dims", [1, 3])
def test_copies_equal_the_program_functions(dims):
    from repro.core.query import ground_truth_kinds, random_queries
    from repro.core.types import QueryBatch
    from repro.data.synthetic import nyc_taxi
    c, a = taxi.nyc_taxi(scale=SCALE, seed=11, dims=dims)
    pc, pa = nyc_taxi(scale=SCALE, seed=11, dims=dims)
    assert np.array_equal(c, pc) and np.array_equal(a, pa)
    lo, hi = queries.random_queries(c, 40, seed=2, min_frac=0.05,
                                    max_frac=0.5)
    pq = random_queries(c, 40, seed=2, min_frac=0.05, max_frac=0.5)
    assert np.array_equal(lo, np.asarray(pq.lo))
    assert np.array_equal(hi, np.asarray(pq.hi))
    want = ground_truth_kinds(c, a, QueryBatch(pq.lo, pq.hi),
                              ("sum", "count", "avg"))
    got = f64.ground_truth_kinds(c, a, lo, hi)
    for k in ("sum", "count", "avg"):
        assert np.array_equal(got[k], want[k])


@pytest.mark.parametrize("dims", [1, 3])
def test_sorted_table_equals_the_scan(dims):
    c, a = taxi.nyc_taxi(scale=SCALE, seed=3, dims=dims)
    lo, hi = queries.random_queries(c, 64, seed=4, min_frac=0.01,
                                    max_frac=0.5)
    scan = f64.ground_truth_kinds(c, a, lo, hi)
    s, n = f64.Table(c, a).sum_count(lo, hi)
    assert np.array_equal(n, scan["count"])
    np.testing.assert_allclose(s, scan["sum"], rtol=1e-12, atol=1e-9)


def test_covered_probe_cuts_no_leaf():
    from bench.harness import work
    leaf_lo = np.array([[0.0], [2.0], [5.0]], np.float32)
    leaf_hi = np.array([[1.0], [4.0], [9.0]], np.float32)
    lo, hi = queries.covered_queries(leaf_lo, leaf_hi, 8, seed=0)
    assert lo[0, 0] < -1e37 and hi[0, 0] > 1e37
    assert not work.partial_mask(lo, hi, leaf_lo, leaf_hi).any()
