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


@pytest.mark.parametrize("dims", [1, 2, 3, 4])
def test_sorted_table_equals_the_scan(dims):
    c, a = taxi.nyc_taxi(scale=SCALE, seed=3, dims=dims)
    lo, hi = queries.random_queries(c, 64, seed=4, min_frac=0.01,
                                    max_frac=0.5)
    scan = f64.ground_truth_kinds(c, a, lo, hi)
    s, n = f64.Table(c, a).sum_count(lo, hi)
    assert np.array_equal(n, scan["count"])
    np.testing.assert_allclose(s, scan["sum"], rtol=1e-12,
                               atol=1e-9 if dims == 1 else 0.0)


def _edge_boxes(c, grid, rng):
    """(lo, hi) float64 boxes on the index's edges and the data's own
    values: bin ends, one step inside and outside them, single rows,
    empty boxes, boxes outside the data and the whole space."""
    n, d = c.shape
    lo, hi = [], []

    def add(l, h):
        lo.append(np.asarray(l, np.float64))
        hi.append(np.asarray(h, np.float64))

    add(np.full(d, -3e38), np.full(d, 3e38))
    for r in rng.choice(n, 12, replace=False):        # single rows
        add(c[r], c[r])
    for _ in range(12):                                # data values
        r = c[rng.choice(n, 2, replace=False)]
        add(r.min(axis=0), r.max(axis=0))
    for _ in range(40):                                # bin ends
        l, h = np.empty(d), np.empty(d)
        for j in range(d):
            ends = np.concatenate([grid.bin_min[j], grid.bin_max[j]])
            l[j], h[j] = np.sort(rng.choice(ends, 2))
        step = rng.integers(-1, 2, size=(2, d))
        add(np.where(step[0] < 0, np.nextafter(l, -np.inf),
                     np.where(step[0] > 0, np.nextafter(l, np.inf), l)),
            np.where(step[1] < 0, np.nextafter(h, -np.inf),
                     np.where(step[1] > 0, np.nextafter(h, np.inf), h)))
    for j in range(d):
        r = c[rng.choice(n, 2, replace=False)]
        l, h = r.min(axis=0), r.max(axis=0)
        add(np.where(np.arange(d) == j, h + 1, l), h)       # empty
        add(np.where(np.arange(d) == j, c[:, j].max() + 1, l),
            np.where(np.arange(d) == j, 3e38, h))            # past the data
        add(np.where(np.arange(d) == j, -3e38, l),
            np.where(np.arange(d) == j, c[:, j].min() - 1, h))
    return np.array(lo), np.array(hi)


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("dims", [2, 3, 4])
def test_index_edges_equal_the_scan(dims, ties):
    """Boxes on the grid's own bin ends and the data's values, single-row,
    empty, outside and whole-space boxes; with ``ties`` the last column
    holds seven integers (from d = 3 the pickup location is 1-265)."""
    c, a = taxi.nyc_taxi(scale=SCALE, seed=8, dims=dims)
    rng = np.random.default_rng(dims)
    if ties:
        c[:, -1] = rng.integers(0, 7, size=c.shape[0])
    table = f64.Table(c, a)
    lo, hi = _edge_boxes(c, table.grid, rng)
    scan = f64.ground_truth_kinds(c, a, lo, hi)
    s, n = table.sum_count(lo, hi)
    assert np.array_equal(n, scan["count"])
    np.testing.assert_allclose(s, scan["sum"], rtol=1e-12, atol=0.0)
    assert n[0] == c.shape[0] and np.any(n == 0) and np.any(n == 1)


@pytest.mark.parametrize("plant", ["count", "scan"])
def test_wrong_index_fails_the_self_check(plant):
    """An index whose prefix counts or scanned rows are off makes
    ``Truth.parts`` raise; the same queries on the sound index pass."""
    from bench.harness import check
    c, a = taxi.nyc_taxi(scale=SCALE, seed=9, dims=3)
    lo, hi = queries.random_queries(c, 24, seed=1, min_frac=0.3,
                                    max_frac=0.8)
    lo = np.concatenate([np.full((1, 3), -3e38, np.float32), lo])
    hi = np.concatenate([np.full((1, 3), 3e38, np.float32), hi])
    truth = check.Truth(c, a, seed=5)
    s, n, _, _ = truth.parts(lo, hi)
    assert truth.self_checked == lo.shape[0] and n[0] == c.shape[0]
    grid = truth.tables[0][0].grid
    if plant == "count":          # one row too many in the middle cell
        m = tuple(x // 2 for x in grid.p_count.shape)
        grid.p_count[m[0]:, m[1]:, m[2]:] += 1
    else:                         # every cut bin's values off by 1e-9
        for _k, _off, _cols, vals in grid.copies:
            vals *= 1.0 + 1e-9
    with pytest.raises(check.IndexMismatch):
        truth.parts(lo, hi)


def test_truth_answers_each_predicate_once_bit_equal():
    """Repeated predicates are answered once; every answer is bit-equal to
    the table's own, in 1-D (the committed cell's path) and in 3-D."""
    from bench.harness import check
    for dims in (1, 3):
        c, a = taxi.nyc_taxi(scale=SCALE, seed=12, dims=dims)
        lo, hi = queries.random_queries(c.reshape(c.shape[0], -1), 40,
                                        seed=2, min_frac=0.05, max_frac=0.6)
        pick = np.random.default_rng(0).integers(0, 40, 100)
        s, n, ps, pn = check.Truth(c, a).parts(lo[pick], hi[pick])
        ws, wn = f64.Table(c, a).sum_count(lo[pick], hi[pick])
        assert np.array_equal(s, ws) and np.array_equal(n, wn)
        assert ps.shape == pn.shape == (0, 100)


def test_covered_probe_cuts_no_leaf():
    from bench.harness import work
    leaf_lo = np.array([[0.0], [2.0], [5.0]], np.float32)
    leaf_hi = np.array([[1.0], [4.0], [9.0]], np.float32)
    lo, hi = queries.covered_queries(leaf_lo, leaf_hi, 8, seed=0)
    assert lo[0, 0] < -1e37 and hi[0, 0] > 1e37
    assert not work.partial_mask(lo, hi, leaf_lo, leaf_hi).any()
