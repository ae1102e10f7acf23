"""The needed-work count of the sample-moment pass on a tiny synopsis."""
import _paths  # noqa: F401
import numpy as np
import pytest

from bench.harness import work


def test_partial_strata_and_work_1d():
    # three leaves [0,1], [2,3], [4,5] (one empty: lo > hi), 10/20/30 samples
    leaf_lo = np.array([[0.0], [2.0], [9.0]], np.float32)
    leaf_hi = np.array([[1.0], [3.0], [8.0]], np.float32)
    n = np.array([10, 20, 30])
    # q0 cuts leaf 0 and covers leaf 1; q1 covers leaf 0 only; q2 misses all
    qlo = np.array([[0.5], [-1.0], [50.0]], np.float32)
    qhi = np.array([[3.5], [1.5], [60.0]], np.float32)
    part = work.partial_mask(qlo, qhi, leaf_lo, leaf_hi)
    assert part.tolist() == [[True, False, False], [False, False, False],
                             [False, False, False]]
    ops, nbytes = work.moment_pass_work(qlo, qhi, leaf_lo, leaf_hi, n)
    d = 1
    assert ops == 10 * (2 * d + 6)
    # samples of leaf 0 once (c and a, f32), bounds of 3 queries, 1 output
    assert nbytes == 10 * 2 * 4 + 3 * 2 * 4 + 1 * 3 * 4


def test_union_of_strata_is_read_once_per_dispatch():
    leaf_lo = np.array([[0.0, 0.0], [0.0, 5.0]], np.float32)
    leaf_hi = np.array([[4.0, 4.0], [4.0, 9.0]], np.float32)
    n = np.array([100, 50])
    qlo = np.array([[1.0, 1.0], [2.0, 2.0]], np.float32)   # both cut leaf 0
    qhi = np.array([[3.0, 6.0], [3.0, 3.0]], np.float32)   # q0 cuts leaf 1
    ops, nbytes = work.moment_pass_work(qlo, qhi, leaf_lo, leaf_hi, n)
    assert ops == (100 + 50 + 100) * (2 * 2 + 6)
    assert nbytes == (100 + 50) * 3 * 4 + 2 * 4 * 4 + 3 * 3 * 4


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    leaf_lo = np.array([[0.0]], np.float32)
    leaf_hi = np.array([[1.0]], np.float32)
    ops, nbytes = work.moment_pass_work(np.array([[0.5]], np.float32),
                                        np.array([[2.0]], np.float32),
                                        leaf_lo, leaf_hi, np.array([1000]))
    assert max(ops / 1e12, nbytes / 1e9) == pytest.approx(nbytes / 1e9)
