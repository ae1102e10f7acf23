"""``Run.build`` takes the synopsis as the configuration states it: the
sample allocation from its ``allocation`` key, the program's default
(``equal``) where the key is absent."""
import _paths  # noqa: F401
import numpy as np

from bench.harness import cells, runner

TINY = runner.Sizes(rows=40_000, k=64, samples=8192, query_pool=256)


def _built(allocation=None):
    cell = cells.resolve(_paths.ROOT, "taxi1d.closed")
    syn = dict(cell.config["synopsis"])
    if allocation is not None:
        syn["allocation"] = allocation
    cell.config = dict(cell.config, synopsis=syn)
    run = runner.Run(cell, 2**31 + 99, 1.0, False, sizes=TINY)
    run.make_data()
    run.build()
    return run


def test_proportional_allocation_follows_the_leaves_rows():
    run = _built("proportional")
    rows = np.asarray(run.syn.n_rows, np.float64)
    k = np.asarray(run.syn.k_per_leaf, np.int64)
    assert k.sum() == TINY.samples
    assert np.all(k <= rows)
    # a floor of 4 per leaf, the rest in proportion to the leaf's rows
    big = rows >= 100
    fit = 4 + rows[big] * (k[big] - 4).sum() / rows[big].sum()
    # (whole samples, handed out over a few rounds)
    assert np.all(np.abs(k[big] - fit) <= 1 + 0.01 * fit)
    assert np.ptp(k[big]) > 10   # the 1-D leaves differ in size


def test_no_allocation_key_builds_todays_synopsis():
    import jax
    from repro.core import build_synopsis
    run = _built()
    syn_cfg = run.config["synopsis"]
    assert "allocation" not in syn_cfg
    want, _ = build_synopsis(
        run.c, run.a, k=TINY.k, sample_budget=TINY.samples,
        method=syn_cfg["method"], seed=run.seeds["build"])
    got = jax.tree_util.tree_leaves(run.syn)
    ref = jax.tree_util.tree_leaves(want)
    assert len(got) == len(ref)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(got, ref))
    k = np.asarray(run.syn.k_per_leaf)
    rows = np.asarray(run.syn.n_rows)
    assert np.array_equal(k, np.minimum(rows, TINY.samples // TINY.k))
