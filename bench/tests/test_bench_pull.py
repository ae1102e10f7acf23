"""The reader of ``pull_transfers.closed``: transfers per dispatch from the
coalescer's counters, and no reading from a program without them."""
from types import SimpleNamespace

import pytest

import _paths
from bench.harness import cells


def _reader():
    return cells.load_module(
        cells.metric_reader_path(_paths.BENCH, "pull_transfers.closed"),
        "pull_transfers")


def test_pull_transfers_reads_transfers_per_dispatch():
    run = SimpleNamespace(
        co_before={"pull_transfers": 21, "dispatches": 1},
        co_after={"pull_transfers": 61, "dispatches": 41})
    assert _reader().read(SimpleNamespace(run=run)) == pytest.approx(1.0)


def test_pull_transfers_reads_nothing_without_the_counter_or_dispatches():
    reader = _reader()
    old = SimpleNamespace(co_before={"dispatches": 0},
                          co_after={"dispatches": 5})
    assert reader.read(SimpleNamespace(run=old)) is None
    idle = SimpleNamespace(co_before={"pull_transfers": 3, "dispatches": 3},
                           co_after={"pull_transfers": 3, "dispatches": 3})
    assert reader.read(SimpleNamespace(run=idle)) is None
