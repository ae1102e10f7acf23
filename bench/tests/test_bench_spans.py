"""The program's spans (``repro.*``) in a trace: parts of a dispatch and
idle-gap labels on a hand-built trace, the queue-wait reader, and one
traced run of the committed cell on the CPU at a size a test can hold."""
import time
from types import SimpleNamespace

import _paths
import pytest

from bench.harness import cells, runner, spans
from bench.harness import trace as tr

MS = 1e6
TICK = "tick-thread"
# the size of test_bench_faults.py
TINY = runner.Sizes(rows=40_000, k=64, samples=8192, query_pool=2048)


def make_trace():
    # window 0..100 ms; device busy 0-10 and 50-55: gaps 10-50 and 55-100
    ops = [tr.Op("/device:TPU:0", "fusion.1", 0, 10 * MS, {}),
           tr.Op("/device:TPU:0", "fusion.2", 50 * MS, 55 * MS, {})]
    bench = [tr.Span("bench.window", 0, 100 * MS),
             tr.Span("bench.generator.wait", 8 * MS, 100 * MS)]
    return tr.Trace(ops, bench)


def make_spans():
    return [
        # straddles the window's start: not a dispatch of the window
        spans.HostSpan("repro.serve.dispatch", -5 * MS, 5 * MS, TICK),
        spans.HostSpan("repro.serve.pull", -4 * MS, 4 * MS, TICK),
        spans.HostSpan("repro.serve.tick", 12 * MS, 48 * MS, TICK),
        spans.HostSpan("repro.serve.dispatch", 14 * MS, 46 * MS, TICK,
                       {"rows": 8, "padded": 8}),
        spans.HostSpan("repro.serve.mux", 15 * MS, 18 * MS, TICK),
        spans.HostSpan("repro.serve.pull", 20 * MS, 40 * MS, TICK),
        # another thread: never nested in the tick thread's spans
        spans.HostSpan("repro.serve.submit", 30 * MS, 31 * MS, "main"),
    ]


def test_parts_of_the_dispatches_wholly_in_the_window():
    got = spans.parts_ms(make_trace(), make_spans())
    assert got == pytest.approx({"dispatch_ms": 32.0, "mux_ms": 3.0,
                                 "engine_ms": 0.0, "pull_ms": 20.0,
                                 "resolve_ms": 0.0, "dispatch_self_ms": 9.0})
    assert spans.parts_ms(make_trace(), []) == {}


def test_self_seconds_clip_to_the_window():
    """The dispatch that straddles the window's start keeps 1 ms of self
    time inside it (0-5 less its pull's 0-4)."""
    got = spans.self_seconds(make_trace(), make_spans())
    assert got == pytest.approx({
        "repro.serve.dispatch": 0.010, "repro.serve.pull": 0.024,
        "repro.serve.tick": 0.004, "repro.serve.mux": 0.003,
        "repro.serve.submit": 0.001})


def test_gap_is_labelled_by_the_child_not_its_parent():
    """10-50: pull's self time covers 20 ms of it, the dispatch's 9, the
    tick's 4, mux 3, submit 1; 55-100 no program span touches, so it keeps
    the benchmark's label."""
    got = spans.label_gaps(make_trace(), make_spans())
    assert got == [["bench.generator.wait", pytest.approx(0.045), 0.0],
                   ["repro.serve.pull", pytest.approx(0.040),
                    pytest.approx(0.020)]]


def test_without_program_spans_the_labels_are_the_breakdowns():
    t = make_trace()
    assert [g[:2] for g in spans.label_gaps(t, [])] \
        == tr.breakdown(t)["idle_gaps"]


def _queue_wait_reader():
    return cells.load_module(
        cells.metric_reader_path(_paths.BENCH, "queue_wait_ms.closed"),
        "queue_wait_ms")


def test_queue_wait_reads_the_counters_and_nothing_without_them():
    reader = _queue_wait_reader()
    run = SimpleNamespace(
        co_before={"queue_wait_ns": 1_000_000, "queue_waits": 2},
        co_after={"queue_wait_ns": 31_000_000, "queue_waits": 12})
    assert reader.read(SimpleNamespace(run=run)) == pytest.approx(3.0)
    old = SimpleNamespace(co_before={"served": 0}, co_after={"served": 5})
    assert reader.read(SimpleNamespace(run=old)) is None


def test_traced_cpu_run_splits_every_dispatch():
    """One traced run of ``taxi1d.closed``: the program's spans load apart
    from the benchmark's, every part of a dispatch reads a positive time,
    the parts fit inside the dispatch, and the queue wait reads."""
    cell = cells.resolve(_paths.ROOT, "taxi1d.closed", bench_dir=_paths.BENCH)
    out = spans.run_cell(cell, 4294967311, 1.0, None, time.perf_counter(),
                         sizes=TINY)
    assert out["correct"], out["checks"]
    parts = out["parts_ms"]
    assert set(parts) == {"dispatch_ms", "dispatch_self_ms",
                          *spans.PARTS}, parts
    assert all(parts[p] > 0 for p in spans.PARTS), parts
    assert sum(parts[p] for p in spans.PARTS) \
        <= parts["dispatch_ms"] * (1 + 1e-9)
    assert out["metrics"]["queue_wait_ms.closed"] > 0
    assert all(g[0].startswith(("repro.", "bench.")) or g[0] == "untraced"
               for g in out["idle_gaps"])


def test_program_spans_load_apart_from_the_benchmarks(tmp_path):
    """A profiled ingest and two coalesced ticks (the second call of a
    shape AOT-compiles): every program span arrives under its bare name,
    the dispatch with its arguments, and none among the benchmark's."""
    import jax
    import numpy as np
    from repro.api import PassEngine
    from repro.core import build_synopsis, random_queries
    from repro.serve import RequestCoalescer
    from repro.streaming import StreamingIngestor
    rng = np.random.default_rng(0)
    c = np.sort(rng.uniform(0, 100, 3000))
    a = rng.lognormal(0, 1, 3000)
    syn, _ = build_synopsis(c, a, k=4, sample_rate=0.05, method="eq", seed=0)
    ing = StreamingIngestor(syn, seed=0)
    co = RequestCoalescer(PassEngine(ing))
    jax.profiler.start_trace(str(tmp_path))
    ing.ingest(c[:256], a[:256])
    for i in range(2):
        co.submit("t", random_queries(c, 3, seed=i))
        co.tick()
    jax.profiler.stop_trace()
    got = spans.load(str(tmp_path))
    assert {s.name for s in got} == {
        "repro.ingest.batch", "repro.ingest.merge", "repro.serve.submit",
        "repro.serve.tick", "repro.serve.dispatch", "repro.serve.mux",
        "repro.engine.prepare", "repro.engine.call", "repro.engine.compile",
        "repro.serve.pull", "repro.serve.resolve"}
    d = [s for s in got if s.name == "repro.serve.dispatch"]
    assert [(s.args["dispatch"], s.args["rows"], s.args["padded"])
            for s in d] == [(0, 3, 8), (1, 3, 8)]
    assert not [s for s in tr.load(str(tmp_path)).spans
                if s.name.startswith("repro.")]
