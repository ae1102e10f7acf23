"""The trace reduction on a hand-built trace."""
import _paths  # noqa: F401
import pytest

from bench.harness import trace as tr
from bench.harness import work

MS = 1e6


def make_trace():
    # window 0..100 ms; device busy 10-30 (moment kernel), 25-40 (overlap,
    # query_eval), 60-70 (ingest step); host spans label the gaps
    ops = [tr.Op("/device:TPU:0", "stratified_moments.1", 10 * MS, 30 * MS,
                 {"tf_op": "jit(_ci_answer_jit)/jit(stratified_moments)/"
                           "pallas_call"}),
           tr.Op("/device:TPU:0", "custom-call.3", 25 * MS, 40 * MS,
                 {"tf_op": "jit(_ci_answer_jit)/jit(query_eval)/pallas_call"}),
           tr.Op("/device:TPU:0", "fusion.1", 60 * MS, 70 * MS, {}),
           tr.Op("/device:TPU:0", "fusion.1", 120 * MS, 130 * MS, {})]
    spans = [tr.Span("bench.window", 0, 100 * MS),
             tr.Span("bench.generator.wait", 0, 9 * MS),
             tr.Span("bench.ingest", 45 * MS, 75 * MS),
             tr.Span("bench.submit", 80 * MS, 81 * MS)]
    # a serving program runs inside the ingest span beside the ingest step
    modules = [tr.Op("/device:TPU:0", "jit__ci_answer_jit", 10 * MS, 40 * MS,
                     {}),
               tr.Op("/device:TPU:0", "jit__ci_answer_jit", 46 * MS, 50 * MS,
                     {}),
               tr.Op("/device:TPU:0", "jit__ingest_step_keyed", 60 * MS,
                     70 * MS, {}),
               tr.Op("/device:TPU:0", "jit__ingest_step_keyed", 120 * MS,
                     130 * MS, {})]
    return tr.Trace(ops, spans, modules)


def test_busy_idle_and_window():
    t = make_trace()
    assert tr.window_seconds(t) == pytest.approx(0.1)
    # union: 10-40 and 60-70 inside the window; the op at 120 is outside
    assert tr.busy_seconds(t) == pytest.approx(0.040)
    assert tr.idle_gaps(t, "/device:TPU:0") == [
        (0, 10 * MS), (40 * MS, 60 * MS), (70 * MS, 100 * MS)]


def test_breakdown_ops_and_gap_labels():
    b = tr.breakdown(make_trace())
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "stratified_moments.1"
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(0.010)
    gaps = b["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    # 70-100: ingest span covers 70-75, submit 80-81 -> ingest wins
    assert gaps[0][0] == "bench.ingest"
    assert gaps[1][0] == "bench.ingest"           # 40-60: ingest 45-60
    assert gaps[2][0] == "bench.generator.wait"   # 0-10: wait 0-9


def test_gap_with_no_span_is_untraced():
    t = tr.Trace([], [tr.Span("bench.window", 0, 10)])
    assert tr.breakdown(t)["idle_gaps"] == [["untraced", 10 / 1e9]]
    assert tr.busy_seconds(t) == 0.0


def test_moment_kernel_told_apart_by_metadata():
    ops = work.moment_ops(make_trace())
    assert [o.name for o in ops] == ["stratified_moments.1"]


def test_union_merges_overlaps():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_op_names_are_shortened_to_the_instruction():
    text = ("%stratified_moments.1 = f32[3,128,1024]{2,1,0:T(8,128)S(1)} "
            "custom-call(f32[8,1048576]{1,0} %pad.12)")
    assert tr.short_name(text) == "stratified_moments.1"
    assert tr.short_name("fusion.3") == "fusion.3"


def test_nested_ops_count_their_self_time():
    ops = [tr.Op("d", "while.1", 0, 10 * MS, {}),
           tr.Op("d", "fusion.2", 1 * MS, 4 * MS, {}),
           tr.Op("d", "fusion.3", 5 * MS, 9 * MS, {}),
           tr.Op("d", "copy.4", 12 * MS, 13 * MS, {})]
    t = tr.Trace(ops, [tr.Span("bench.window", 0, 20 * MS)])
    got = dict(tr.breakdown(t)["device_ops"])
    assert got == pytest.approx({"fusion.3": 0.004, "fusion.2": 0.003,
                                 "while.1": 0.003, "copy.4": 0.001})
    assert tr.busy_seconds(t) == pytest.approx(0.011)


def test_module_names_drop_the_fingerprint():
    assert tr.module_name("jit__ingest_step_keyed(8013883179700404511)") \
        == "jit__ingest_step_keyed"


def test_ingest_step_time_leaves_out_the_serving_programs():
    """Only the ingest step's executions inside the window count, not a
    serving program that ran inside an ingest span."""
    from types import SimpleNamespace
    from bench.harness import cells
    reader = cells.load_module(
        cells.metric_reader_path(_paths.BENCH, "ingest_device_ms_per_batch"),
        "ingest_device_ms_per_batch")
    assert reader.read(SimpleNamespace(trace=make_trace())) \
        == pytest.approx(10.0)
    assert reader.read(SimpleNamespace(trace=None)) is None
