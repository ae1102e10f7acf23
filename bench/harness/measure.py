"""A whole run: set-up, window, probe, metrics, check, result line."""
from __future__ import annotations

import time

import numpy as np

from bench.harness import check, device, runner


class Context:
    """What a metric reader reads: the finished run, its set-up seconds,
    the reduced trace (``--trace 1`` only) and the chip's peaks."""

    def __init__(self, run, setup_s: float, trace, peaks: dict | None):
        self.run = run
        self.setup_s = setup_s
        self.trace = trace
        self.peaks = peaks

    def latencies_ms(self) -> np.ndarray:
        """Every request due in the window, from due time to answer. One
        that was shed, raised or never answered counts as answered a
        minute after the close (``runner.WAIT_PAST_CLOSE_S``): it misses
        any latency limit."""
        run = self.run
        never = run.t_closed + runner.WAIT_PAST_CLOSE_S
        return np.array([((r.t_done if r.result is not None else never)
                          - r.t_due) * 1e3 for r in run.requests])


def percentile_line(name: str, values) -> str:
    v = np.asarray(values, np.float64)
    if not v.size:
        return f"{name}: none"
    return (f"{name}: n={v.size} p50={float(np.percentile(v, 50))!r} "
            f"p95={float(np.percentile(v, 95))!r} max={float(v.max())!r}")


def measure(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float, sizes=None, log=print) -> dict:
    """Run the cell once; return the result line's object plus, under
    ``"_lines"``, the check lines for standard error."""
    run = runner.Run(cell, seed, seconds, trace, sizes=sizes,
                     devices=devices)
    run.make_data()
    run.build()
    run.warm_up()
    setup_s = time.perf_counter() - t_start
    log(f"setup: setup_s={setup_s!r} " + " ".join(
        f"{k}_s={v!r}" for k, v in run.phase_s.items()))
    run.window()
    mem = device.memory_peak_bytes(devices) if devices else None
    run.probe()
    run.stop()

    reduced = None
    peaks = device.peaks(devices[0].device_kind) if devices else None
    if trace:
        import shutil
        from bench.harness import trace as tr
        try:
            for line in tr.describe(run._trace_dir):
                log("trace " + line)
            reduced = tr.load(run._trace_dir)
        finally:
            shutil.rmtree(run._trace_dir, ignore_errors=True)

    reqs = run.requests
    shed = sum(r.shed for r in reqs)
    errors = sum(1 for r in reqs if r.error)
    never = sum(1 for r in reqs if not r.shed and r.t_done is None)
    late = [(r.t_submit - r.t_due) * 1e3 for r in reqs]
    log(percentile_line("generator lateness ms", late))
    eng = {k: run.eng_after[k] - run.eng_before[k]
           for k in ("aot_compiles", "misses", "aot_fallbacks")}
    log(f"window: requests={len(reqs)} shed={shed} errors={errors} "
        f"never_answered={never} engine_in_window={eng} "
        f"jax_compile_events_in_window={run.compiles.events}")
    gcp = run.gc.pauses
    log(f"gc pauses in window: n={len(gcp)} total_s={sum(gcp)!r} "
        f"max_s={max(gcp, default=0.0)!r}")
    ctx = Context(run, setup_s, reduced, peaks)
    if run.stream is not None:
        st = run.stream
        log(f"stream: batches_dispatched={st.dispatched} acked={st.acked} "
            f"batch_rows={st.batch_rows} pool_batches={st.pool_batches}")
        log(percentile_line("light serving latency ms", ctx.latencies_ms()))

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    numbers, lines = check.compare(run)
    for line in lines:
        log(line)
    correct = all(v <= lim for v, lim in numbers.values())
    stream_batches = stream_errors = 0
    if run.stream is not None:
        stream_batches = run.stream.dispatched - run.stream_before[0]
        stream_errors = run.stream.errors
    out = {"correct": bool(correct),
           "attempted": len(reqs) + stream_batches + stream_errors,
           "failed": shed + errors + never + stream_errors,
           "metrics": metrics,
           "device": dict(device.describe(devices) if devices else
                          {"platform": "cpu", "kind": "cpu", "count": 1},
                          memory_peak_bytes=mem)}
    if reduced is not None:
        from bench.harness import trace as tr
        out["device"]["busy_s"] = tr.busy_seconds(reduced)
        out["device"]["window_s"] = tr.window_seconds(reduced)
        out["breakdown"] = tr.breakdown(reduced)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in numbers.items()}
    return out
