"""The program's own spans in a profiler trace of the measured window, and
what they say about the host path between device dispatches.

The program marks its serving path with ``jax.profiler.TraceAnnotation``
spans named ``repro.*``: ``repro.serve.*`` in the request coalescer,
``repro.engine.*`` in ``PassEngine``, ``repro.ingest.*`` in the streaming
ingestor. They are host events on the profiler's clock, one line per
thread, beside the benchmark's ``bench.*`` spans that ``trace.load`` keeps;
this module reads them and reduces them:

* ``parts_ms``: over the ``repro.serve.dispatch`` spans that lie wholly in
  the window, the mean dispatch length and, per dispatch, the self time of
  each of its parts (mux, engine, pull, resolve) and of the dispatch
  itself;
* ``label_gaps``: the device's longest idle gaps, each named by the program
  span whose self time covers most of it (summed by name), else by the
  benchmark span ``trace.label`` names;
* ``self_seconds``: each span name's self time in the window, all threads.

A trace without program spans gives no parts and the benchmark's labels.

    python3 -m bench.harness.spans --workload taxi1d.closed --seed <n> \\
        --seconds 20

from the root of a checkout runs one cell with the profiler on, as
``bench/run.py --trace 1`` does, and prints one JSON line: the check, the
cell's per-layer metrics, the parts, the labelled gaps and the
benchmark's breakdown.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os

from bench.harness import trace

PREFIX = "repro."
DISPATCH = "repro.serve.dispatch"
# The parts of a dispatch, each the summed self time of these spans.
PARTS = {
    "mux_ms": ("repro.serve.mux",),
    "engine_ms": ("repro.engine.prepare", "repro.engine.call",
                  "repro.engine.compile"),
    "pull_ms": ("repro.serve.pull",),
    "resolve_ms": ("repro.serve.resolve",),
}


@dataclasses.dataclass
class HostSpan:
    name: str
    start_ns: float
    end_ns: float
    thread: str = ""            # the host line (thread) it ran on
    args: dict = dataclasses.field(default_factory=dict)


def load(profile_dir: str) -> list[HostSpan]:
    """The ``repro.*`` host events of the newest ``.xplane.pb`` under
    ``profile_dir``. A span opened with arguments may be named
    ``<name>#<k>=<v>,...#``; its name is what precedes the first ``#``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{line.name}"
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(HostSpan(e.name.split("#", 1)[0], e.start_ns,
                                        e.start_ns + e.duration_ns, thread,
                                        trace._stats(e)))
    return out


def _self_ns(spans: list[HostSpan], lo: float, hi: float) -> dict[str, float]:
    """Self time inside [lo, hi] summed by name: each span less the spans
    nested in it on its own thread (``trace.self_times`` with the thread in
    the device's place)."""
    ops = [trace.Op(s.thread, s.name, s.start_ns, s.end_ns, s.args)
           for s in spans]
    out: dict[str, float] = {}
    for o, d in trace.self_times(ops, lo, hi):
        if d > 0:
            out[o.name] = out.get(o.name, 0.0) + d
    return out


def self_seconds(tr: trace.Trace, spans: list[HostSpan]) -> dict[str, float]:
    """Self time of each span name inside the window, in seconds."""
    return {k: v / 1e9 for k, v in _self_ns(spans, *tr.window()).items()}


def parts_ms(tr: trace.Trace, spans: list[HostSpan]) -> dict[str, float]:
    """``dispatch_ms``: mean length of the dispatch spans wholly in the
    window; each part of ``PARTS`` and ``dispatch_self_ms``: self time of
    those spans nested in them, per dispatch. The parts and the dispatch's
    own self time add up to ``dispatch_ms``. Empty without dispatches."""
    w0, w1 = tr.window()
    by_thread: dict[str, list[HostSpan]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    inside: list[HostSpan] = []
    n, total = 0, 0.0
    for ss in by_thread.values():
        cur = None
        for s in sorted(ss, key=lambda s: (s.start_ns, -s.end_ns)):
            if s.name == DISPATCH and w0 <= s.start_ns and s.end_ns <= w1:
                cur = s
                n += 1
                total += s.end_ns - s.start_ns
                inside.append(s)
            elif (cur is not None and cur.start_ns <= s.start_ns
                  and s.end_ns <= cur.end_ns):
                inside.append(s)
    if n == 0:
        return {}
    self_ns = _self_ns(inside, -math.inf, math.inf)
    out = {"dispatch_ms": total / n / 1e6}
    for part, names in PARTS.items():
        out[part] = sum(self_ns.get(x, 0.0) for x in names) / n / 1e6
    out["dispatch_self_ms"] = self_ns.get(DISPATCH, 0.0) / n / 1e6
    return out


def label(gap: tuple[float, float], spans: list[HostSpan],
          bench_spans: list[trace.Span]) -> tuple[str, float]:
    """The program span name whose self time covers most of the gap, with
    the nanoseconds it covers; else ``trace.label`` of the benchmark's
    spans, with 0."""
    near = [s for s in spans if s.end_ns > gap[0] and s.start_ns < gap[1]]
    by_name = _self_ns(near, *gap)
    if not by_name:
        return trace.label(gap, bench_spans), 0.0
    name = max(by_name, key=by_name.get)
    return name, by_name[name]


def label_gaps(tr: trace.Trace, spans: list[HostSpan], n: int = 10
               ) -> list[list]:
    """The ``n`` longest idle gaps of the first device, as
    ``trace.breakdown`` picks them: ``[label, gap seconds, seconds the
    label's self time covers]``."""
    w0, w1 = tr.window()
    devs = tr.devices()
    gaps = trace.idle_gaps(tr, devs[0]) if devs else [(w0, w1)]
    out = []
    for g in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        name, covered = label(g, spans, tr.spans)
        out.append([name, (g[1] - g[0]) / 1e9, covered / 1e9])
    return out


def run_cell(cell, seed: int, seconds: float, devices, t_start: float,
             sizes=None) -> dict:
    """One traced run of ``cell`` through the benchmark's own phases (as
    ``measure.measure`` makes them), reduced to the check, the per-layer
    metrics, the parts of a dispatch, the labelled gaps and the self time
    of each span name in the window."""
    import shutil
    import time
    from bench.harness import check, device, runner
    from bench.harness.measure import Context
    run = runner.Run(cell, seed, seconds, True, sizes=sizes, devices=devices)
    run.make_data()
    run.build()
    run.warm_up()
    setup_s = time.perf_counter() - t_start
    run.window()
    run.probe()
    run.stop()
    try:
        tr = trace.load(run._trace_dir)
        spans = load(run._trace_dir)
    finally:
        shutil.rmtree(run._trace_dir, ignore_errors=True)
    ctx = Context(run, setup_s, tr,
                  device.peaks(devices[0].device_kind) if devices else None)
    numbers, _ = check.compare(run)
    metrics = {m["name"]: cell.readers[m["name"]].read(ctx)
               for m in cell.per_layer}
    return {"correct": all(v <= lim for v, lim in numbers.values()),
            "requests": len(run.requests), "setup_s": setup_s,
            "metrics": metrics, "parts_ms": parts_ms(tr, spans),
            "idle_gaps": label_gaps(tr, spans),
            "self_s": self_seconds(tr, spans),
            "breakdown": trace.breakdown(tr),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}}


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    import time
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(root, "src"))
    from bench.harness import cells, device
    device.use_checkout_cache(root)
    from repro.compile_cache import enable_compile_cache
    cell = cells.resolve(root, args.workload)
    enable_compile_cache()
    devices = device.require_chips(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, devices, t_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
