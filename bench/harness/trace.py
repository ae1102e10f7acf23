"""Reduce a profiler trace of the measured window to what the per-layer
metrics read: device busy time, per-op device time, idle gaps, and the
benchmark's own host spans.

The profiler writes an ``.xplane.pb``. Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
that ran on the device, their ``XLA Modules`` line one per execution of a
compiled program. Host threads are lines of ``/host:CPU``; the
benchmark's own spans (``jax.profiler.TraceAnnotation``) are the events
named ``bench.*``. The window is the span ``bench.window``. Everything
below works on plain tuples, so tests can hand it a trace built by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    device: str
    name: str
    start_ns: float
    end_ns: float
    meta: dict


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: list[Op]
    spans: list[Span]
    modules: list[Op] = dataclasses.field(default_factory=list)

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0].start_ns, w[0].end_ns

    def devices(self) -> list[str]:
        return sorted({o.device for o in self.ops})


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping cover of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def busy(trace: Trace, device: str | None = None, lo=None, hi=None
         ) -> list[tuple[float, float]]:
    """Union of the device's op intervals inside [lo, hi] (the window by
    default)."""
    w0, w1 = trace.window()
    lo = w0 if lo is None else lo
    hi = w1 if hi is None else hi
    return clip(union((o.start_ns, o.end_ns) for o in trace.ops
                      if device is None or o.device == device), lo, hi)


def busy_seconds(trace: Trace) -> float:
    """Device busy seconds in the window, averaged over the devices that
    ran anything."""
    devs = trace.devices()
    if not devs:
        return 0.0
    return sum(total(busy(trace, d)) for d in devs) / len(devs) / 1e9


def window_seconds(trace: Trace) -> float:
    w0, w1 = trace.window()
    return (w1 - w0) / 1e9


def idle_gaps(trace: Trace, device: str) -> list[tuple[float, float]]:
    w0, w1 = trace.window()
    gaps, t = [], w0
    for s, e in busy(trace, device):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


def label(gap: tuple[float, float], spans: list[Span]) -> str:
    """The benchmark span that covers most of the gap, else ``untraced``."""
    best, best_ns = "untraced", 0.0
    for s in spans:
        if s.name == WINDOW_SPAN:
            continue
        ov = min(gap[1], s.end_ns) - max(gap[0], s.start_ns)
        if ov > best_ns:
            best, best_ns = s.name, ov
    return best


def self_times(ops: list[Op], lo: float, hi: float) -> list[tuple[Op, float]]:
    """Each op's time inside [lo, hi] less the time of the ops nested in
    it (a ``while`` op holds the events of its body on the same line)."""
    out = []
    for dev in sorted({o.device for o in ops}):
        stack: list[list] = []          # [op, end, self time]
        for o in sorted((o for o in ops if o.device == dev),
                        key=lambda o: (o.start_ns, -o.end_ns)):
            while stack and stack[-1][1] <= o.start_ns:
                out.append((stack[-1][0], stack.pop()[2]))
            d = max(0.0, min(o.end_ns, hi) - max(o.start_ns, lo))
            if stack and o.end_ns <= stack[-1][1]:
                stack[-1][2] -= d
            stack.append([o, o.end_ns, d])
        out.extend((e[0], e[2]) for e in stack)
    return out


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The ``n`` device ops that took most time in the window (self time,
    summed by name over devices) and the ``n`` longest idle gaps of the
    first device, each labelled by the benchmark span that covers most of
    it."""
    w0, w1 = trace.window()
    by_name: dict[str, float] = {}
    for o, d in self_times(trace.ops, w0, w1):
        if d > 0:
            by_name[o.name] = by_name.get(o.name, 0.0) + d
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    devs = trace.devices()
    gaps = idle_gaps(trace, devs[0]) if devs else [(w0, w1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[label(g, trace.spans), (g[1] - g[0]) / 1e9]
                          for g in gaps]}


def short_name(hlo_text: str) -> str:
    """An op event on the TPU is named by its whole HLO instruction
    (``%stratified_moments.1 = f32[3,128,1024]... custom-call(...)``);
    keep the instruction's name (``stratified_moments.1``)."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """A program's event on the ``XLA Modules`` line is named by its module
    and a fingerprint (``jit__ingest_step_keyed(8013883179700404511)``);
    keep the module's name."""
    return event_name.split("(", 1)[0]


def _stats(event) -> dict:
    out = {}
    for kv in event.stats:
        try:
            k, v = kv
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def load(profile_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, spans, modules = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append(Op(plane.name, short_name(e.name),
                                      e.start_ns, e.start_ns + e.duration_ns,
                                      _stats(e)))
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        modules.append(Op(plane.name, module_name(e.name),
                                          e.start_ns,
                                          e.start_ns + e.duration_ns, {}))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Trace(ops, spans, modules)


def describe(profile_dir: str, limit: int = 40) -> list[str]:
    """A few lines on the trace's planes, lines and first events, to look
    at a trace by hand."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name}: lines {[l.name for l in lines]}")
        for line in lines:
            for e in list(line.events)[:3]:
                out.append(f"  {line.name} | {e.name} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={_stats(e)}"[:600])
            if len(out) > limit:
                return out
    return out
