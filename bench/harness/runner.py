"""One run of one cell: set-up, the measured window, the check.

Set-up builds the deployment through the program's user entry points
(``build_synopsis``, ``PassEngine``, ``RequestCoalescer`` under a
``TickDriver``, ``StreamingIngestor``), warms up exactly the shape classes
the cell's traffic uses, and then the window drives the traffic for the
run's seconds. Requests are host float32 predicate batches, as they would
arrive from the network. After the window closes every answer due in it is
awaited, the covered-query probe goes through the same coalescer, and the
answers are compared with the benchmark's float64 reference.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time

import numpy as np

WAIT_PAST_CLOSE_S = 60.0  # an answer later than this never came
BIG = 3.0e38              # a predicate bound past every value


@dataclasses.dataclass
class Sizes:
    """The deployment's scale; tests shrink it, runs take the config's."""
    rows: int
    k: int
    samples: int
    query_pool: int = 16384


def sizes_of(config: dict) -> Sizes:
    rows = int(config["data"]["rows"])
    syn = config["synopsis"]
    return Sizes(rows=rows, k=int(syn["k"]),
                 samples=int(math.ceil(float(syn["sample_rate"]) * rows)))


@dataclasses.dataclass
class Request:
    tenant: str
    qidx: np.ndarray              # rows of the query pool
    t_due: float
    t_submit: float = 0.0
    t_done: float | None = None
    shed: bool = False
    error: str | None = None
    result: dict | None = None
    probe: bool = False
    whole: bool = False           # the whole table, not a pool query
    acked_at_submit: int = 0      # stream batches acknowledged at submit
    dispatched_at_done: int = 0   # stream batches handed to ingest by done


class Stream:
    """The ingest side of a run: batches handed to ``ingest`` and
    acknowledged (call returned and state ready)."""

    def __init__(self, pool_c, pool_a, batch_rows: int):
        self.pool_c = pool_c
        self.pool_a = pool_a
        self.batch_rows = batch_rows
        self.pool_batches = pool_a.shape[0] // batch_rows
        self.dispatched = 0
        self.acked = 0
        self.errors = 0
        self.ack_times: list[float] = []

    def batch(self, i: int):
        p = i % self.pool_batches
        sl = slice(p * self.batch_rows, (p + 1) * self.batch_rows)
        return self.pool_c[sl], self.pool_a[sl]


class GcPauses:
    """Python garbage-collector pauses while on: they stop every thread
    of the process, the program's tick thread among them."""

    def __init__(self):
        import gc
        self.on = False
        self.pauses: list[float] = []
        self._t0 = None
        gc.callbacks.append(self._hear)

    def _hear(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            if self.on:
                self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None


class CompileCounter:
    """Counts JAX's compile and trace events (cache hits included) while
    on: there should be none inside the window."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.events: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, _secs, **_kw):
        if self.on and ("compile" in name or "trace" in name):
            self.events[name] = self.events.get(name, 0) + 1


def seeds_of(seed: int) -> dict:
    """Independent 31-bit sub-seeds of the run's seed, one per use."""
    names = ("data", "build", "queries", "schedule", "stream", "ingest",
             "probe")
    words = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {n: int(w) & 0x7FFFFFFF for n, w in zip(names, words)}


class Run:
    """State of one run; the phases are its methods, in order."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 sizes: Sizes | None = None, devices=None):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.traffic
        self.seed = int(seed)
        self.seeds = seeds_of(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.sizes = sizes or sizes_of(cell.config)
        self.devices = devices
        self.kinds = tuple(self.config["kinds"])
        self.requests: list[Request] = []
        self.probes: list[Request] = []
        self.stream: Stream | None = None
        self.phase_s: dict[str, float] = {}

    # -- set-up ------------------------------------------------------------
    def make_data(self) -> None:
        from bench.data import queries, taxi
        t0 = time.perf_counter()
        d = int(self.config["data"]["dims"])
        scale = self.sizes.rows / taxi.PAPER_ROWS
        self.c, self.a = taxi.nyc_taxi(scale=scale, seed=self.seeds["data"],
                                       dims=d)
        w = self.config["query_widths"]
        self.qlo, self.qhi = queries.random_queries(
            self.c, self.sizes.query_pool, seed=self.seeds["queries"],
            min_frac=float(w["min_frac"]), max_frac=float(w["max_frac"]))
        ing = self.mix.get("ingest")
        if ing is not None:
            rows = int(ing["batch_rows"]) * int(ing["pool_batches"])
            pc, pa = taxi.stream_pool(rows, self.seeds["stream"], d)
            self.stream = Stream(pc, pa, int(ing["batch_rows"]))
        self.phase_s["data"] = time.perf_counter() - t0

    def build(self) -> None:
        from repro.api import CIConfig, CoalescerConfig, PassEngine, \
            ServingConfig
        from repro.core import build_synopsis
        from repro.serve import RequestCoalescer
        t0 = time.perf_counter()
        syn_cfg = self.config["synopsis"]
        self.syn, _ = build_synopsis(
            self.c, self.a, k=self.sizes.k, sample_budget=self.sizes.samples,
            method=syn_cfg["method"], seed=self.seeds["build"],
            allocation=syn_cfg.get("allocation", "equal"))
        if self.stream is not None:
            from repro.streaming import StreamingIngestor
            self.ingestor = StreamingIngestor(self.syn,
                                              seed=self.seeds["ingest"])
            source = self.ingestor
        else:
            source = self.syn
        self.engine = PassEngine(
            source, serving=ServingConfig(kinds=self.kinds),
            ci=CIConfig(level=float(self.mix["ci_level"])))
        self.co = RequestCoalescer(self.engine, CoalescerConfig())
        import jax
        jax.block_until_ready(self.syn)
        self.phase_s["build"] = time.perf_counter() - t0

    def _batch(self, lo, hi):
        from repro.core.types import QueryBatch
        return QueryBatch(lo, hi)

    def _tick_until_done(self, futs) -> None:
        while not all(f.done() for f in futs):
            self.co.tick()

    def warm_up(self) -> None:
        """Three rounds of every shape class the mix sends (the first call
        of a class traces, the second AOT-compiles, the third runs the
        compiled program), then, in an ingest cell, the same after each
        of the warm-up batches (the ingest step and the delta merge)."""
        t0 = time.perf_counter()
        rows_by_class: dict[int, int] = {}
        for r in self.cell.generator.shape_rows(self.mix):
            rows_by_class[self.co.config.padded_size(r)] = r
        n = self.qlo.shape[0]

        def round_():
            futs = []
            for i, (_c, r) in enumerate(sorted(rows_by_class.items())):
                idx = (np.arange(r) + 97 * i) % n
                futs.append(self.co.submit(
                    f"warmup-{i}", self._batch(self.qlo[idx], self.qhi[idx])))
            self._tick_until_done(futs)
            for f in futs:
                f.result()

        for _ in range(3):
            round_()
        if self.stream is not None:
            import jax
            for _ in range(int(self.mix["ingest"]["warmup_batches"])):
                self.ingestor.ingest(*self.stream.batch(
                    self.stream.dispatched))
                self.stream.dispatched += 1
                jax.block_until_ready(self.ingestor.state)
                self.stream.acked += 1
                round_()
        self.phase_s["warmup"] = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    def _submit(self, req: Request, lo, hi, on_done=None) -> None:
        from jax.profiler import TraceAnnotation
        from repro.serve import Overloaded
        stream = self.stream

        def done(fut, req=req):
            now = time.perf_counter()
            if stream is not None:
                req.dispatched_at_done = stream.dispatched
            exc = fut.exception()
            if exc is not None:
                req.error = repr(exc)
            else:
                req.result = fut.result()
            req.t_done = now             # last: the main thread polls it
            if on_done is not None:
                on_done(req)

        if stream is not None:
            req.acked_at_submit = stream.acked
        req.t_submit = time.perf_counter()
        with TraceAnnotation("bench.submit"):
            try:
                fut = self.co.submit(req.tenant, self._batch(lo, hi))
            except Overloaded:
                req.shed = True
                return
        fut.add_done_callback(done)

    def _next_queries(self, size: int) -> np.ndarray:
        n = self.qlo.shape[0]
        idx = self._perm[(self._cursor + np.arange(size)) % n]
        self._cursor += size
        return idx

    def _open_loop(self, t0: float) -> None:
        from jax.profiler import TraceAnnotation
        size = self.cell.generator.QUERIES_PER_REQUEST
        due = self.cell.generator.open_schedule(self.mix, self.seconds,
                                                self.seeds["schedule"])
        for i, off in enumerate(due):
            t_due = t0 + float(off)
            wait = t_due - time.perf_counter()
            if wait > 0:
                with TraceAnnotation("bench.generator.wait"):
                    time.sleep(wait)
            if self.cell.generator.whole_table(self.mix, i):
                req = Request(tenant=f"user-{i}", qidx=np.zeros(1, np.int64),
                              t_due=t_due, whole=True)
            else:
                req = Request(tenant=f"user-{i}",
                              qidx=self._next_queries(size), t_due=t_due)
            self.requests.append(req)
            self._submit(req, *self.query_bounds(req))

    def _closed_loop(self, t0: float, t_end: float) -> None:
        from jax.profiler import TraceAnnotation
        size = self.cell.generator.QUERIES_PER_REQUEST
        n_sess = self.cell.generator.closed_sessions(self.mix)
        done_q: queue.Queue = queue.Queue()
        tick_s = self.co.config.tick_ms / 1e3

        def send(s: int) -> None:
            idx = self._next_queries(size)
            req = Request(tenant=f"session-{s}", qidx=idx,
                          t_due=time.perf_counter())
            self.requests.append(req)
            self._submit(req, self.qlo[idx], self.qhi[idx],
                         on_done=lambda _r, s=s: done_q.put(s))
            if req.shed:          # a shed session retries after a tick
                time.sleep(tick_s)
                done_q.put(s)

        for s in range(n_sess):
            send(s)
        while True:
            remaining = t_end - time.perf_counter()
            if remaining <= 0:
                break
            try:
                with TraceAnnotation("bench.generator.wait"):
                    s = done_q.get(timeout=remaining)
            except queue.Empty:
                break
            send(s)

    def _ingest_loop(self, t_end: float) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        st = self.stream
        while time.perf_counter() < t_end:
            try:
                with TraceAnnotation("bench.ingest"):
                    self.ingestor.ingest(*st.batch(st.dispatched))
                    st.dispatched += 1
                    jax.block_until_ready(self.ingestor.state)
            except Exception:            # reported and counted as failed
                import traceback
                traceback.print_exc()
                st.errors += 1
                return
            st.acked += 1
            st.ack_times.append(time.perf_counter())

    def window(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        from repro.serve import TickDriver
        rng = np.random.default_rng(self.seeds["queries"])
        self._perm = rng.permutation(self.qlo.shape[0])
        self._cursor = 0
        self.compiles = CompileCounter()
        self.gc = GcPauses()
        self.driver = TickDriver(self.co).start()
        self.co_before = self.co.stats()
        self.eng_before = dict(self.engine._stats)
        if self.trace:
            import tempfile
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir)
        self.compiles.on = True
        self.gc.on = True
        ingest_thread = None
        with TraceAnnotation("bench.window"):
            self.t0 = time.perf_counter()
            self.t_end = self.t0 + self.seconds
            if self.stream is not None:
                self.stream_before = (self.stream.dispatched,
                                      self.stream.acked)
                ingest_thread = threading.Thread(
                    target=self._ingest_loop, args=(self.t_end,),
                    name="bench-ingest")
                ingest_thread.start()
            if self.mix["loop"] == "open":
                self._open_loop(self.t0)
            elif self.mix["loop"] == "closed":
                self._closed_loop(self.t0, self.t_end)
            else:
                raise ValueError(f"unknown loop {self.mix['loop']!r}")
            rest = self.t_end - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        self.t_closed = time.perf_counter()
        if ingest_thread is not None:
            ingest_thread.join()
        if self.trace:
            jax.profiler.stop_trace()
        self.compiles.on = False
        self.gc.on = False
        self._await(self.requests)
        self.co_after = self.co.stats()
        self.eng_after = dict(self.engine._stats)

    def _await(self, reqs) -> None:
        deadline = time.perf_counter() + WAIT_PAST_CLOSE_S
        while time.perf_counter() < deadline:
            if all(r.shed or r.t_done is not None for r in reqs):
                return
            time.sleep(0.01)

    # -- after the window --------------------------------------------------
    def probe(self) -> None:
        """Covered queries (no partial leaf) through the same coalescer,
        shape classes and compiled programs as the window, after it."""
        from bench.data import queries
        syn = self.engine.resolve()
        lo, hi = queries.covered_queries(
            np.asarray(syn.leaf_lo), np.asarray(syn.leaf_hi),
            int(self.config["check"]["covered_probes"]), self.seeds["probe"])
        base = self.qlo.shape[0]
        self.probe_lo, self.probe_hi = lo, hi
        for i in range(lo.shape[0]):
            idx = np.array([i])
            req = Request(tenant=f"probe-{i}", qidx=idx + base,
                          t_due=time.perf_counter(), probe=True)
            self.probes.append(req)
            self._submit(req, lo[idx], hi[idx])
        self._await(self.probes)

    def stop(self) -> None:
        self.driver.stop()

    def query_bounds(self, req: Request):
        """(lo, hi) of a request's queries (probes index past the pool)."""
        n = self.qlo.shape[0]
        if req.whole:
            d = self.qlo.shape[1]
            return (np.full((1, d), -BIG, np.float32),
                    np.full((1, d), BIG, np.float32))
        if req.probe:
            return (self.probe_lo[req.qidx - n], self.probe_hi[req.qidx - n])
        return self.qlo[req.qidx], self.qhi[req.qidx]
