"""Async multi-tenant request coalescer over :class:`PassEngine`
(DESIGN.md §12).

Production PASS traffic is many concurrent tenants issuing small ragged
query batches; per-call dispatch dominates there (the
``serving_prepared_speedup_x`` bench measures ~5x when it does). The
coalescer turns that workload back into the shape the prepared-query
layer is fastest at:

1. **Shape classes** — an incoming request is assigned the smallest
   padded batch size from ``CoalescerConfig.shape_classes`` that holds
   its rows, and bucketed by ``(padded_B, ServingConfig, CIConfig)``.
   Each bucket reuses ONE prepared AOT executable from the engine's plan
   cache (PR 4), so the executable set stays bounded no matter how
   ragged the tenants are.
2. **Cross-tenant batching** — at each tick, every bucket's queued
   requests are concatenated into padded batches and served in a single
   device dispatch per batch. Device-resident requests are muxed by a
   small jitted concat+pad executable cached per row-size composition
   (eager per-tenant ``jnp.concatenate`` or a numpy round-trip both cost
   more than the dispatch being saved); host-side batches fall back to a
   numpy mux with one padded upload. Pad rows are empty predicates
   (``lo=+BIG > hi=-BIG`` — the query-side analogue of the
   ``leaf_id=-1`` padding convention): they match no stratum, cost one
   masked lane, and never perturb real rows (every per-query artifact is
   row-independent; bit-identity is asserted in tests and in the
   ``bench_coalescer`` gate).
3. **Demux** — a dispatch's whole result (every kind's
   :class:`QueryResult`) reaches the host in ONE device-to-host transfer:
   a small jitted pack executable, cached per layout and run after the
   engine's program, concatenates the present fields of each dtype into
   one device array, which is copied once. Each synchronizing copy costs
   about 0.5 ms on a v5e whatever its size, far more than a dispatch's
   device work, so the number of copies sets the pull's cost;
   ``stats()["pull_transfers"]`` counts them. Every field is a zero-copy
   view of the host buffer, sliced into per-request row ranges as
   zero-copy numpy views and delivered through per-request
   :class:`concurrent.futures.Future`\\ s. Host-side demux matters: a
   lazy per-request ``jax`` slice costs one eager dispatch per field per
   request (~85x slower than the numpy views at 8 tenants x 3 kinds),
   which would eat the entire coalescing win.

Admission control sheds load *at submit time*: a tenant past its
``max_outstanding`` budget, or any submission past the global
``max_queue_depth``, raises the typed :class:`Overloaded` error instead
of growing an unbounded queue. Per-tenant accounting (requests, queries
served, shed counts), the coalescer-wide queue wait (``queue_wait_ns``
over ``queue_waits`` requests) and dispatch amortization are surfaced
through ``coalescer.stats()`` — and through ``engine.stats()["coalescer"]``,
since constructing a coalescer attaches it to its engine.

The tick thread's work carries profiler spans
(``jax.profiler.TraceAnnotation``, the ``SPAN_*`` names below), which join
the device ops on the profiler trace's clock: per tick, per dispatch, and
inside a dispatch its mux, pull and resolve (the engine's prepare, call
and compile spans nest between them). With no profiler running a span
costs about a microsecond.

Streaming epoch invalidation: an ingest epoch bump must drain in-flight
buckets before the prepared entries re-pin onto the fresh delta merge.
The synchronous demux makes the drain structural — every dispatched
bucket is fully materialized on host before ``tick()`` returns, so a
bucket launched against epoch N can never observe epoch N+1 state — and
the tick that first serves the new epoch records one ``epoch_drains``
so the transition is observable in ``stats()``.

The tick is driven either by :class:`repro.serve.TickDriver` (a
pure-Python event-loop thread, ``tick_ms`` cadence) or manually via
``tick()`` / ``flush()`` — the deterministic synchronous mode the tests
and the bench use.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation, annotate_function

from ..api.config import ServingConfig, CIConfig, CoalescerConfig
from ..api.engine import PassEngine, _UNSET
from ..core.types import QueryBatch, QueryResult

# Empty-predicate pad rows: lo > hi matches no row and no stratum. Finite
# (not inf) so distance arithmetic in every backend stays NaN-free.
PAD_LO, PAD_HI = 3.0e38, -3.0e38

# Profiler span names; the benchmark's trace reduction reads them by name.
SPAN_SUBMIT = "repro.serve.submit"
SPAN_TICK = "repro.serve.tick"
SPAN_DISPATCH = "repro.serve.dispatch"
SPAN_MUX = "repro.serve.mux"
SPAN_PULL = "repro.serve.pull"
SPAN_RESOLVE = "repro.serve.resolve"


class Overloaded(RuntimeError):
    """Typed admission-control rejection: the request was shed, not queued.

    ``reason`` is ``"tenant_outstanding"`` (the tenant's own budget) or
    ``"queue_depth"`` (global shed threshold); ``limit`` is the budget
    that tripped. Back off and resubmit.
    """

    def __init__(self, tenant, reason: str, limit: int):
        super().__init__(
            f"request from tenant {tenant!r} shed ({reason}, limit={limit})")
        self.tenant = tenant
        self.reason = reason
        self.limit = limit


@dataclasses.dataclass
class _Pending:
    """One queued tenant request (host-side bookkeeping only). ``dups``
    collects same-tick requests with bit-identical (predicate, config)
    payloads — they ride this request's dispatch and demux from its row
    range instead of buying lanes of their own."""
    tenant: object
    queries: QueryBatch
    serving: ServingConfig
    ci: CIConfig | None
    future: Future
    t_submit: float
    rows: int
    join: bool = False
    t_deadline: float | None = None   # absolute perf_counter deadline
    dups: list = dataclasses.field(default_factory=list)


class _TenantAccount:
    """Per-tenant serving telemetry."""

    def __init__(self):
        self.requests = 0
        self.queries = 0
        self.shed = 0
        self.outstanding = 0

    def snapshot(self) -> dict:
        return {"requests": self.requests, "queries": self.queries,
                "shed": self.shed, "outstanding": self.outstanding}


_QR_FIELDS = tuple(f.name for f in dataclasses.fields(QueryResult))


# Jitted pack executables, keyed by one dtype group's field shapes (bounded
# LRU like the mux cache: a result's layout follows its shape class and
# config, a handful in steady state).
_PACK_CACHE: OrderedDict[tuple, object] = OrderedDict()
_PACK_LOCK = threading.Lock()
# Device-to-host transfers issued by this thread's latest pull; _dispatch
# reads it into stats()["pull_transfers"].
_PULLED = threading.local()


def _pack(key: tuple):
    """The jitted executable that flattens and concatenates one dtype
    group's fields into a single 1-D array (an exact copy)."""
    with _PACK_LOCK:
        fn = _PACK_CACHE.get(key)
        if fn is None:
            fn = _PACK_CACHE[key] = jax.jit(
                lambda arrs: jnp.concatenate([jnp.ravel(a) for a in arrs]))
            if len(_PACK_CACHE) > 256:
                _PACK_CACHE.popitem(last=False)
        else:
            _PACK_CACHE.move_to_end(key)
    return fn


@functools.partial(annotate_function, name=SPAN_PULL)
def _pull_host(results: dict[str, QueryResult]) -> dict[str, list]:
    """Pull the whole batch result to the host, flattened to ``{kind:
    [field arrays in _QR_FIELDS order, None where absent]}``. The present
    device fields of each dtype are packed on the device into one array
    and copied to the host in ONE transfer (each synchronizing copy costs
    about as much as the dispatch's device work); every field is then a
    zero-copy view of that host buffer. Fields already on the host pass
    through."""
    host = {kind: [None] * len(_QR_FIELDS) for kind in results}
    groups: dict[object, list] = {}
    for kind, r in results.items():
        for i, name in enumerate(_QR_FIELDS):
            v = getattr(r, name)
            if isinstance(v, jax.Array):
                groups.setdefault(v.dtype, []).append((kind, i, v))
            elif v is not None:
                host[kind][i] = np.asarray(v)
    for dtype, members in groups.items():
        arrs = [v for _, _, v in members]
        flat = np.asarray(_pack((dtype, tuple(a.shape for a in arrs)))(arrs))
        off = 0
        for kind, i, v in members:
            host[kind][i] = flat[off:off + v.size].reshape(v.shape)
            off += v.size
    _PULLED.transfers = len(groups)
    return host


def _slice_results(host: dict[str, list], off: int, rows: int
                   ) -> dict[str, QueryResult]:
    """Demux one request's row range out of a pulled batch result
    (zero-copy numpy views — see the module doc on why not jax slices)."""
    end = off + rows
    return {kind: QueryResult(*[None if a is None else a[off:end]
                                for a in arrs])
            for kind, arrs in host.items()}


class RequestCoalescer:
    """Multi-tenant front door over one :class:`PassEngine` (module doc)."""

    def __init__(self, engine: PassEngine,
                 config: CoalescerConfig | None = None):
        self.engine = engine
        self.config = (config or CoalescerConfig()).validate()
        self._lock = threading.Lock()
        self._queue: list[_Pending] = []
        self._tenants: dict[object, _TenantAccount] = {}
        self._stats = {"submitted": 0, "served": 0, "shed": 0,
                       "dispatches": 0, "ticks": 0, "coalesced_rows": 0,
                       "padded_rows": 0, "epoch_drains": 0, "dedup_hits": 0,
                       "degraded_served": 0, "failed": 0,
                       "driver_errors": 0, "last_driver_error": None,
                       "queue_wait_ns": 0, "queue_waits": 0,
                       "pull_transfers": 0}
        # EWMA of device dispatch latency — the deadline router compares
        # a request's remaining budget against this prediction.
        self._dispatch_ewma_ms = 0.0
        self._epoch = engine.epoch
        self._generation = engine._generation
        # The synchronous demux completes every dispatch before tick()
        # returns; this flag only makes the epoch-transition drain
        # observable in stats().
        self._dispatched_since_drain = False
        # Jitted concat+pad mux executables, keyed by the row-size
        # composition of the group (bounded LRU: steady-state traffic
        # repeats a handful of compositions).
        self._mux_cache: OrderedDict[tuple, object] = OrderedDict()
        engine._coalescer = self

    # -- submission --------------------------------------------------------
    def _account(self, tenant) -> _TenantAccount:
        acct = self._tenants.get(tenant)
        if acct is None:
            acct = self._tenants[tenant] = _TenantAccount()
        return acct

    @functools.partial(annotate_function, name=SPAN_SUBMIT)
    def submit(self, tenant, queries: QueryBatch, *, kinds=None, ci=_UNSET,
               serving: ServingConfig | None = None,
               join: bool = False,
               deadline_ms: float | None = None) -> Future:
        """Queue one tenant request; returns a Future resolving to the
        same ``{kind: QueryResult}`` dict ``engine.answer`` would return
        (bit-identically — see tests). ``kinds=``/``ci=``/``serving=``
        override the engine configs per request, exactly like
        ``engine.answer``; requests only share a device dispatch with
        requests of the same effective config. ``join=True`` routes the
        request through ``engine.answer_join`` semantics (``queries`` in
        any layout ``answer_join`` accepts; join requests bucket apart
        from single-table ones). Raises :class:`Overloaded` when
        admission control sheds the request.

        ``deadline_ms`` opts the request into degraded serving instead of
        shedding: a submission admission control would reject, or a tick
        that predicts the device dispatch would blow the remaining budget,
        serves the tier-0 aggregates-only answer (hard-bound envelope,
        zero sample work) immediately rather than raising
        :class:`Overloaded` or missing the deadline. Single-table
        requests only — tier-0 has no join analogue.
        """
        if join:
            if deadline_ms is not None:
                raise ValueError(
                    "deadline_ms applies to single-table requests only "
                    "(tier-0 degraded serving has no join analogue)")
            sv, cfg = self.engine._effective_join(kinds, ci, serving)
            queries = self.engine._as_join_batch(queries)
        else:
            sv, cfg = self.engine._effective(kinds, ci, serving)
        if deadline_ms is not None and deadline_ms < 0:
            raise ValueError(f"deadline_ms must be >= 0, got {deadline_ms}")
        if queries.lo.ndim != 2 or queries.lo.shape[0] < 1:
            raise ValueError(
                f"expected a non-empty (q, d) batch, got {queries.lo.shape}")
        now = time.perf_counter()
        pend = _Pending(tenant=tenant, queries=queries, serving=sv, ci=cfg,
                        future=Future(), t_submit=now,
                        rows=int(queries.lo.shape[0]), join=join,
                        t_deadline=(None if deadline_ms is None
                                    else now + deadline_ms / 1e3))
        with self._lock:
            acct = self._account(tenant)
            shed_reason = None
            if len(self._queue) >= self.config.max_queue_depth:
                shed_reason = ("queue_depth", self.config.max_queue_depth)
            elif acct.outstanding >= self.config.max_outstanding:
                shed_reason = ("tenant_outstanding",
                               self.config.max_outstanding)
            if shed_reason is not None and pend.t_deadline is None:
                acct.shed += 1
                self._stats["shed"] += 1
                raise Overloaded(tenant, *shed_reason)
            acct.requests += 1
            self._stats["submitted"] += 1
            if shed_reason is None:
                acct.outstanding += 1
                self._queue.append(pend)
        if shed_reason is not None:
            # Deadline-aware overload: the request that would have been
            # shed gets the degraded tier inline (no queue slot consumed).
            self._serve_tier0(pend, count_outstanding=False)
        return pend.future

    def answer(self, tenant, queries: QueryBatch, *, timeout=None,
               **overrides) -> dict[str, QueryResult]:
        """Blocking convenience: ``submit(...).result()`` (background
        driver mode — in synchronous mode call ``tick()`` yourself)."""
        return self.submit(tenant, queries, **overrides).result(timeout)

    # -- epoch drain -------------------------------------------------------
    def _drain_on_epoch_bump(self) -> None:
        """Re-pin bookkeeping on a source epoch bump (ingest or
        replace_source). In-flight buckets are already fully drained —
        demux materializes every dispatch on host before tick() returns,
        so work launched against epoch N can never straddle into N+1 —
        which leaves only the observable transition count to record."""
        eng = self.engine
        if (eng.epoch == self._epoch
                and eng._generation == self._generation):
            return
        if self._dispatched_since_drain:
            self._stats["epoch_drains"] += 1
        self._dispatched_since_drain = False
        self._epoch = eng.epoch
        self._generation = eng._generation

    # -- dispatch ----------------------------------------------------------
    @functools.partial(annotate_function, name=SPAN_MUX)
    def _mux(self, group: list[_Pending], padded_b: int, d: int
             ) -> QueryBatch:
        """Build the padded cross-tenant batch. Device-resident requests
        go through one jitted concat+pad executable cached per row-size
        composition; anything else takes the numpy path with one padded
        upload per operand."""
        if all(isinstance(p.queries.lo, jax.Array)
               and isinstance(p.queries.hi, jax.Array) for p in group):
            key = (tuple(p.rows for p in group), padded_b, d)
            mux = self._mux_cache.get(key)
            if mux is None:
                pad = padded_b - sum(key[0])

                def _concat_pad(parts_lo, parts_hi, _pad=pad, _d=d):
                    pads_lo = ([jnp.full((_pad, _d), PAD_LO, jnp.float32)]
                               if _pad else [])
                    pads_hi = ([jnp.full((_pad, _d), PAD_HI, jnp.float32)]
                               if _pad else [])
                    return (jnp.concatenate(list(parts_lo) + pads_lo),
                            jnp.concatenate(list(parts_hi) + pads_hi))

                mux = self._mux_cache[key] = jax.jit(_concat_pad)
                if len(self._mux_cache) > 256:
                    self._mux_cache.popitem(last=False)
            else:
                self._mux_cache.move_to_end(key)
            lo, hi = mux([p.queries.lo for p in group],
                         [p.queries.hi for p in group])
            return QueryBatch(lo, hi)
        lo = np.full((padded_b, d), PAD_LO, np.float32)
        hi = np.full((padded_b, d), PAD_HI, np.float32)
        off = 0
        for p in group:
            lo[off:off + p.rows] = np.asarray(p.queries.lo, np.float32)
            hi[off:off + p.rows] = np.asarray(p.queries.hi, np.float32)
            off += p.rows
        return QueryBatch(jnp.asarray(lo), jnp.asarray(hi))

    def _serve_tier0(self, p: _Pending, count_outstanding: bool = True
                     ) -> None:
        """Resolve one request with the tier-0 aggregates-only answer
        (deadline-degraded path: planner hard bounds, zero sample work,
        no device dispatch)."""
        from .refine import tier0_answer
        try:
            res = tier0_answer(self.engine, p.queries, p.serving.kinds)
        except Exception as exc:
            p.future.set_exception(exc)
            res = None
        with self._lock:
            acct = self._account(p.tenant)
            if count_outstanding:
                acct.outstanding -= 1
            if res is not None:
                acct.queries += p.rows
                self._stats["served"] += 1
                self._stats["degraded_served"] += 1
            else:
                self._stats["failed"] += 1
        if res is not None:
            self.engine._stats["degraded_serves"] += 1
            p.future.set_result(res)

    def _dispatch(self, group: list[_Pending], padded_b: int,
                  serving: ServingConfig, ci: CIConfig | None) -> None:
        """Serve one padded batch (one device dispatch) and demux. Every
        request riding it, dedup riders included, adds its wait since
        submit to the queue-wait counters."""
        t0 = time.perf_counter()
        d = int(group[0].queries.lo.shape[1])
        rows = sum(p.rows for p in group)
        pad = padded_b - rows
        everyone = [q for p in group for q in (p, *p.dups)]
        with self._lock:
            seq = self._stats["dispatches"]
            self._stats["queue_waits"] += len(everyone)
            self._stats["queue_wait_ns"] += sum(
                int((t0 - p.t_submit) * 1e9) for p in everyone)
        with TraceAnnotation(SPAN_DISPATCH, dispatch=seq, rows=rows,
                             padded=padded_b):
            try:
                if group[0].join:
                    prepared = self.engine.prepare_join(
                        (padded_b, d), serving=serving, ci=ci)
                else:
                    prepared = self.engine.prepare((padded_b, d),
                                                   serving=serving, ci=ci)
                results = prepared(self._mux(group, padded_b, d))
                # One synchronizing transfer per dtype of the result; the
                # per-request demux below is zero-copy numpy views.
                _PULLED.transfers = 0
                host = _pull_host(results)
                transfers = _PULLED.transfers
            except Exception as exc:              # deliver, don't swallow
                with TraceAnnotation(SPAN_RESOLVE):
                    for p in everyone:
                        p.future.set_exception(exc)
                    self._finish(everyone, served=False)
                return
            dt_ms = (time.perf_counter() - t0) * 1e3
            with TraceAnnotation(SPAN_RESOLVE):
                with self._lock:
                    self._dispatched_since_drain = True
                    self._stats["dispatches"] += 1
                    self._stats["pull_transfers"] += transfers
                    self._stats["coalesced_rows"] += rows
                    self._stats["padded_rows"] += pad
                    self._dispatch_ewma_ms = (
                        dt_ms if self._dispatch_ewma_ms == 0.0
                        else 0.7 * self._dispatch_ewma_ms + 0.3 * dt_ms)
                off = 0
                for p in group:
                    p.future.set_result(_slice_results(host, off, p.rows))
                    # Deduped duplicates demux the same row range — each
                    # gets its own fresh view dict, so tenants never share
                    # result objects.
                    for q in p.dups:
                        q.future.set_result(_slice_results(host, off, q.rows))
                    off += p.rows
                self._finish(everyone, served=True)

    def _finish(self, group: list[_Pending], served: bool) -> None:
        with self._lock:
            for p in group:
                acct = self._account(p.tenant)
                acct.outstanding -= 1
                if served:
                    acct.queries += p.rows
                    self._stats["served"] += 1

    @functools.partial(annotate_function, name=SPAN_TICK)
    def tick(self) -> int:
        """One coalescing pass: drain on an epoch bump, bucket everything
        queued, dispatch each bucket's padded batches, demux. Returns the
        number of device dispatches. Deterministic: buckets form in
        first-submission order and pack requests in arrival order, so a
        given submission sequence always yields the same batches.
        """
        from ..testing import faults as _faults
        inj = _faults.active()
        if inj is not None:
            delay = inj.tick_delay_s()
            if delay:
                time.sleep(delay)   # injected straggler tick
        with self._lock:
            batch, self._queue = self._queue, []
        if not batch:
            self._stats["ticks"] += 1
            return 0
        # Deadline routing: a request whose remaining budget is unlikely
        # to survive a device dispatch (EWMA prediction) gets the tier-0
        # degraded answer now instead of missing its deadline in a bucket.
        now = time.perf_counter()
        ready = []
        for p in batch:
            if (p.t_deadline is not None
                    and (p.t_deadline - now) * 1e3 <= self._dispatch_ewma_ms):
                self._serve_tier0(p)
            else:
                ready.append(p)
        batch = ready
        if not batch:
            self._stats["ticks"] += 1
            return 0
        self._drain_on_epoch_bump()
        # Bucket by (padded shape class, serving config, ci config, join
        # flag); a request bigger than the top class gets a rounded-up
        # class of its own (still a bounded executable set — multiples of
        # the top).
        buckets: OrderedDict[tuple, list[_Pending]] = OrderedDict()
        for p in batch:
            padded_b = self.config.padded_size(p.rows)
            key = (padded_b, int(p.queries.lo.shape[1]), p.serving.cache_key(),
                   p.ci.cache_key() if p.ci is not None else None, p.join)
            buckets.setdefault(key, []).append(p)
        n_dispatch = 0
        for (padded_b, _d, _sk, _ck, _jn), group in buckets.items():
            # Cross-tenant dedup: identical predicate batches within one
            # bucket dispatch once; later arrivals ride the first request's
            # result rows (each still gets its own demuxed view).
            primaries: list[_Pending] = []
            first: dict[tuple, _Pending] = {}
            for p in group:
                sig = (p.rows,
                       np.asarray(p.queries.lo, np.float32).tobytes(),
                       np.asarray(p.queries.hi, np.float32).tobytes())
                owner = first.get(sig)
                if owner is None:
                    first[sig] = p
                    primaries.append(p)
                else:
                    owner.dups.append(p)
                    with self._lock:
                        self._stats["dedup_hits"] += 1
            cur: list[_Pending] = []
            cur_rows = 0
            for p in primaries:     # greedy fill, never split a request
                if cur and cur_rows + p.rows > padded_b:
                    self._dispatch(cur, padded_b, cur[0].serving, cur[0].ci)
                    n_dispatch += 1
                    cur, cur_rows = [], 0
                cur.append(p)
                cur_rows += p.rows
            if cur:
                self._dispatch(cur, padded_b, cur[0].serving, cur[0].ci)
                n_dispatch += 1
        self._stats["ticks"] += 1
        return n_dispatch

    def flush(self) -> int:
        """Tick until the queue is empty (shutdown / test convenience);
        returns total dispatches."""
        total = 0
        while True:
            with self._lock:
                empty = not self._queue
            if empty:
                return total
            total += self.tick()

    def fail_pending(self, exc: BaseException) -> int:
        """Fail every queued future with ``exc`` and release their queue
        accounting; returns the number of requests failed. The driver's
        last-resort containment — no future is ever left unresolved by a
        tick that cannot run."""
        with self._lock:
            batch, self._queue = self._queue, []
            for p in batch:
                acct = self._account(p.tenant)
                acct.outstanding -= 1
                self._stats["failed"] += 1
        for p in batch:
            p.future.set_exception(exc)
        return len(batch)

    def _record_driver_error(self, exc: BaseException) -> None:
        """Surface an exception that escaped a driver tick: count it,
        pin its repr in ``stats()``, and fail whatever was queued so no
        submitter blocks forever on a dead tick."""
        with self._lock:
            self._stats["driver_errors"] += 1
            self._stats["last_driver_error"] = repr(exc)
        self.fail_pending(exc)

    # -- telemetry ---------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def stats(self) -> dict:
        """Coalescer snapshot: overall counters (submitted/served/shed,
        device ``dispatches`` vs ``coalesced_rows`` — the amortization —
        pad overhead, epoch drains, ``queue_wait_ns`` summed over
        ``queue_waits`` requests from submit to the start of their
        dispatch — tier-0 answers have no dispatch and do not count — and
        ``pull_transfers``, the device-to-host transfers the result pulls
        issued: one per dispatch when the result holds one dtype) plus
        ``tenants``: per-tenant requests, queries served, shed count and
        outstanding."""
        with self._lock:
            out = dict(self._stats, queue_depth=len(self._queue))
            out["tenants"] = {t: a.snapshot()
                              for t, a in self._tenants.items()}
        return out


__all__ = ["RequestCoalescer", "Overloaded", "PAD_LO", "PAD_HI"]
