"""`PassEngine`: the one front door for PASS serving (DESIGN.md §8).

PASS's value proposition is a physical design you *build once and serve
many queries against* (paper §2, §4); this module gives the codebase the
matching API shape. A :class:`PassEngine` is constructed once from a
:class:`~repro.core.types.Synopsis` **or** a streaming ingestor plus two
frozen typed configs, then answers query batches forever:

    eng = PassEngine(syn, serving=ServingConfig(kinds=("sum", "avg")),
                     ci=CIConfig(level=0.95))
    results = eng.answer(queries)            # {kind: QueryResult}

Steady-state serving goes through the **prepared-query layer**:
``eng.prepare(queries)`` returns a :class:`PreparedQuery` handle pinning
the resolved synopsis, backend resolution, and the compiled program for
that batch shape x config; repeated ``prepared(queries)`` calls skip every
piece of per-call Python plumbing (kwarg threading, kind validation,
synopsis re-resolution, jit-cache lookup — the handle AOT-compiles the
entry on its second concrete call and then invokes the executable
directly). An LRU plan cache keyed on batch shape x config lives in the
engine, so plain ``eng.answer(...)`` also reuses prepared entries;
``eng.stats()`` exposes hits/misses/evictions/invalidations.

Streaming sources carry an ``epoch`` that bumps on every ``ingest()`` /
re-optimization swap; prepared artifacts (the pinned delta-merged
synopsis) are invalidated on epoch change, so handles stay correct across
ingestion without being rebuilt (the compiled executable survives as long
as the synopsis shapes do).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict

import jax
from jax.profiler import annotate_function

from ..core.types import QueryBatch, QueryResult
from ..engine import executor as _executor
from ..engine.assemble import _answer_jit
from ..kernels.registry import get_backend
from .config import ServingConfig, CIConfig, as_ci_config

# Profiler span names; the benchmark's trace reduction reads them by name.
SPAN_PREPARE = "repro.engine.prepare"
SPAN_CALL = "repro.engine.call"
SPAN_COMPILE = "repro.engine.compile"


class _Unset:
    """Sentinel distinguishing 'inherit the engine's CIConfig' from an
    explicit ci=None (= no intervals); stable repr for signature
    snapshots."""

    def __repr__(self):
        return "<inherit>"


_UNSET = _Unset()


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _resolve_key(key):
    """CIConfig.key (None | int seed | PRNG key array) -> PRNG key array."""
    if key is None:
        return jax.random.PRNGKey(0)
    if isinstance(key, int):
        return jax.random.PRNGKey(key)
    return key


def _validate_request(serving: ServingConfig, ci: CIConfig | None) -> None:
    serving.validate()
    if ci is None:
        return
    ci.validate()
    if ci.method == "bootstrap":
        from ..uncertainty.bootstrap import BOOT_KINDS
        for kind in serving.kinds:
            if kind not in BOOT_KINDS:
                raise ValueError(
                    f"bootstrap supports {BOOT_KINDS}, got {kind!r}")
    if "avg" in serving.kinds and serving.avg_mode != "ratio":
        # Both ci methods center AVG intervals on the ratio estimator.
        raise ValueError(
            f"{ci.method} intervals support avg_mode='ratio' only"
            if ci.method == "bootstrap" else
            "calibrated intervals support avg_mode='ratio' only")


def _validate_join_request(serving: ServingConfig, ci: CIConfig | None):
    from ..joins import JOIN_KINDS
    serving.validate()
    if serving.sample_slots is not None:
        raise ValueError(
            "sample_slots applies to the single-table refinement ladder "
            "only; join serving estimates from key-universe samples, not "
            "the stratified reservoir")
    for kind in serving.kinds:
        if kind not in JOIN_KINDS:
            raise ValueError(
                f"join serving supports kinds {JOIN_KINDS}, got {kind!r} "
                "(min/max have no unbiased universe-sample estimator)")
    if ci is not None:
        ci.validate()
        if ci.method != "clt":
            raise ValueError(
                "join serving supports ci method 'clt' only "
                f"(got {ci.method!r}); the bootstrap resamples reservoir "
                "rows, not key universes")


def _join_dispatch_entry(serving: ServingConfig, ci: CIConfig | None):
    """(jit entry, static kwargs, args builder) for one join serving
    config — the join analogue of :func:`_dispatch_entry`. One compiled
    entry covers both the plain (``ci=None``, lam-scaled CLT width) and
    calibrated-interval paths; ``plan_masks`` is accepted and ignored so
    the builder signature matches the prepared-query plumbing."""
    from ..joins.executor import _join_answer_jit
    backend_name = get_backend(serving.backend).name
    lam = serving.lam
    statics = dict(
        kinds=serving.kinds,
        level=None if ci is None else float(ci.level),
        small_n_threshold=12 if ci is None else int(ci.small_n_threshold),
        delta_budget="stratum" if ci is None else ci.delta_budget,
        backend_name=backend_name)
    return (_join_answer_jit, statics,
            lambda syn, queries, plan_masks: (syn, queries, lam))


def _validate_catalog_request(serving: ServingConfig, ci: CIConfig | None):
    from ..partitions import CATALOG_KINDS
    serving.validate()
    if serving.sample_slots is not None:
        raise ValueError(
            "sample_slots applies to the single-table refinement ladder "
            "only; the partition tier re-stacks per-partition reservoirs "
            "per batch")
    for kind in serving.kinds:
        if kind not in CATALOG_KINDS:
            raise ValueError(
                f"catalog serving supports kinds {CATALOG_KINDS}, got "
                f"{kind!r} (min/max cannot be composed across an "
                "importance-sampled partition stage)")
    if ci is not None:
        ci.validate()
        if ci.method != "clt":
            raise ValueError(
                "catalog serving supports ci method 'clt' only "
                f"(got {ci.method!r}); the bootstrap resamples rows, not "
                "the partition-selection stage")


def _catalog_dispatch_entry(serving: ServingConfig, ci: CIConfig | None,
                            k_part: int):
    """(jit entry, static kwargs, args builder) for one catalog serving
    config. The pinned "synopsis" is the :class:`CatalogSource` itself;
    the builder delegates to ``source.stage(queries)``, which selects,
    materializes, and stacks the partitions for this batch and hands back
    the full dynamic argument tuple."""
    from ..partitions.executor import _catalog_answer_jit
    backend_name = get_backend(serving.backend).name
    lam = serving.lam
    statics = dict(
        kinds=serving.kinds,
        k_part=int(k_part),
        level=None if ci is None else float(ci.level),
        small_n_threshold=12 if ci is None else int(ci.small_n_threshold),
        use_fpc=serving.use_fpc,
        delta_budget="stratum" if ci is None else ci.delta_budget,
        backend_name=backend_name)
    return (_catalog_answer_jit, statics,
            lambda src, queries, plan_masks: src.stage(queries, lam))


def _dispatch_entry(serving: ServingConfig, ci: CIConfig | None):
    """(jit entry, static kwargs, args builder) for one serving config.

    The three compiled entries (plain / CLT intervals / bootstrap) all take
    ``plan_masks`` as a dynamic pytree (None = batched classification) and
    every config field as a static, so one (shape x config) pair maps to
    exactly one executable. The builder closes over everything per-call
    code would otherwise recompute (backend resolution, key material), so
    a prepared call only assembles the dynamic argument tuple.
    """
    backend_name = get_backend(serving.backend).name
    if ci is None:
        lam = serving.lam
        return (_answer_jit,
                dict(kinds=serving.kinds, use_fpc=serving.use_fpc,
                     zero_var_rule=serving.zero_var_rule,
                     use_aggregates=serving.use_aggregates,
                     avg_mode=serving.avg_mode, backend_name=backend_name),
                lambda syn, queries, plan_masks: (syn, queries, lam,
                                                  plan_masks))
    if ci.method == "clt":
        from ..uncertainty import intervals as _intervals
        return (_intervals._ci_answer_jit,
                dict(kinds=serving.kinds, level=float(ci.level),
                     small_n_threshold=int(ci.small_n_threshold),
                     use_fpc=serving.use_fpc,
                     zero_var_rule=serving.zero_var_rule,
                     use_aggregates=serving.use_aggregates,
                     avg_mode=serving.avg_mode,
                     delta_budget=ci.delta_budget,
                     backend_name=backend_name),
                lambda syn, queries, plan_masks: (syn, queries, plan_masks))
    from ..uncertainty import bootstrap as _bootstrap
    key = _resolve_key(ci.key)
    return (_bootstrap._bootstrap_jit,
            dict(kinds=serving.kinds, n_boot=int(ci.n_boot),
                 level=float(ci.level), normalize=ci.boot_normalize,
                 use_aggregates=serving.use_aggregates,
                 backend_name=backend_name, fused=bool(ci.boot_fused)),
            lambda syn, queries, plan_masks: (syn, queries, plan_masks, key))


class PreparedQuery:
    """A pinned (batch shape x config) serving entry (DESIGN.md §8).

    Calling the handle with a same-shaped :class:`QueryBatch` runs the
    pinned compiled program with no Python-side re-setup: configs are
    pre-validated, the backend is pre-resolved, the synopsis is pinned
    (re-resolved only when the source's epoch bumps), and from the second
    concrete call on the jit dispatch itself is bypassed via an
    AOT-compiled executable (``jit.lower(...).compile()`` — bit-identical
    to the jit path, it is the same program).

    Differently-shaped batches fall back to ``engine.answer`` (a plan-cache
    miss there), so a handle never answers wrongly — it only ever loses its
    fast path.
    """

    def __init__(self, engine: "PassEngine", serving: ServingConfig,
                 ci: CIConfig | None, shape: tuple, has_plan: bool = False):
        self._engine = engine
        self.serving = serving
        self.ci = ci
        self.shape = tuple(shape)
        self.has_plan = bool(has_plan)
        self._epoch = engine.epoch
        self._generation = engine._generation
        self._syn = self._resolve_source()
        self._fn, self._statics, self._build = self._make_entry()
        self._aot = None
        self._calls = 0

    # Subclass hooks: which source view is pinned, which compiled entry
    # serves it, and where differently-shaped batches fall back to.
    def _make_entry(self):
        return _dispatch_entry(self.serving, self.ci)

    def _resolve_source(self):
        # sample_slots pins the refinement-ladder view: the first-N
        # reservoir slots per stratum (a uniform subsample — validity is a
        # per-stratum prefix), giving this entry a proportionally cheaper
        # moment pass. None = the full reservoir.
        return _executor.slice_sample_slots(self._engine.resolve(),
                                            self.serving.sample_slots)

    def _fallback_answer(self, queries) -> dict[str, QueryResult]:
        return self._engine.answer(queries, kinds=self.serving.kinds,
                                   ci=self.ci, serving=self.serving)

    def _refresh(self) -> None:
        """Re-pin the serving synopsis after a source epoch bump or a
        replace_source() swap (two immutable synopses both report epoch 0,
        so source identity is tracked via the engine generation)."""
        eng = self._engine
        if eng.epoch == self._epoch and eng._generation == self._generation:
            return
        old_syn = self._syn
        self._epoch = eng.epoch
        self._generation = eng._generation
        self._syn = self._resolve_source()
        eng._stats["invalidations"] += 1
        # The executable only bakes shapes; drop it iff they changed
        # (e.g. a re-optimization rebuilt the synopsis at a different k).
        try:
            same = jax.tree_util.tree_all(jax.tree_util.tree_map(
                lambda a, b: (getattr(a, "shape", None)
                              == getattr(b, "shape", None)),
                old_syn, self._syn))
        except ValueError:            # pytree structure itself changed
            same = False
        if not same:
            self._aot = None

    @functools.partial(annotate_function, name=SPAN_COMPILE)
    def _build_aot(self, args) -> None:
        # A compile failure raises: serving must not silently leave the
        # compiled path it was prepared for.
        self._aot = self._fn.lower(*args, **self._statics).compile()
        self._engine._stats["aot_compiles"] += 1

    @functools.partial(annotate_function, name=SPAN_CALL)
    def __call__(self, queries: QueryBatch,
                 plan_masks=None) -> dict[str, QueryResult]:
        if (plan_masks is not None) != self.has_plan:
            raise ValueError(
                "prepared entry was pinned with has_plan="
                f"{self.has_plan}; pass plan_masks accordingly")
        if tuple(queries.lo.shape) != self.shape:
            if self.has_plan:
                # Planner masks are (Q, k)-shaped: re-key on the batch's own
                # shape so the fallback stays a (counted) plan-cache miss.
                return self._engine._lookup(
                    tuple(queries.lo.shape), self.serving, self.ci,
                    has_plan=True)(queries, plan_masks)
            return self._fallback_answer(queries)
        self._refresh()
        _executor.count_artifact_pass(self.serving.kinds)
        if (self.ci is not None and self.ci.method == "bootstrap"
                and self.ci.boot_fused):
            self._engine._stats["fused_serves"] += 1
        args = self._build(self._syn, queries, plan_masks)
        self._calls += 1
        if _is_tracer(queries.lo):
            # Traced inside a caller's jit: there is no concrete input to
            # run an executable on, so the caller's program inlines ours.
            self._engine._stats["aot_fallbacks"] += 1
            return self._fn(*args, **self._statics)
        if self._aot is None and self._calls >= 2:
            self._build_aot(args)
        if self._aot is not None:
            try:
                return self._aot(*args)
            except TypeError:
                # Same shape but a different dtype than the lowering was
                # compiled for: the jit path recompiles and answers.
                self._engine._stats["aot_fallbacks"] += 1
        return self._fn(*args, **self._statics)


class PreparedJoinQuery(PreparedQuery):
    """A pinned fk-join serving entry (DESIGN.md §13): same lifecycle as
    :class:`PreparedQuery` (plan cache slot, epoch-driven re-pin, AOT on
    the second concrete call), but pinning the resolved
    :class:`~repro.joins.JoinSynopsis` and the compiled join entry. The
    pinned batch shape is the full concatenated ``(Q, d_fact + d_dim)``
    join-rectangle shape."""

    def _make_entry(self):
        return _join_dispatch_entry(self.serving, self.ci)

    def _resolve_source(self):
        return self._engine.resolve_join()

    def _fallback_answer(self, queries) -> dict[str, QueryResult]:
        return self._engine.answer_join(queries, kinds=self.serving.kinds,
                                        ci=self.ci, serving=self.serving)


class PreparedCatalogQuery(PreparedQuery):
    """A pinned partition-tier serving entry (DESIGN.md §14): same plan
    cache slot / epoch-driven re-pin lifecycle as :class:`PreparedQuery`,
    but pinning the :class:`~repro.partitions.CatalogSource` itself — the
    per-call ``stage()`` re-draws the partition selection, so the dynamic
    argument shapes vary with how many partitions get picked (padded to a
    power of two; the AOT fast path engages whenever consecutive calls
    land on the same padded width and falls back to jit otherwise)."""

    def _make_entry(self):
        return _catalog_dispatch_entry(self.serving, self.ci,
                                       self._engine._source.config.k)

    def _resolve_source(self):
        return self._engine._source


class PassEngine:
    """Stateful PASS serving facade: configure once, serve many.

    ``source`` is a :class:`~repro.core.types.Synopsis` or any delta-merge
    source exposing ``as_synopsis()`` (a ``StreamingIngestor`` serves
    straight from its device-resident base+delta combine). ``serving`` and
    ``ci`` are the frozen typed configs; ``ci=None`` serves plain
    estimates, ``ci=0.95`` is shorthand for ``CIConfig(level=0.95)``.

    ``answer()`` routes through an LRU prepared-plan cache keyed on
    (batch shape, serving config, ci config); source changes invalidate
    lazily through the epoch/generation counters, not the key.
    ``prepare()`` returns the cache entry as an explicit handle. See
    :class:`PreparedQuery` for what a hit skips.
    """

    def __init__(self, source, serving: ServingConfig | None = None,
                 ci: CIConfig | float | None = None,
                 plan_cache_size: int = 32):
        self._source = source
        self.serving = (serving or ServingConfig()).validate()
        self.ci = as_ci_config(ci)
        _validate_request(self.serving, self.ci)
        if plan_cache_size < 1:
            raise ValueError("plan_cache_size must be >= 1")
        self._plan_cache_size = int(plan_cache_size)
        self._cache: OrderedDict[tuple, PreparedQuery] = OrderedDict()
        self._generation = 0
        self._coalescer = None
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "invalidations": 0, "aot_compiles": 0,
                       "aot_fallbacks": 0,
                       "fused_serves": 0, "tier0_serves": 0,
                       "refine_steps": 0, "degraded_serves": 0}
        self._refine_ewma_ms = 0.0

    # -- construction ------------------------------------------------------
    @classmethod
    def from_sharded(cls, c, a, *, k: int = 64, mesh=None,
                     serving: ServingConfig | None = None,
                     ci: CIConfig | float | None = None,
                     plan_cache_size: int = 32,
                     **build_kw) -> "PassEngine":
        """Build a synopsis data-parallel over ``mesh`` and serve it.

        Runs :func:`repro.sharded.build_synopsis_sharded` (rows sharded
        over the mesh's ``"shards"`` axis, O(k) merge) and wraps the
        resulting :class:`~repro.sharded.ShardedIngestor` as the engine
        source, so the engine keeps streaming data-parallel afterwards:
        ``eng.source.ingest(...)`` bumps the epoch and prepared plans
        re-pin on their next call, exactly like the single-device
        streaming source. ``build_kw`` forwards to the sharded builder
        (``sample_budget``, ``method``, ``opt_samples``, ``seed``, ...).
        """
        from ..sharded import build_synopsis_sharded
        ing, _report = build_synopsis_sharded(c, a, k=k, mesh=mesh,
                                              **build_kw)
        return cls(ing, serving=serving, ci=ci,
                   plan_cache_size=plan_cache_size)

    @classmethod
    def from_catalog(cls, parts, *, catalog=None,
                     serving: ServingConfig | None = None,
                     ci: CIConfig | float | None = None,
                     plan_cache_size: int = 32,
                     **build_kw) -> "PassEngine":
        """Serve partitioned data through the sketch-guided partition
        tier (DESIGN.md §14).

        ``parts`` is a :class:`~repro.partitions.PartitionStore` or a
        sequence of per-partition ``(c, a)`` row blocks. ``catalog`` is a
        :class:`~repro.api.CatalogConfig`; with a ``max_partitions``
        budget the engine materializes PASS synopses only for the
        partitions the picker selects per batch (disjoint/covered ones
        are pruned exactly) and composes answers by Horvitz-Thompson
        with two-stage intervals. Without a budget the tier serves the
        flat synopsis over all rows (``build_kw`` forwards to
        ``build_synopsis``), bit-identical to never partitioning.
        """
        from ..partitions import CatalogSource, PartitionStore
        from .config import CatalogConfig
        store = (parts if isinstance(parts, PartitionStore)
                 else PartitionStore(parts))
        cfg = (catalog if catalog is not None else CatalogConfig()).validate()
        return cls(CatalogSource(store, cfg, build_kw), serving=serving,
                   ci=ci, plan_cache_size=plan_cache_size)

    # -- source ------------------------------------------------------------
    @property
    def source(self):
        return self._source

    def _catalog_selective(self) -> bool:
        """True when the source is a budgeted CatalogSource: serving must
        route through the partition-selection entry (a dense catalog
        source flows through the ordinary flat path instead)."""
        src = self._source
        return (getattr(src, "is_catalog_source", False)
                and not src.serves_flat)

    @property
    def epoch(self) -> int:
        """Monotone change counter of the source (0 for an immutable
        synopsis; streaming ingestors bump it per ingest/re-optimization)."""
        return getattr(self._source, "epoch", 0)

    def resolve(self):
        """Current serving synopsis (delta-merged for streaming sources)."""
        return _executor.resolve_synopsis(self._source)

    def replace_source(self, source) -> "PassEngine":
        """Swap the serving source (e.g. after ``reoptimize`` returned a
        fresh ingestor) and invalidate every cached plan. The generation
        bump also reaches handles the user still holds from ``prepare()``
        (epochs alone cannot: two immutable synopses both report 0)."""
        self._source = source
        self._generation += 1
        self.clear_cache()
        self._stats["invalidations"] += 1
        return self

    # -- config plumbing ---------------------------------------------------
    def _effective_catalog(self, kinds, ci, serving):
        from ..partitions import CATALOG_KINDS
        sv = serving if serving is not None else self.serving
        if kinds is not None:
            sv = dataclasses.replace(sv, kinds=kinds)
        else:
            # Inherited kinds keep only the catalog-answerable ones (same
            # contract as join serving's kind inheritance).
            sv = dataclasses.replace(
                sv, kinds=tuple(k for k in sv.kinds if k in CATALOG_KINDS)
                or ("sum",))
        cfg = self.ci if ci is _UNSET else as_ci_config(ci)
        _validate_catalog_request(sv, cfg)
        return sv, cfg

    def _effective(self, kinds, ci, serving):
        sv = serving if serving is not None else self.serving
        if kinds is not None:
            sv = dataclasses.replace(sv, kinds=kinds)
        cfg = self.ci if ci is _UNSET else as_ci_config(ci)
        _validate_request(sv.validate(), cfg)
        return sv, cfg

    # -- plan cache --------------------------------------------------------
    # Epoch bumps need no eager sweep here: every PreparedQuery.__call__
    # starts with _refresh(), which lazily re-pins the delta merge (and
    # counts one invalidation) the next time that plan is actually used —
    # O(1) per ingest instead of O(cache) per bump.

    def _lookup(self, shape, serving, ci, has_plan: bool = False,
                join: bool = False, catalog: bool = False) -> PreparedQuery:
        key = (tuple(shape), serving.cache_key(),
               ci.cache_key() if ci is not None else None, has_plan, join,
               catalog)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            self._stats["hits"] += 1
            return hit
        self._stats["misses"] += 1
        cls = (PreparedCatalogQuery if catalog
               else PreparedJoinQuery if join else PreparedQuery)
        prepared = cls(self, serving, ci, shape, has_plan=has_plan)
        self._cache[key] = prepared
        if len(self._cache) > self._plan_cache_size:
            self._cache.popitem(last=False)
            self._stats["evictions"] += 1
        return prepared

    def clear_cache(self) -> None:
        self._cache.clear()

    def stats(self) -> dict:
        """Plan-cache instrumentation: hits/misses/evictions/invalidations/
        aot_compiles/aot_fallbacks (prepared calls served through jit
        instead: a traced batch, or a dtype the executable was not
        compiled for)/fused_serves (calls answered through the fused
        bootstrap megakernel path) plus current entry count and source
        epoch. When a :class:`repro.serve.RequestCoalescer` is attached to
        this engine, its snapshot (dispatch amortization, queue-wait
        counters, per-tenant served counts) rides along under the
        ``"coalescer"`` key."""
        out = dict(self._stats, entries=len(self._cache), epoch=self.epoch)
        if self._coalescer is not None:
            out["coalescer"] = self._coalescer.stats()
        if getattr(self._source, "is_catalog_source", False):
            out["catalog"] = self._source.stats()
        out["faults"] = self._fault_snapshot()
        return out

    def _fault_snapshot(self) -> dict:
        """Containment-policy observability (DESIGN.md §15): quarantined
        row counts and dispatch/materialization containment counters from
        the source, injected-event counts when a fault harness is
        installed, degraded partitions from a catalog source."""
        faults: dict = {}
        src = self._source
        if hasattr(src, "n_quarantined"):
            faults["quarantined_rows"] = src.n_quarantined
        if hasattr(src, "fault_stats"):
            faults.update(src.fault_stats())
        if hasattr(src, "degraded_partitions"):
            faults["degraded_partitions"] = sorted(src.degraded_partitions)
        from ..testing import faults as _faults
        inj = _faults.active()
        if inj is not None:
            faults["injected"] = inj.snapshot()
        return faults

    # -- serving -----------------------------------------------------------
    @functools.partial(annotate_function, name=SPAN_PREPARE)
    def prepare(self, queries_or_shape, *, kinds=None, ci=_UNSET,
                serving: ServingConfig | None = None) -> PreparedQuery:
        """Pin a (batch shape x config) serving entry and return the handle.

        ``queries_or_shape`` is a :class:`QueryBatch` (its shape is used) or
        a ``(Q, d)`` tuple. The handle is registered in the plan cache, so a
        later same-shaped ``answer()`` call reuses it (and vice versa).
        """
        shape = (tuple(queries_or_shape.lo.shape)
                 if hasattr(queries_or_shape, "lo")
                 else tuple(queries_or_shape))
        if len(shape) != 2:
            raise ValueError(f"expected a (Q, d) batch shape, got {shape}")
        if self._catalog_selective():
            sv, cfg = self._effective_catalog(kinds, ci, serving)
            return self._lookup(shape, sv, cfg, catalog=True)
        sv, cfg = self._effective(kinds, ci, serving)
        return self._lookup(shape, sv, cfg)

    def answer(self, queries: QueryBatch, *, kinds=None, ci=_UNSET,
               serving: ServingConfig | None = None, plan=None,
               deadline_ms: float | None = None) -> dict[str, QueryResult]:
        """Answer a batch for every configured kind from one shared
        artifact pass; returns ``{kind: QueryResult}``.

        ``kinds=`` / ``ci=`` / ``serving=`` override the engine configs for
        this call (overrides are themselves cached per shape x config).
        ``plan=`` injects a planner ``QueryPlan``; the masks are dynamic
        (Q, k) operands of the same compiled entry, so plan-carrying calls
        share a prepared plan-cache slot per shape x config (keyed apart
        from the plan-less entries, whose pytree lacks the mask operands)
        instead of bypassing the cache — ``stats()`` hits/misses stay
        truthful either way.

        ``deadline_ms=`` (or ``CIConfig(max_ci_width=...)``) switches to
        the graceful degradation ladder (DESIGN.md §15): a tier-0
        aggregates-only answer is produced immediately from the planner
        descent + §2.3 hard bounds (zero sample work), then refined
        through growing reservoir slices until the CI-width target or the
        deadline is hit. The ladder never blows the deadline: the next
        tier only starts when its EWMA-predicted latency still fits.
        """
        shape = tuple(queries.lo.shape)
        if self._catalog_selective():
            if plan is not None:
                raise ValueError(
                    "plan= is not supported with a budgeted catalog "
                    "source; planner masks are per-stratum of ONE synopsis "
                    "while the partition tier re-stacks strata per batch")
            if deadline_ms is not None:
                raise ValueError(
                    "deadline_ms needs the aggregate-tree tier-0 path; a "
                    "budgeted catalog source degrades per partition "
                    "instead (see stats()['faults'])")
            sv, cfg = self._effective_catalog(kinds, ci, serving)
            return self._lookup(shape, sv, cfg, catalog=True)(queries)
        sv, cfg = self._effective(kinds, ci, serving)
        if (deadline_ms is not None
                or (cfg is not None and cfg.max_ci_width is not None
                    and plan is None)):
            if plan is not None:
                raise ValueError(
                    "deadline_ms cannot be combined with plan=; the "
                    "ladder plans tier 0 itself")
            return self.answer_progressive(
                queries, kinds=kinds, ci=ci, serving=serving,
                deadline_ms=deadline_ms).run()
        if plan is not None:
            return self._lookup(shape, sv, cfg, has_plan=True)(
                queries, _executor.plan_to_masks(plan))
        return self._lookup(shape, sv, cfg)(queries)

    def answer_progressive(self, queries: QueryBatch, *, kinds=None,
                           ci=_UNSET, serving: ServingConfig | None = None,
                           deadline_ms: float | None = None):
        """Start the degradation ladder and return its
        :class:`~repro.serve.RefinementHandle` — ``handle.results`` holds
        the tier-0 answer immediately; ``refine()`` / ``final()`` /
        ``run()`` tighten it from progressively larger sample slices."""
        from ..serve.refine import RefinementHandle
        if self._catalog_selective():
            raise ValueError(
                "progressive refinement needs the aggregate-tree tier-0 "
                "path; not available on a budgeted catalog source")
        sv, cfg = self._effective(kinds, ci, serving)
        if sv.sample_slots is not None:
            raise ValueError(
                "sample_slots is managed by the ladder itself; pass a "
                "serving config without it")
        return RefinementHandle(self, queries, sv, cfg,
                                deadline_ms=deadline_ms)

    # -- checkpoint / restore (DESIGN.md §15) --------------------------------
    def checkpoint(self, path) -> dict:
        """Snapshot the serving state (synopsis / streaming reservoir /
        join universe buffers / catalog state) at an epoch boundary; see
        :func:`repro.serve.checkpoint.save_engine`. Returns the metadata
        dict that was written."""
        from ..serve.checkpoint import save_engine
        return save_engine(self, path)

    @classmethod
    def restore(cls, path, *, serving: ServingConfig | None = None,
                ci: CIConfig | float | None = None, mesh=None,
                plan_cache_size: int = 32) -> "PassEngine":
        """Rebuild an engine from a :meth:`checkpoint` file, bit-identical
        on the serving path; see :func:`repro.serve.checkpoint.load_engine`.
        ``serving=`` / ``ci=`` default to the checkpointed configs."""
        from ..serve.checkpoint import load_engine
        return load_engine(cls, path, serving=serving, ci=ci, mesh=mesh,
                           plan_cache_size=plan_cache_size)

    # -- fk-join serving (DESIGN.md §13) ------------------------------------
    def resolve_join(self):
        """Current join synopsis; raises TypeError when the engine source
        has no join augmentation (``build_join_synopsis`` /
        ``JoinStreamingIngestor``)."""
        from ..joins import resolve_join_synopsis
        return resolve_join_synopsis(self._source)

    def _effective_join(self, kinds, ci, serving):
        sv = serving if serving is not None else self.serving
        if kinds is not None:
            sv = dataclasses.replace(sv, kinds=kinds)
        else:
            from ..joins import JOIN_KINDS
            # Inherited kinds keep only the join-answerable ones, so an
            # engine configured for 5-kind single-table serving still
            # answers joins without per-call kinds= plumbing.
            sv = dataclasses.replace(
                sv, kinds=tuple(k for k in sv.kinds if k in JOIN_KINDS)
                or ("sum",))
        cfg = self.ci if ci is _UNSET else as_ci_config(ci)
        _validate_join_request(sv, cfg)
        return sv, cfg

    def _as_join_batch(self, queries, dim_queries=None) -> QueryBatch:
        """Normalize to the concatenated ``[fact ‖ dim attrs]`` rectangle:
        accepts (fact, dim) batch pairs, a full-width batch, or a
        fact-width batch (dim side unconstrained)."""
        import jax.numpy as jnp
        from ..joins import join_queries
        from ..kernels.ref import NEG_BIG, POS_BIG
        jsyn = self.resolve_join()
        d_f, d_d = jsyn.d_fact, jsyn.d_dim
        if dim_queries is not None:
            return join_queries(queries, dim_queries)
        if isinstance(queries, tuple):
            return join_queries(*queries)
        width = queries.lo.shape[1]
        if width == d_f + d_d:
            return queries
        if width == d_f:
            q = queries.lo.shape[0]
            return QueryBatch(
                jnp.concatenate(
                    [jnp.asarray(queries.lo, jnp.float32),
                     jnp.full((q, d_d), NEG_BIG, jnp.float32)], axis=1),
                jnp.concatenate(
                    [jnp.asarray(queries.hi, jnp.float32),
                     jnp.full((q, d_d), POS_BIG, jnp.float32)], axis=1))
        raise ValueError(
            f"join query width {width} matches neither the fact side "
            f"({d_f}) nor the concatenated layout ({d_f + d_d})")

    def _check_join_binding(self, dim_table, on) -> None:
        jsyn = self.resolve_join()
        if on is not None and on != jsyn.key_name:
            raise ValueError(
                f"engine's join synopsis is keyed on {jsyn.key_name!r}, "
                f"got on={on!r}; universe membership is drawn per key at "
                "build time, so the join key cannot change at query time")
        if dim_table is not None and dim_table is not jsyn.dim:
            d = jsyn.dim
            if (dim_table.num_keys != d.num_keys
                    or dim_table.num_partitions != d.num_partitions
                    or dim_table.d_attr != d.d_attr):
                raise ValueError(
                    "dim_table differs from the one this join synopsis "
                    "was built against; rebuild with build_join_synopsis "
                    "to join a different dimension relation")

    @functools.partial(annotate_function, name=SPAN_PREPARE)
    def prepare_join(self, queries_or_shape, *, kinds=None, ci=_UNSET,
                     serving: ServingConfig | None = None
                     ) -> PreparedJoinQuery:
        """Pin a join serving entry (the join analogue of ``prepare``).

        Accepts a :class:`QueryBatch` in any ``answer_join`` layout, a
        (fact, dim) batch pair, or a full concatenated ``(Q, d_fact +
        d_dim)`` shape tuple.
        """
        if hasattr(queries_or_shape, "lo") or isinstance(
                queries_or_shape, tuple) and hasattr(
                    queries_or_shape[0] if queries_or_shape else None, "lo"):
            shape = tuple(self._as_join_batch(queries_or_shape).lo.shape)
        else:
            shape = tuple(queries_or_shape)
        if len(shape) != 2:
            raise ValueError(f"expected a (Q, d) batch shape, got {shape}")
        sv, cfg = self._effective_join(kinds, ci, serving)
        return self._lookup(shape, sv, cfg, join=True)

    def answer_join(self, fact_queries, dim_queries=None, *, dim_table=None,
                    on: str | None = None, kinds=None, ci=_UNSET,
                    serving: ServingConfig | None = None
                    ) -> dict[str, QueryResult]:
        """Answer fk-join aggregate queries against the engine's join
        synopsis; returns ``{kind: QueryResult}`` like ``answer``.

        ``fact_queries`` is a :class:`QueryBatch` over fact coordinates
        (the dim side is then unconstrained), a full concatenated
        ``[fact ‖ dim attrs]`` batch, or a (fact, dim) pair —
        equivalently pass ``dim_queries=`` for the dimension-side
        rectangles. ``dim_table=``/``on=`` optionally assert which
        dimension relation/key the query intends (the synopsis is bound
        to one at build time). Cells covered on both sides are answered
        exactly from pre-joined aggregates; overlapping cells by
        Horvitz-Thompson over the correlated key-universe samples, with
        CLT/Bernstein intervals composed through ``uncertainty``.
        """
        self._check_join_binding(dim_table, on)
        queries = self._as_join_batch(fact_queries, dim_queries)
        sv, cfg = self._effective_join(kinds, ci, serving)
        return self._lookup(tuple(queries.lo.shape), sv, cfg, join=True)(
            queries)


__all__ = ["PassEngine", "PreparedQuery", "PreparedJoinQuery",
           "PreparedCatalogQuery"]
