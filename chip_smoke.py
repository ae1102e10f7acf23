"""Chip smoke: the PASS serving and ingest path on one TPU, at paper size.

    python chip_smoke.py              # one chip: build, serve, bootstrap,
                                      # stream, coalesce (all phases)
    python chip_smoke.py --chips 4    # the sharded synopsis on a 4-chip
                                      # mesh against a 1-chip mesh, only

Everything runs in this one process, through the entry points a user
calls (``build_synopsis``, ``PassEngine``, ``StreamingIngestor``,
``RequestCoalescer``, ``PassEngine.from_sharded``), on data generated from
``--seed``: the NYC-taxi stand-in at the paper's full 7.7 M rows, a
synopsis of k=1024 leaves holding ~1 M samples. Answers are checked
against the float64 host reference (``repro.core.query.ground_truth*``):

* covered queries (no partial leaf, so a zero-width interval) must equal
  the reference to f32 rounding: relative error at most (2m + 4) * 2^-24
  over m covered leaves;
* median relative error of SUM, COUNT and AVG on the non-empty random
  queries at most ``MEDIAN_RELERR_BOUND`` (1-D) and
  ``MEDIAN_RELERR_BOUND_3D`` (3-D);
* 95% interval coverage at least ``MIN_COVERAGE`` on the random queries
  with a non-zero-width interval (CLT and bootstrap intervals);
* the fused bootstrap answer bit-identical to the per-replicate scan path.

Earlier lines report each phase's set-up (compile) seconds, its steady
seconds, and the accuracy figures. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase ran and
every check held. Without a TPU, or with ``REPRO_KERNEL_BACKEND`` set,
the script exits non-zero before any phase. Where
``JAX_COMPILATION_CACHE_DIR`` is set the compile cache lives there,
otherwise in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))

MEDIAN_RELERR_BOUND = 0.005      # 1-D nyc_taxi, ~13% of rows sampled
MEDIAN_RELERR_BOUND_3D = 0.02    # 3-D: three predicates, smaller results
# Per-dimension predicate widths (fractions of the rows' range). The 3-D
# columns are strongly correlated, so the 1-D widths would leave most 3-D
# rectangles empty.
QUERY_WIDTHS = {"1d": dict(min_frac=0.005, max_frac=0.3),
                "3d": dict(min_frac=0.05, max_frac=0.5)}
MIN_COVERAGE = 0.92              # nominal 0.95
KINDS = ("sum", "count", "avg")
BIG = 3.0e38


@dataclasses.dataclass(frozen=True)
class Sizes:
    scale: float = 1.0           # nyc_taxi scale: 1.0 = 7.7 M rows
    k: int = 1024                # leaves
    samples: int = 1_048_576     # sample budget
    queries: int = 2000          # random queries per serve call, all
                                 # scored against the reference
    covered: int = 64            # constructed covered queries (1-D)
    boot_queries: int = 256
    n_boot: int = 200
    batch: int = 262_144         # streamed rows per ingest call
    batches: int = 4
    tenants: int = 8


FULL = Sizes()


class Checks:
    """Collects failed checks; the run fails at the end if any did."""

    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def log(msg: str) -> None:
    print(msg, flush=True)


SETUP_S: list[float] = []


def phase_line(name: str, setup_s: float, steady_s: float | None = None,
               **extra) -> None:
    """One phase's set-up (compile included) and steady seconds."""
    SETUP_S.append(setup_s)
    fields = [f"setup_s={setup_s!r}"]
    if steady_s is not None:
        fields.append(f"steady_s={steady_s!r}")
    fields += [f"{k}={v!r}" if isinstance(v, float) else f"{k}={v}"
               for k, v in extra.items()]
    log(f"phase {name}: " + " ".join(fields))


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# References and query construction
# --------------------------------------------------------------------------

def truth(c, a, qs, block: int = 64):
    """SUM and COUNT from the f64 host scan, in query blocks on a thread
    pool (numpy releases the GIL in the scan's large array operations)."""
    import numpy as np
    from repro.core.query import ground_truth_kinds
    from repro.core.types import QueryBatch
    lo, hi = np.asarray(qs.lo), np.asarray(qs.hi)

    def part(i):
        return ground_truth_kinds(
            c, a, QueryBatch(lo[i:i + block], hi[i:i + block]),
            ("sum", "count"))

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        parts = list(pool.map(part, range(0, lo.shape[0], block)))
    return {k: np.concatenate([p[k] for p in parts]) for k in ("sum", "count")}


def with_avg(t):
    import numpy as np
    return dict(t, avg=t["sum"] / np.maximum(t["count"], 1))


def add_truth(t1, t2):
    return with_avg({k: t1[k] + t2[k] for k in ("sum", "count")})


def take(qs, n):
    from repro.core.types import QueryBatch
    return QueryBatch(qs.lo[:n], qs.hi[:n])


def clean_thresholds(lo, hi):
    """f32 thresholds t in one dimension at which a predicate ``x <= t`` or
    ``x >= t`` splits the leaves exactly as it splits their f64 rows: every
    nonempty leaf box lies at least one f32 step from t (a box stores its
    rows' extremes rounded to f32, so its rows lie within half a step of
    it). Returns (thresholds, number of gaps where none exists)."""
    import numpy as np
    ne = lo <= hi
    lo, hi = lo[ne].astype(np.float32), hi[ne].astype(np.float32)
    cand = np.unique(np.nextafter(lo, np.float32(-np.inf)))
    up = np.nextafter(hi, np.float32(np.inf))
    down = np.nextafter(lo, np.float32(-np.inf))
    # clean: for every leaf, next(hi) <= t  or  prev(lo) >= t
    clean = np.all((up[None, :] <= cand[:, None])
                   | (down[None, :] >= cand[:, None]), axis=1)
    inner = cand[(cand > lo.min()) & clean]
    return inner, int(np.sum(cand > lo.min())) - inner.size


def covered_queries(syn, n, rng):
    """Queries with no partial leaf: 1-D intervals between clean thresholds,
    or for d > 1 the whole space plus half-spaces at clean thresholds."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.types import QueryBatch
    lo = np.asarray(syn.leaf_lo, np.float32)
    hi = np.asarray(syn.leaf_hi, np.float32)
    d = lo.shape[1]
    qlo, qhi, unclean = [], [], 0
    if d == 1:
        t, unclean = clean_thresholds(lo[:, 0], hi[:, 0])
        t = np.concatenate([[-BIG], t, [BIG]]).astype(np.float32)
        for _ in range(n):
            i, j = np.sort(rng.choice(t.size, 2, replace=False))
            qlo.append([t[i]])
            qhi.append([t[j]])
    else:
        qlo.append([-BIG] * d)
        qhi.append([BIG] * d)
        for j in range(d):
            t, u = clean_thresholds(lo[:, j], hi[:, j])
            unclean += u
            for tj in t[: max(1, n // (2 * d))]:
                a = [-BIG] * d
                b = [BIG] * d
                b[j] = tj
                qlo.append(list(a))
                qhi.append(list(b))
                a[j], b[j] = tj, BIG
                qlo.append(a)
                qhi.append(b)
    qs = QueryBatch(jnp.asarray(np.array(qlo, np.float32)),
                    jnp.asarray(np.array(qhi, np.float32)))
    inside = (np.all(np.asarray(qs.lo)[:, None] <= lo[None], -1)
              & np.all(hi[None] <= np.asarray(qs.hi)[:, None], -1)
              & np.all(lo <= hi, -1)[None])
    return qs, inside.sum(axis=1), unclean


def check_covered(chk, tag, res, ref, m_leaves, unclean):
    """Zero-width intervals equal to the f64 reference to f32 rounding."""
    import numpy as np
    bound = (2 * m_leaves + 4) * 2.0 ** -24
    for kind in KINDS:
        r = res[kind]
        est = np.asarray(r.estimate, np.float64)
        width = np.asarray(r.ci_hi, np.float64) - np.asarray(r.ci_lo,
                                                             np.float64)
        t = ref[kind]
        rel = np.abs(est - t) / np.maximum(np.abs(t), 1e-30)
        log(f"covered {tag} {kind}: n={est.size} max_width={width.max()!r} "
            f"max_relerr={rel.max()!r} max_bound={bound.max()!r} "
            f"gaps_without_clean_f32_threshold={unclean}")
        chk.expect(bool(np.all(width == 0.0)),
                   f"{tag} covered {kind}: zero-width intervals")
        chk.expect(bool(np.all(rel <= bound)),
                   f"{tag} covered {kind}: equal to f64 reference at f32 "
                   "rounding")


def check_accuracy(chk, tag, res, ref, bound):
    """Median relative error and interval coverage on random queries."""
    import numpy as np
    for kind in KINDS:
        r = res[kind]
        n = ref[kind].size
        est = np.asarray(r.estimate, np.float64)[:n]
        lo = np.asarray(r.ci_lo, np.float64)[:n]
        hi = np.asarray(r.ci_hi, np.float64)[:n]
        t = ref[kind]
        chk.expect(bool(np.all(np.isfinite(est)) and np.all(lo <= hi)),
                   f"{tag} {kind}: finite estimates, ordered intervals")
        # relative error where the answer is not empty; coverage where
        # the interval is not a point (covered queries are checked apart)
        ne = ref["count"] > 0
        rel = np.abs(est[ne] - t[ne]) / np.abs(t[ne])
        med = float(np.median(rel))
        partial = hi > lo
        cov = float(np.mean(((lo <= t) & (t <= hi))[partial]))
        log(f"accuracy {tag} {kind}: n={n} nonempty={rel.size} "
            f"median_relerr={med!r} "
            f"p90_relerr={float(np.quantile(rel, 0.9))!r} "
            f"coverage95={cov!r} (over {int(partial.sum())} non-zero-width)")
        chk.expect(med <= bound,
                   f"{tag} {kind}: median relative error <= {bound}")
        chk.expect(cov >= MIN_COVERAGE,
                   f"{tag} {kind}: 95% interval coverage >= {MIN_COVERAGE}")


def bit_equal(a, b, fields=("estimate", "ci_lo", "ci_hi", "lower", "upper")):
    import numpy as np
    return all(np.array_equal(np.asarray(getattr(a[k], f)),
                              np.asarray(getattr(b[k], f)))
               for k in a for f in fields)


# --------------------------------------------------------------------------
# One-chip phases
# --------------------------------------------------------------------------

def phase_data(z: Sizes, seed: int) -> dict:
    import numpy as np
    from repro.data import synthetic
    t0 = time.perf_counter()
    c1, a1 = synthetic.nyc_taxi(scale=z.scale, seed=seed, dims=1)
    c3, a3 = synthetic.nyc_taxi(scale=z.scale, seed=seed + 1, dims=3)
    # streamed rows: a later draw from the same month, as f32 rows (what
    # the ingest path receives), in arrival order
    n_new = z.batch * z.batches
    scale_new = z.scale * 1.01 * n_new / max(c1.shape[0], 1)
    s1 = synthetic.nyc_taxi(scale=scale_new, seed=seed + 2, dims=1)
    s3 = synthetic.nyc_taxi(scale=scale_new, seed=seed + 3, dims=3)
    rng = np.random.default_rng(seed)
    new = {}
    for tag, (c, a) in (("1d", s1), ("3d", s3)):
        p = rng.permutation(c.shape[0])[:n_new]
        new[tag] = (np.asarray(c[p], np.float32).reshape(n_new, -1),
                    np.asarray(a[p], np.float32))
    phase_line("data", time.perf_counter() - t0, rows_1d=c1.shape[0],
               rows_3d=c3.shape[0], streamed_rows_each=n_new)
    return {"1d": (c1, a1), "3d": (c3, a3), "new": new}


def phase_build(z: Sizes, seed: int, data: dict) -> dict:
    from repro.core import build_synopsis
    syns = {}
    for tag, method in (("1d", "adp"), ("3d", "kd")):
        c, a = data[tag]
        t0 = time.perf_counter()
        syn, rep = build_synopsis(c, a, k=z.k, sample_budget=z.samples,
                                  method=method, seed=seed)
        phase_line(f"build {tag}", time.perf_counter() - t0, method=method,
                   k=rep.k, samples=rep.total_samples,
                   sample_shape=tuple(syn.sample_a.shape))
        syns[tag] = syn
    return syns


def make_engine(source):
    from repro.api import PassEngine, ServingConfig
    return PassEngine(source, serving=ServingConfig(kinds=KINDS), ci=0.95)


def serve(chk, eng, qs, tag):
    """answer(), prepare(), two more calls (the second AOT-compiles), then
    steady calls. Returns the last result."""
    _, t_first = timed(lambda: eng.answer(qs))
    prep = eng.prepare(qs)
    _, t_second = timed(lambda: prep(qs))
    steady = []
    for _ in range(3):
        res, t = timed(lambda: prep(qs))
        steady.append(t)
    aot = eng.stats()["aot_compiles"]
    phase_line(f"serve {tag}", t_first + t_second, min(steady),
               Q=qs.num_queries, jit_first_call_s=t_first,
               aot_second_call_s=t_second, aot_compiles=aot)
    chk.expect(aot >= 1, f"{tag}: prepared call AOT-compiled")
    return res


def phase_serve(chk, z: Sizes, seed: int, data: dict, syns: dict) -> dict:
    import numpy as np
    from repro.core.query import random_queries
    out = {}
    rng = np.random.default_rng(seed + 7)
    for tag in ("1d", "3d"):
        c, a = data[tag]
        eng = make_engine(syns[tag])
        qs = random_queries(c, z.queries, seed=seed + 11,
                            **QUERY_WIDTHS[tag])
        res = serve(chk, eng, qs, tag)
        t0 = time.perf_counter()
        ref = with_avg(truth(c, a, qs))
        log(f"reference {tag}: queries={qs.num_queries} "
            f"host_s={time.perf_counter() - t0!r}")
        check_accuracy(chk, tag, res, ref, MEDIAN_RELERR_BOUND if tag == "1d"
                       else MEDIAN_RELERR_BOUND_3D)
        cq, m, unclean = covered_queries(syns[tag], z.covered, rng)
        check_covered(chk, tag, eng.answer(cq), with_avg(truth(c, a, cq)),
                      m, unclean)
        out[tag] = {"engine": eng, "queries": qs, "ref": ref}
    return out


def phase_bootstrap(chk, z: Sizes, syns: dict, served: dict) -> None:
    """Fused bootstrap intervals: bit-identical to the per-replicate scan
    reference (``boot_fused=False``) on the same queries, and calibrated
    against the f64 reference."""
    from repro.api import CIConfig, PassEngine, ServingConfig

    def engine(fused):
        return PassEngine(syns["1d"], serving=ServingConfig(kinds=KINDS),
                          ci=CIConfig(method="bootstrap", n_boot=z.n_boot,
                                      boot_fused=fused))

    eng = engine(True)
    qs = take(served["1d"]["queries"], z.boot_queries)
    _, t_first = timed(lambda: eng.answer(qs))
    res, t_steady = timed(lambda: eng.answer(qs))
    scan = engine(False)
    _, t_scan_first = timed(lambda: scan.answer(qs))
    res_scan, t_scan = timed(lambda: scan.answer(qs))
    phase_line("bootstrap", t_first, t_steady, Q=z.boot_queries,
               R=z.n_boot, fused_serves=eng.stats()["fused_serves"],
               scan_setup_s=t_scan_first, scan_steady_s=t_scan)
    chk.expect(bit_equal(res, res_scan),
               "bootstrap: fused answer bit-identical to the scan path")
    n = z.boot_queries
    ref = {k: v[:n] for k, v in served["1d"]["ref"].items()}
    check_accuracy(chk, "bootstrap", res, ref, MEDIAN_RELERR_BOUND)


def phase_stream(chk, z: Sizes, seed: int, data: dict, syns: dict,
                 served: dict) -> None:
    import numpy as np
    from repro.streaming import StreamingIngestor
    rng = np.random.default_rng(seed + 13)
    for tag in ("1d", "3d"):
        c_new, a_new = data["new"][tag]
        ing = StreamingIngestor(syns[tag], seed=seed)
        eng = make_engine(ing)
        qs = served[tag]["queries"]
        eng.answer(qs)
        times = []
        for i in range(z.batches):
            sl = slice(i * z.batch, (i + 1) * z.batch)
            _, t = timed(lambda: ing.ingest(c_new[sl], a_new[sl]).state)
            times.append(t)
        phase_line(f"stream {tag}", times[0], min(times[1:]),
                   batches=z.batches, batch_rows=z.batch,
                   rows_per_s=z.batch / min(times[1:]), oob=ing.n_oob,
                   quarantined=ing.n_quarantined)
        chk.expect(ing.n_quarantined == 0, f"stream {tag}: no row quarantined")
        res, t_serve = timed(lambda: eng.answer(qs))
        log(f"stream {tag} serve after ingest: s={t_serve!r} "
            f"aot_compiles={eng.stats()['aot_compiles']}")
        ref = add_truth(served[tag]["ref"],
                        truth(c_new, a_new, qs))
        check_accuracy(chk, f"stream {tag}", res, ref,
                       MEDIAN_RELERR_BOUND if tag == "1d"
                       else MEDIAN_RELERR_BOUND_3D)
        syn = ing.as_synopsis()
        cq, m, unclean = covered_queries(syn, z.covered, rng)
        c, a = data[tag]
        ref_c = add_truth(truth(c, a, cq), truth(c_new, a_new, cq))
        check_covered(chk, f"stream {tag}", eng.answer(cq), ref_c, m, unclean)


def phase_coalesce(chk, z: Sizes, seed: int, served: dict) -> None:
    import numpy as np
    from repro.api import CoalescerConfig
    from repro.core.types import QueryBatch
    from repro.serve import RequestCoalescer
    eng = served["1d"]["engine"]
    qs = served["1d"]["queries"]
    rng = np.random.default_rng(seed + 17)
    sizes = [3 + 2 * i + int(rng.integers(0, 2)) for i in range(z.tenants)]
    starts = np.cumsum([0] + sizes)
    batches = {f"tenant-{i}": QueryBatch(qs.lo[starts[i]:starts[i + 1]],
                                         qs.hi[starts[i]:starts[i + 1]])
               for i in range(z.tenants)}
    co = RequestCoalescer(eng, CoalescerConfig(
        max_outstanding=z.tenants + 1, max_queue_depth=4 * z.tenants))

    def round_():
        futs = {t: co.submit(t, b) for t, b in batches.items()}
        co.tick()
        return {t: f.result(timeout=0) for t, f in futs.items()}

    _, t_first = timed(round_)
    got, t_steady = timed(round_)
    want = {t: eng.answer(b) for t, b in batches.items()}
    s = co.stats()
    phase_line("coalesce", t_first, t_steady, tenants=z.tenants,
               sizes=sizes, dispatches=s["dispatches"], ticks=s["ticks"])
    chk.expect(all(bit_equal(got[t], want[t]) for t in batches),
               "coalescer demux bit-identical to engine.answer")


def run_one_chip(chk: Checks, z: Sizes, seed: int) -> None:
    state = {}

    def step(name, fn):
        if any(need not in state for need in NEEDS[name]):
            chk.expect(False, f"phase {name}: skipped, an earlier phase "
                              "failed")
            return
        try:
            state[name] = fn()
        except Exception:                       # noqa: BLE001
            traceback.print_exc()
            chk.expect(False, f"phase {name}: raised")

    NEEDS = {"data": (), "build": ("data",), "serve": ("build",),
             "bootstrap": ("serve",), "stream": ("serve",),
             "coalesce": ("serve",)}
    step("data", lambda: phase_data(z, seed))
    step("build", lambda: phase_build(z, seed, state["data"]))
    step("serve", lambda: phase_serve(chk, z, seed, state["data"],
                                      state["build"]))
    step("bootstrap", lambda: phase_bootstrap(chk, z, state["build"],
                                              state["serve"]))
    step("stream", lambda: phase_stream(chk, z, seed, state["data"],
                                        state["build"], state["serve"]))
    step("coalesce", lambda: phase_coalesce(chk, z, seed, state["serve"]))


# --------------------------------------------------------------------------
# Four chips: the sharded synopsis against its one-chip mesh
# --------------------------------------------------------------------------

EXACT_COLS = (0, 2, 3, 4)          # SUM, COUNT, MIN, MAX (not SUMSQ)


def sharded_digests(syn) -> dict:
    """The DESIGN §11 device-count invariants of a served synopsis: leaf
    and tree aggregates, leaf boxes, tree boxes and row counts. SUMSQ is
    kept apart: per leaf it leaves the 2^24 exact-integer window of f32,
    where regrouped shard partials may round differently."""
    import numpy as np
    cols = np.array(EXACT_COLS)
    return {"leaf_agg": np.asarray(syn.leaf_agg)[:, cols],
            "leaf_lo": np.asarray(syn.leaf_lo),
            "leaf_hi": np.asarray(syn.leaf_hi),
            "tree_agg": np.asarray(syn.tree.agg)[:, cols],
            "tree_lo": np.asarray(syn.tree.lo),
            "tree_hi": np.asarray(syn.tree.hi),
            "n_rows": np.asarray(syn.n_rows),
            "leaf_sumsq": np.asarray(syn.leaf_agg)[:, 1]}


def run_sharded(chk: Checks, z: Sizes, seed: int, n_chips: int = 4) -> None:
    import numpy as np
    import jax
    from repro.api import PassEngine, ServingConfig
    from repro.core.query import random_queries
    from repro.data import synthetic
    from repro.sharded import data_mesh, reoptimize_sharded

    t0 = time.perf_counter()
    c, dist = synthetic.nyc_taxi(scale=z.scale, seed=seed, dims=1)
    # The sharded builder takes f32 rows; trip distances are recorded in
    # hundredths of a mile, so the measure is integer-valued and every
    # per-leaf SUM/COUNT partial is exact in f32 in any grouping.
    c = c.astype(np.float32)
    a = np.round(dist * 100.0).astype(np.float32)
    rng = np.random.default_rng(seed + 5)
    n_new = z.batch
    cs, ds = synthetic.nyc_taxi(scale=z.scale * 1.01 * n_new / c.shape[0],
                                seed=seed + 2, dims=1)
    p = rng.permutation(cs.shape[0])[:n_new]
    c_new = cs[p].astype(np.float32)
    a_new = np.round(ds[p] * 100.0).astype(np.float32)
    qs = random_queries(c, z.queries, seed=seed + 11)
    # the f64 host references run on a thread while the devices work
    pool = ThreadPoolExecutor(1)
    refs = pool.submit(lambda: (truth(c, a, qs), truth(c_new, a_new, qs)))
    pool.shutdown(wait=False)

    def ref_base():
        return with_avg(refs.result()[0])

    def ref_new():
        return add_truth(*refs.result())

    exact_sum = float(np.sum(a, dtype=np.float64)
                      + np.sum(a_new, dtype=np.float64))
    phase_line("sharded data", time.perf_counter() - t0, rows=c.shape[0],
               streamed_rows=n_new)

    out = {}
    for n_dev in (n_chips, 1):
        tag = f"D={n_dev}"
        mesh = data_mesh(n_dev)
        t0 = time.perf_counter()
        eng = PassEngine.from_sharded(
            c, a, k=z.k, mesh=mesh, sample_budget=z.samples, seed=seed,
            batch_rows=z.batch, serving=ServingConfig(kinds=KINDS), ci=0.95)
        ing = eng.source
        jax.block_until_ready(ing.state)
        t_build = time.perf_counter() - t0
        placed = {f: len(getattr(ing.state, f).sharding.device_set)
                  for f in ("sample_a", "delta_agg", "leaf_lo")}
        chk.expect(all(v == n_dev for v in placed.values()),
                   f"{tag}: sharded state spans all {n_dev} devices "
                   f"({placed})")
        built = sharded_digests(ing.as_synopsis())
        res = serve(chk, eng, qs, tag)
        check_accuracy(chk, f"sharded {tag}", res, ref_base(),
                       MEDIAN_RELERR_BOUND)
        cq, m, unclean = covered_queries(ing.as_synopsis(), z.covered,
                                         np.random.default_rng(seed + 23))
        res_c = eng.answer(cq)
        check_covered(chk, f"sharded {tag}", res_c,
                      with_avg(truth(c, a, cq)), m, unclean)

        _, t_first = timed(lambda: ing.ingest(c_new, a_new).state)
        streamed = sharded_digests(ing.as_synopsis())
        res_s = eng.answer(qs)
        check_accuracy(chk, f"sharded {tag} after ingest", res_s, ref_new(),
                       MEDIAN_RELERR_BOUND)

        t0 = time.perf_counter()
        ing2, rep = reoptimize_sharded(ing, np.concatenate([c, c_new]),
                                       np.concatenate([a, a_new]),
                                       seed=seed + 3, batch_rows=z.batch)
        eng.replace_source(ing2)
        syn2 = ing2.as_synopsis()
        jax.block_until_ready(syn2)
        t_reopt = time.perf_counter() - t0
        res_r = eng.answer(qs)
        check_accuracy(chk, f"sharded {tag} after reopt", res_r, ref_new(),
                       MEDIAN_RELERR_BOUND)
        # After the re-opt the cuts come from each mesh's own reservoir
        # pool, so leaves differ across D. COUNT, MIN and MAX of the root
        # stay exact; the root SUM leaves the 2^24 exact-integer window of
        # f32, so it is held to the exact total at f32 rounding instead.
        root_agg = np.asarray(syn2.tree.agg, np.float64)[0]
        root = root_agg[np.array(EXACT_COLS[1:])]
        sum_rel = abs(root_agg[0] - exact_sum) / exact_sum
        sum_bound = (rep["k"] + 1) * 2.0 ** -24
        log(f"sharded {tag} root SUM after reopt: relative error "
            f"{sum_rel!r} against the exact total (bound {sum_bound!r})")
        chk.expect(sum_rel <= sum_bound,
                   f"{tag}: root SUM after reopt equal to the exact total "
                   "at f32 rounding")
        phase_line(f"sharded {tag}", t_build + t_first + t_reopt,
                   build_s=t_build, ingest_first_s=t_first,
                   reopt_s=t_reopt, leaves_after_reopt=rep["k"])
        out[n_dev] = {"built": built, "streamed": streamed,
                      "root": root, "total": int(syn2.total_rows),
                      "res": res, "res_c": res_c, "cq": cq}

    a4, a1 = out[n_chips], out[1]
    for stage in ("built", "streamed"):
        for name in a4[stage]:
            x, y = a4[stage][name], a1[stage][name]
            if name == "leaf_sumsq":
                # f32 summation bound over the rows of the largest leaf
                bound = float(a1[stage]["n_rows"].max()) * 2.0 ** -24
                rel = float(np.max(np.abs(x.astype(np.float64) - y)
                                   / np.maximum(np.abs(y), 1.0)))
                log(f"sharded {stage} leaf SUMSQ max relative difference "
                    f"D={n_chips} vs D=1: {rel!r} (bound {bound!r})")
                chk.expect(rel <= bound,
                           f"{stage} leaf SUMSQ equal to f32 rounding "
                           "across D")
            else:
                chk.expect(np.array_equal(x, y),
                           f"{stage} {name} bit-identical across D")
    chk.expect(np.array_equal(a4["root"], a1["root"])
               and a4["total"] == a1["total"],
               "root COUNT/MIN/MAX after reopt bit-identical across D")
    chk.expect(np.array_equal(np.asarray(a4["cq"].lo),
                              np.asarray(a1["cq"].lo))
               and bit_equal(a4["res_c"], a1["res_c"]),
               "covered answers bit-identical across D")
    for kind in KINDS:
        e4 = np.asarray(a4["res"][kind].estimate, np.float64)
        e1 = np.asarray(a1["res"][kind].estimate, np.float64)
        diff = float(np.median(np.abs(e4 - e1)
                               / np.maximum(np.abs(ref_base()[kind]),
                                            1e-12)))
        log(f"sharded {kind}: median |est(D={n_chips}) - est(D=1)| / truth "
            f"= {diff!r}")
        chk.expect(diff <= 2 * MEDIAN_RELERR_BOUND,
                   f"sharded {kind}: answers agree across D")


# --------------------------------------------------------------------------

def refuse(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded phase, on a 4-chip mesh "
                        "against a 1-chip mesh")
    args = p.parse_args(argv)
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        return refuse("REPRO_KERNEL_BACKEND is set; the smoke runs the "
                      "kernels the platform selects")
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError:
        return refuse("run from a checkout of the repository "
                      "(src/repro not found)")
    import jax
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return refuse(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devices) < args.chips:
        return refuse(f"--chips {args.chips} but {len(devices)} device(s)")
    from repro.kernels.backends import interpret_mode
    from repro.kernels.registry import get_backend
    backend, interp = get_backend().name, interpret_mode()
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} backend={backend} interpret={interp} "
        f"jax={jax.__version__} compile_cache={cache}")
    if backend != "pallas" or interp:
        return refuse("the platform must select the compiled pallas "
                      "kernels")
    chk = Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(chk, FULL, args.seed)
    else:
        run_sharded(chk, FULL, args.seed, n_chips=args.chips)
    log(f"total_s={time.perf_counter() - t0!r} "
        f"setup_s_total={sum(SETUP_S)!r}")
    if chk.failed:
        log(f"{len(chk.failed)} check(s) failed:")
        for what in chk.failed:
            log(f"  {what}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
