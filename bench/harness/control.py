"""The control and the planted faults: the timed path broken underneath,
the rest of a run as it is. Each must come out as not correct.

* ``precision``: the step that would tempt a later PR. Every matmul the
  program asks for at ``Precision.HIGHEST`` (the exact aggregates of
  ``query_eval`` and ``segment_reduce``) takes one bf16 pass instead: the
  operands are rounded to bf16 and accumulated in float32, which is what
  the MXU does at the default precision. ``Precision.HIGH`` (three passes)
  would be nearer, but the chip's kernel compiler refuses it in a Pallas
  kernel ("Unsupported dot precision: HIGH"), so one pass is the nearest
  precision below that the program's kernels can take.
* ``answer_altered``: every SUM estimate is scaled by 1 + 2^-10 where the
  coalescer pulls a dispatch's result to the host.
* ``ci_halved``: every interval is shrunk to half its width about its
  estimate where the coalescer pulls the result (a variance taken over
  the wrong sample count would do as much).
* ``half_batch``: the second half of every dispatch's real rows is replaced
  by padding before the device sees it, so those queries go unanswered
  while their requests still resolve.
* ``state_unchanged``: ``StreamingIngestor.ingest`` returns with its state
  unchanged (the epoch still advances), in an ingest cell.
* ``stale_merge``: ``StreamingIngestor.ingest`` keeps the merged serving
  synopsis it had cached before the batch, as a merge that raced the
  batch would: reads go on being served from a state that lacks
  acknowledged batches.

Run on the chip, one process per mode, at the cell's own size:

    python3 bench/harness/control.py --workload taxi1d.dash \\
        --mode precision --seeds 1,2,3 --seconds 20
"""
import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

MODES = ("precision", "answer_altered", "ci_halved", "half_batch",
         "state_unchanged", "stale_merge")


@contextlib.contextmanager
def planted(mode: str):
    """Plant ``mode`` in the program for the duration of the block."""
    import jax
    import jax.numpy as jnp
    jax.clear_caches()
    undo = []
    if mode == "precision":
        orig = jax.lax.dot_general

        def one_pass(lhs, rhs, *args, precision=None, **kw):
            if precision in (jax.lax.Precision.HIGHEST,
                             (jax.lax.Precision.HIGHEST,) * 2):
                lhs = lhs.astype(jnp.bfloat16)
                rhs = rhs.astype(jnp.bfloat16)
                precision = None
                kw.setdefault("preferred_element_type", jnp.float32)
            return orig(lhs, rhs, *args, precision=precision, **kw)

        jax.lax.dot_general = one_pass
        undo.append(lambda: setattr(jax.lax, "dot_general", orig))
    elif mode in ("answer_altered", "ci_halved"):
        from repro.serve import coalescer
        orig_pull = coalescer._pull_host
        field = [f.name for f in dataclasses.fields(coalescer.QueryResult)]
        est, lo, hi = (field.index(n) for n in ("estimate", "ci_lo", "ci_hi"))

        def altered(results):
            host = orig_pull(results)
            for kind, arrs in host.items():
                if mode == "answer_altered" and kind == "sum":
                    arrs[est] = arrs[est] * (1.0 + 2.0 ** -10)
                elif mode == "ci_halved":
                    arrs[lo] = arrs[est] - (arrs[est] - arrs[lo]) / 2
                    arrs[hi] = arrs[est] + (arrs[hi] - arrs[est]) / 2
            return host

        coalescer._pull_host = altered
        undo.append(lambda: setattr(coalescer, "_pull_host", orig_pull))
    elif mode == "half_batch":
        from repro.serve import coalescer
        cls = coalescer.RequestCoalescer
        orig_mux = cls._mux

        def half(self, group, padded_b, d):
            qb = orig_mux(self, group, padded_b, d)
            rows = sum(p.rows for p in group)
            keep = (rows + 1) // 2
            idx = jnp.arange(padded_b)[:, None]
            lo = jnp.where(idx < keep, qb.lo, coalescer.PAD_LO)
            hi = jnp.where(idx < keep, qb.hi, coalescer.PAD_HI)
            return type(qb)(lo, hi)

        cls._mux = half
        undo.append(lambda: setattr(cls, "_mux", orig_mux))
    elif mode == "state_unchanged":
        from repro.streaming import ingest as ingest_mod
        cls = ingest_mod.StreamingIngestor
        orig_ingest = cls.ingest

        def unchanged(self, c_rows, a_vals, u=None):
            state = self.state
            out = orig_ingest(self, c_rows, a_vals, u)
            self.state = state
            return out

        cls.ingest = unchanged
        undo.append(lambda: setattr(cls, "ingest", orig_ingest))
    elif mode == "stale_merge":
        from repro.streaming import ingest as ingest_mod
        cls = ingest_mod.StreamingIngestor
        orig_ingest = cls.ingest

        def keeps_merge(self, c_rows, a_vals, u=None):
            merged = self._merged
            out = orig_ingest(self, c_rows, a_vals, u)
            self._merged = merged
            return out

        cls.ingest = keeps_merge
        undo.append(lambda: setattr(cls, "ingest", orig_ingest))
    else:
        raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
    try:
        yield
    finally:
        for u in reversed(undo):
            u()
        jax.clear_caches()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--mode", required=True, choices=MODES)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench.harness import device as _device
    _device.use_checkout_cache(ROOT)
    from repro.compile_cache import enable_compile_cache
    from bench.harness import cells, device
    from bench.harness.measure import measure
    cell = cells.resolve(ROOT, args.workload)
    enable_compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in [int(s) for s in args.seeds.split(",")]:
        with planted(args.mode):
            out = measure(cell, seed, args.seconds, False, devices,
                          time.perf_counter(), log=lambda _m: None)
        print("control " + json.dumps({"workload": cell.name,
                                       "mode": args.mode, "seed": seed,
                                       "correct": out["correct"],
                                       "checks": out["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
