"""Benchmark driver: one module per paper table/figure + kernels.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig6]

Prints per-benchmark rows plus a final ``name,us_per_call,derived`` CSV
summary line per benchmark (wall time per row and the headline metric).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from . import (table1_accuracy, fig3_partitions, fig4_samplerate,
                   fig6_adversarial, fig7_challenging, fig8_multidim,
                   fig9_workload_shift, table3_preproc, bench_kernels)
    benches = {
        "table1": table1_accuracy.run,
        "fig3": fig3_partitions.run,
        "fig4_5": fig4_samplerate.run,
        "fig6": fig6_adversarial.run,
        "fig7": fig7_challenging.run,
        "fig8": fig8_multidim.run,
        "fig9": fig9_workload_shift.run,
        "table3": table3_preproc.run,
        "kernels": bench_kernels.run,
    }
    only = set(args.only.split(",")) if args.only else None
    csv = ["name,us_per_call,derived"]
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"\n=== {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            rows = fn()
            dt = time.perf_counter() - t0
            derived = f"rows={len(rows) if rows is not None else 0}"
            csv.append(f"{name},{dt * 1e6 / max(len(rows or [1]), 1):.0f},"
                       f"{derived}")
        except Exception as e:  # keep the suite running; record the failure
            dt = time.perf_counter() - t0
            print(f"  FAILED: {type(e).__name__}: {e}")
            csv.append(f"{name},{dt*1e6:.0f},FAILED:{type(e).__name__}")
    print("\n" + "\n".join(csv))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
