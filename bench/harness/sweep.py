"""Find the knee of an open-loop cell: one process, one build, a ladder of
fixed arrival rates, a short window at each.

    python3 bench/harness/sweep.py --workload taxi1d.dash --seed 11 \\
        --rates 500,1000,2000,4000 --seconds 8

Prints one line per rate: requests, shed, latency p50/p95 over all
requests due, and the p50 of the last quarter of the window against the
first (a growing backlog shows as a rising ratio). The knee is the highest
rate with nothing shed and no growing backlog; the cell's rate is 4/5 of
it, written into its traffic file as a number. Needs the chip, like
``bench/run.py``; the benchmark's own runs never sweep.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench.harness import device as _device
    _device.use_checkout_cache(ROOT)
    import numpy as np
    from repro.compile_cache import enable_compile_cache
    from bench.harness import cells, device, runner
    cell = cells.resolve(ROOT, args.workload)
    enable_compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    run = runner.Run(cell, args.seed, args.seconds, False, devices=devices)
    run.make_data()
    run.build()
    run.warm_up()
    print(f"sweep {cell.name}: setup_s={time.perf_counter() - T_START!r}",
          flush=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        run.mix = dict(cell.traffic, arrivals=dict(cell.traffic["arrivals"],
                                                   rate_per_s=rate))
        run.requests = []
        run.window()
        run.stop()
        reqs = run.requests
        never = run.t_closed + runner.WAIT_PAST_CLOSE_S
        lat = np.array([((r.t_done if r.result is not None else never)
                         - r.t_due) * 1e3 for r in reqs])
        due = np.array([r.t_due - run.t0 for r in reqs])
        q = args.seconds / 4
        first = lat[due < q]
        last = lat[due >= 3 * q]
        growth = (float(np.median(last) / np.median(first))
                  if first.size and last.size else None)
        row = {"rate_per_s": rate, "requests": len(reqs),
               "queries_per_s": sum(r.qidx.size for r in reqs)
               / args.seconds,
               "shed": int(sum(r.shed for r in reqs)),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "last_over_first_p50": growth,
               "dispatches": run.co_after["dispatches"]
               - run.co_before["dispatches"]}
        rows.append(row)
        print("sweep " + json.dumps(row), flush=True)
    print(json.dumps({"workload": cell.name, "sweep": rows,
                      "device": device.describe(devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
