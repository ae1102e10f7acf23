"""Run one cell of the benchmark on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is resolved from ``BENCHMARK.json``
to the files under ``bench/`` by name (``harness/cells.py``). One process
does everything: it turns on JAX's persistent compile cache in the
checkout's ``.jax_cache``, refuses to run without the TPU chips the cell
asks for and the compiled Pallas kernels, builds the deployment from
``--seed``, warms up, drives the traffic for ``--seconds``, and checks
every answer against the float64 reference. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
The same numbers are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def refuse(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench.harness import cells, device
    device.use_checkout_cache(ROOT)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError:
        return refuse("the system under test (src/repro) is not here")
    try:
        cell = cells.resolve(ROOT, args.workload)
    except (KeyError, FileNotFoundError) as e:
        return refuse(str(e))
    cache = enable_compile_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoDevice as e:
        return refuse(str(e))
    import jax
    print(f"bench: workload={cell.name} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} "
          f"device={device.describe(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    from bench.harness.measure import measure
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  T_START)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
