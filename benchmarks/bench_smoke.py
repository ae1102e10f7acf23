"""CI bench-smoke: tiny-config perf runs -> BENCH_pr.json.

Runs the PASS serving hillclimb (incl. the prepared-query steady-state
case), the streaming ingest benchmark, the distributed psum-merge case,
and the CI-calibration + build-path smoke in their CI-sized configs and
writes a flat metric JSON. ``check_regression`` compares it against the
checked-in ``BENCH_baseline.json`` (fails on >2x regression on
wall-clock/speedup metrics; coverage metrics are informational). The
calibration table is written next to the metrics JSON
(``CI_calibration.json``) and uploaded as a workflow artifact. Locally:

    PYTHONPATH=src python -m benchmarks.bench_smoke [out.json]
    PYTHONPATH=src python -m benchmarks.check_regression BENCH_pr.json
"""
from __future__ import annotations

import json
import pathlib
import platform
import sys

from . import bench_coalescer
from . import bench_degrade
from . import bench_distributed
from . import bench_fused
from . import bench_joins
from . import bench_partitions
from . import bench_streaming_ingest
from . import fig_ci_calibration
from . import perf_pass_serving


def run() -> tuple[dict, list]:
    serve_rows, serve_speedups = perf_pass_serving.run(
        **perf_pass_serving.tiny_config())
    stream = bench_streaming_ingest.run(**bench_streaming_ingest.tiny_config())
    metrics = dict(stream)
    # serving wall-clock per iteration label + the headline speedups
    for name, t in serve_rows:
        key = name.split("(")[0]                  # strip dynamic suffixes
        metrics[f"serving_{key}_ms"] = t * 1e3
    metrics.update(serve_speedups)
    # fused hot paths: bootstrap megakernel + tiled multi-D router
    metrics.update(bench_fused.run(**bench_fused.tiny_config()))
    # multi-tenant coalesced serving (demux bit-identity asserted inside)
    metrics.update(bench_coalescer.run(**bench_coalescer.tiny_config()))
    # deadline-degraded tier-0 first answer (bit-identity asserted inside)
    metrics.update(bench_degrade.run(**bench_degrade.tiny_config()))
    # fk-join serving vs materialized-join scan at matched error
    metrics.update(bench_joins.run(**bench_joins.tiny_config()))
    # partition-selection tier vs flat full-lake build (clustered lake)
    metrics.update(bench_partitions.run(**bench_partitions.tiny_config()))
    # multi-device serving path: psum merge of the mergeable summaries
    metrics.update(bench_distributed.run(**bench_distributed.tiny_config()))
    # sharded-ingest weak scaling over data_mesh(1/2/4), in this process
    # (needs 4 visible devices: forced host devices on the CPU)
    metrics.update(bench_distributed.run_scale(
        **bench_distributed.tiny_scale_config()))
    # uncertainty smoke: empirical coverage + the build-path wall clock
    cal_metrics, cal_rows = fig_ci_calibration.run(
        **fig_ci_calibration.tiny_config())
    metrics.update(cal_metrics)
    return metrics, cal_rows


def main(out_path: str = "BENCH_pr.json") -> None:
    metrics, cal_rows = run()
    payload = {
        "metrics": metrics,
        "meta": {"python": platform.python_version(),
                 "machine": platform.machine(),
                 "config": "tiny"},
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {out_path} ({len(metrics)} metrics)")
    cal_path = pathlib.Path(out_path).with_name("CI_calibration.json")
    with open(cal_path, "w") as f:
        json.dump({"table": cal_rows}, f, indent=2, sort_keys=True)
    print(f"wrote {cal_path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(*sys.argv[1:2])
