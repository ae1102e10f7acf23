"""A whole run, past the look for a chip, at a size a test can hold, with
the timed path broken underneath: ``correct`` must come out false for the
control (one bf16 pass where the program asks for HIGHEST) and for every
fault the cells can have. A sound run at the same size comes out true.

Besides the committed cell, the cells left out of ``BENCHMARK.json`` for
now (``taxi1d.dash``, ``taxi1d.ingest``) run from their committed files,
and ``taxi3d.closed`` from a configuration that lives only in the test's
root: the paper's multi-dimensional taxi deployment as the repository's
Figure 8 reproduction sets it (KD-PASS, 64 leaves, 2% proportional
samples, widths of 30-80% of the rows per column, d = 3)."""
import contextlib
import json
import os
import threading
import time

import _paths
import pytest

from bench.harness import cells, control, runner
from bench.harness.measure import measure

TINY = runner.Sizes(rows=40_000, k=64, samples=8192, query_pool=2048)
LEFT_OUT = {"taxi1d.dash": "dash", "taxi1d.ingest": "ingest"}


def nyc_taxi_3d() -> dict:
    with open(os.path.join(_paths.BENCH, "configs", "nyc_taxi_1d.json")) as f:
        config = json.load(f)
    config["name"] = "nyc_taxi_3d"
    config["data"] = dict(config["data"], dims=3, predicate_columns=[
        "pickup_time_min", "dropoff_time_min", "pickup_location_id"])
    config["synopsis"] = {"method": "kd", "k": 64, "sample_rate": 0.02,
                          "allocation": "proportional"}
    config["query_widths"] = {"min_frac": 0.3, "max_frac": 0.8}
    return config


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    """A root whose BENCHMARK.json holds the committed cells and the ones
    left out, resolving to the committed files under ``bench/``, and
    ``taxi3d.closed`` with its configuration in the root."""
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        c["file"] = os.path.join(_paths.ROOT, c["file"])
    spec["workloads"] += [{"name": n, "config": "nyc_taxi_1d",
                           "traffic": t, "chips": 1}
                          for n, t in LEFT_OUT.items()]
    root = tmp_path_factory.mktemp("spec")
    (root / "nyc_taxi_3d.json").write_text(json.dumps(nyc_taxi_3d()))
    spec["configs"].append({"name": "nyc_taxi_3d",
                            "file": str(root / "nyc_taxi_3d.json")})
    spec["workloads"].append({"name": "taxi3d.closed",
                              "config": "nyc_taxi_3d", "traffic": "closed",
                              "chips": 1})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def _run(spec_root, cell_name, seed=20260, seconds=1.0, backend=None):
    cell = cells.resolve(spec_root, cell_name, bench_dir=_paths.BENCH)
    if "ingest" in cell.traffic:
        cell.traffic = dict(cell.traffic, ingest=dict(
            cell.traffic["ingest"], batch_rows=8192, pool_batches=3))
    old = os.environ.get("REPRO_KERNEL_BACKEND")
    if backend is not None:
        os.environ["REPRO_KERNEL_BACKEND"] = backend
    try:
        return measure(cell, seed, seconds, False, None, time.perf_counter(),
                       sizes=TINY, log=lambda _m: None)
    finally:
        if backend is not None:
            if old is None:
                os.environ.pop("REPRO_KERNEL_BACKEND")
            else:
                os.environ["REPRO_KERNEL_BACKEND"] = old


@pytest.mark.parametrize("cell_name", ["taxi1d.dash", "taxi1d.closed",
                                       "taxi3d.closed"])
def test_sound_run_is_correct(spec_root, cell_name):
    out = _run(spec_root, cell_name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@contextlib.contextmanager
def _serialized_ingest():
    """``StreamingIngestor.ingest`` and ``as_synopsis`` under one lock: the
    program's merge cache then cannot race a batch."""
    from repro.streaming import ingest as ingest_mod
    cls = ingest_mod.StreamingIngestor
    lock = threading.Lock()
    orig = {n: getattr(cls, n) for n in ("ingest", "as_synopsis")}

    def locked(fn):
        def call(self, *a, **kw):
            with lock:
                return fn(self, *a, **kw)
        return call

    for n, fn in orig.items():
        setattr(cls, n, locked(fn))
    try:
        yield
    finally:
        for n, fn in orig.items():
            setattr(cls, n, fn)


def test_serialized_ingest_run_is_correct(spec_root):
    """The ingest cell is left out of BENCHMARK.json: unserialized, a read
    can be served from a merge that missed an acknowledged batch. With the
    two calls serialized the same run is correct; the read rate is a tenth
    of dash's, so a longer window gives a few hundred intervals."""
    with _serialized_ingest():
        out = _run(spec_root, "taxi1d.ingest", seconds=4.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["stale_reads"]["value"] == 0


@pytest.mark.parametrize("cell_name,mode,number", [
    ("taxi1d.dash", "answer_altered", "covered_err_ulp"),
    ("taxi1d.dash", "ci_halved", "ci_miss"),
    ("taxi1d.dash", "half_batch", "bound_miss"),
    ("taxi1d.closed", "answer_altered", "covered_err_ulp"),
    ("taxi1d.closed", "ci_halved", "ci_miss"),
    ("taxi1d.closed", "half_batch", "bound_miss"),
    ("taxi3d.closed", "answer_altered", "covered_err_ulp"),
    ("taxi3d.closed", "half_batch", "bound_miss"),
    ("taxi1d.ingest", "state_unchanged", "bound_miss"),
    ("taxi1d.ingest", "state_unchanged", "stale_reads"),
    ("taxi1d.ingest", "stale_merge", "stale_reads"),
])
def test_fault_is_not_correct(spec_root, cell_name, mode, number):
    with control.planted(mode):
        out = _run(spec_root, cell_name)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


def test_control_precision_is_not_correct(spec_root):
    """The pallas kernels (interpreted here) with one bf16 pass in place of
    HIGHEST: the covered probe's exactness fails; the same run at HIGHEST
    passes."""
    sound = _run(spec_root, "taxi1d.closed", backend="pallas", seconds=0.5)
    assert sound["correct"], sound["checks"]
    with control.planted("precision"):
        out = _run(spec_root, "taxi1d.closed", backend="pallas",
                   seconds=0.5)
    c = out["checks"]["covered_err_ulp"]
    assert not out["correct"] and c["value"] > c["limit"], out["checks"]
