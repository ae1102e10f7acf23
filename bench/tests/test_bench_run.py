"""``bench/run.py`` refuses to measure where it cannot: no TPU, or a
directory without the system under test. It prints no result then."""
import os
import shutil
import subprocess
import sys

import _paths


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "taxi1d.closed",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_refuses_without_a_tpu():
    p = _run(_paths.ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(_paths.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(str(tmp_path)))
