"""Cells resolve by name from files alone, the committed ones and a cell
defined only in a temporary directory."""
import json
import os
import shutil

import _paths
import pytest

from bench.harness import cells


def test_every_committed_cell_resolves():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = cells.resolve(_paths.ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names
        assert set(cell.readers) == names
        assert any(m["name"] != "setup_s" for m in cell.end_to_end)
        assert cell.per_layer


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve(_paths.ROOT, "no.such.cell")


def test_new_cell_from_files_alone(tmp_path):
    """A cell, configuration, mix and metric that exist only as new files
    in another directory resolve by name, with no edit of any file."""
    bench = tmp_path / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "metrics").mkdir()
    with open(os.path.join(_paths.BENCH, "configs", "nyc_taxi_1d.json")) as f:
        config = json.load(f)
    config["name"] = "tiny_taxi"
    (bench / "configs" / "tiny_taxi.json").write_text(json.dumps(config))
    mix = {"kind": "generator", "loop": "open",
           "arrivals": {"process": "poisson", "rate_per_s": 3.0},
           "ci_level": 0.9}
    (bench / "traffic" / "trickle.json").write_text(json.dumps(mix))
    shutil.copy(os.path.join(_paths.BENCH, "traffic", "generator.py"),
                bench / "traffic" / "generator.py")
    (bench / "metrics" / "answered.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    shutil.copy(os.path.join(_paths.BENCH, "metrics", "setup_s.py"),
                bench / "metrics" / "setup_s.py")
    (bench / "metrics" / "fill.py").write_text(
        "def read(ctx):\n    return None\n")
    spec = {"configs": [{"name": "tiny_taxi", "source": "x",
                         "file": "bench/configs/tiny_taxi.json",
                         "reduced": [], "why": "x"}],
            "workloads": [{"name": "tiny.trickle", "config": "tiny_taxi",
                           "traffic": "trickle", "chips": 1, "why": "x"}],
            "end_to_end": [{"name": "setup_s", "unit": "s"},
                           {"name": "answered", "unit": "1",
                            "workloads": ["tiny.trickle"]},
                           {"name": "answered_elsewhere", "unit": "1",
                            "workloads": ["other.cell"]}],
            "per_layer": [{"name": "fill.trickle", "unit": "%"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.resolve(str(tmp_path), "tiny.trickle")
    assert cell.config["name"] == "tiny_taxi"
    assert cell.traffic["arrivals"]["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "answered"]
    assert cell.readers["answered"].read(None) == 7.0
    # a dotted metric name falls back to the reader of its prefix
    assert cell.readers["fill.trickle"].read(None) is None
    due = cell.generator.open_schedule(cell.traffic, 10.0, seed=3)
    assert 0 < due.size < 100
