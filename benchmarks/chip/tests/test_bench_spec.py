"""Lookup by name: a configuration, a mix or a per-layer metric is found
from its file alone, and the committed BENCHMARK.json is complete."""
import json
import os
import subprocess
import sys

import pytest

import spec


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


def test_new_files_are_found_with_no_edit(tmp_path):
    here = str(tmp_path)
    _write(os.path.join(here, "configs", "tiny-x.json"),
           {"dim": 2, "functions": {"sphere": [-1, 1]}})
    _write(os.path.join(here, "traffic", "trickle.json"),
           {"loop": "open", "rate_per_s": 1.0})
    _write(os.path.join(here, "layer_metrics", "new.metric.py"),
           "def read(ctx):\n    return ctx['x'] * 2\n")
    bench = {
        "workloads": [{"name": "tiny-x.trickle", "config": "tiny-x",
                       "traffic": "trickle", "chips": 1, "why": "t"}],
        "end_to_end": [{"name": "setup_s"},
                       {"name": "only_elsewhere", "workloads": ["other"]}],
        "per_layer": [{"name": "new.metric",
                       "workloads": ["tiny-x.trickle"]}],
    }
    cell = spec.load_cell("tiny-x.trickle", bench, here)
    assert cell.config["dim"] == 2 and cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert spec.load_reader("new.metric", here).read({"x": 21}) == 42
    assert spec.functions(cell.config) == {"sphere": (-1.0, 1.0)}


@pytest.mark.parametrize("bad", ["../configs/x", "/etc/passwd", ".hidden",
                                 "a/b", ""])
def test_names_cannot_leave_their_directory(bad):
    with pytest.raises(ValueError):
        spec.load_config(bad)


def test_committed_cells_have_all_their_pieces():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.config["correct"]["limits"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]).read)
    for c in bench["configs"]:
        assert os.path.normpath(c["file"]) == os.path.relpath(
            os.path.join(spec.HERE, "configs", c["name"] + ".json"),
            spec.ROOT)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "cell.py"), "--workload",
         "t4-cubic1d.solo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    import shutil
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/cell.py", "--workload",
         "t4-cubic1d.solo", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
