"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names every cell, with its
configuration and traffic mix, and every metric. Each piece lives in a
file of its own under this directory, found from its name alone:

* ``configs/<config>.json``: the deployment (shapes, budget, variant, the
  semantics its answers are held to and the limits of the comparison);
* ``traffic/<mix>.json``: the parameters the one generator in
  ``traffic.py`` reads;
* ``layer_metrics/<metric>.py``: the reader of one per-layer metric.

So a later change adds a configuration, a mix or a metric by adding its
file and its entry, and edits nothing here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.normpath(os.path.join(HERE, os.pardir, os.pardir))

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    """A name from ``BENCHMARK.json`` as a file name: no separators, no
    leading dot, so it cannot reach outside its directory."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_config(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "configs", _checked(name) + ".json"))


def load_traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", _checked(name) + ".json"))


def load_reader(name: str, here: str = HERE):
    """The per-layer reader module ``layer_metrics/<name>.py``. Its
    ``read(ctx)`` returns the metric's value, or None where the run had
    nothing for it to read."""
    path = os.path.join(here, "layer_metrics", _checked(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench: dict = None, here: str = HERE) -> Cell:
    bench = load_benchmark() if bench is None else bench
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_config(w["config"], here),
        traffic=load_traffic(w["traffic"], here),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def functions(config: dict) -> Dict[str, tuple]:
    """``{objective: (lo, hi)}`` of a configuration, in file order."""
    return {k: (float(v[0]), float(v[1]))
            for k, v in config["functions"].items()}
