"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its own:

- ``bench/configs/<config>.json``: the model as it is run (net, sizes,
  precision, weight recipe);
- ``bench/reference/<config>.py``: its plain reference;
- ``bench/traffic/<traffic>.json``: the load (batch, loop, input pool);
- ``bench/cells/<cell>.json``: the limits of the output comparison;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a cell, a configuration or a per-layer metric is adding files of
these kinds and entries to ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    pass


def _json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {os.path.relpath(path, ROOT)}")


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def reference(name: str):
    """The configuration's plain reference module."""
    return _module(os.path.join(BENCH_DIR, "reference", f"{name}.py"),
                   f"bench_reference_{name.replace('-', '_')}")


def traffic(name: str) -> dict:
    t = _json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))
    if t.get("loop") != "closed":
        raise SpecError(f"traffic {name!r}: loop {t.get('loop')!r} is not "
                        f"one the generator runs ('closed')")
    for key in ("batch", "pool", "sample"):
        if not (isinstance(t.get(key), int) and t[key] >= 1):
            raise SpecError(f"traffic {name!r}: {key} must be a whole "
                            f"number >= 1")
    return t


def cell_limits(name: str) -> Dict[str, float]:
    return _json(os.path.join(BENCH_DIR, "cells", f"{name}.json"))["limits"]


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of one per-layer metric."""
    return _module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                   f"bench_metric_{name.replace('-', '_').replace('.', '_')}"
                   ).read


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict = None) -> Cell:
    """The cell ``name`` with its files and the metrics it reports."""
    bench = benchmark() if bench is None else bench
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = rows[0]
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                traffic_name=w["traffic"], config=config(w["config"]),
                traffic=traffic(w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])
