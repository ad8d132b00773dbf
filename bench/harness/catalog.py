"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

- ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives),
- ``bench/traffic/<mix>.json``,
- ``bench/metrics/<metric>.py``, a module with ``read(ctx) -> float | None``;
- ``bench/planes/<reader>.py``, how the harness reads a plane (the
  configuration's optional ``"reader"``, default ``centralized``): a module
  with ``attach(cp, on_drop)``, ``live(cp)``, ``is_live(cp, rid)`` and
  ``committed_share(cp)``;
- ``bench/references/<reference>.py``, what the configuration promises for
  a request's first placement (its optional ``"reference"``, default
  ``leastcost``): a module with ``first_cost(ref, r, *, max_supersteps=None)``.

A new cell, mix, metric, plane reader or reference is new files and new
entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "bench")


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _module(kind: str, name: str, bench_dir: str):
    """The module ``bench/<kind>/<name>.py``; a missing file fails here,
    naming the path looked for."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    return _module("metrics", name, bench_dir).read


def reader(config: dict, bench_dir: str = BENCH_DIR):
    """The module that reads the configuration's plane."""
    return _module("planes", config.get("reader", "centralized"), bench_dir)


def reference(config: dict, bench_dir: str = BENCH_DIR):
    """The ``first_cost`` the configuration's placements are judged by."""
    return _module("references", config.get("reference", "leastcost"),
                   bench_dir).first_cost


def metrics_of(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it, and those that list no cells.  A per-layer metric without
    a list goes with the cells that report the metric it moves."""
    entries = bench[kind]
    chosen = []
    for m in entries:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                chosen.append(m)
        elif kind == "end_to_end":
            chosen.append(m)
        elif m["moves"] in {e["name"] for e in metrics_of(bench, cell_name,
                                                          "end_to_end")}:
            chosen.append(m)
    return chosen
