"""Find a cell's pieces by name: ``BENCHMARK.json`` plus one file per piece.

``configs/<config>.json``  the deployment (``BENCHMARK.json`` names the file)
``traffic/<mix>.json``     the traffic mix (``traffic.py`` reads it)
``cells/<cell>.json``      the cell's limits, and its ``(n_node, n_core)``
                           mesh where it is not ``(chips, 1)``
``metrics/<metric>.py``    one reader per per-layer metric: ``read(ctx)``
                           returns a number, or None where it finds nothing

A later change adds a piece as a new file and new ``BENCHMARK.json``
entries, and edits no file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = os.path.basename(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    mesh: tuple[int, int]
    config: dict
    mix: dict
    limits: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(name: str, root: str = ROOT) -> Cell:
    """Everything one cell runs with, found by the names in
    ``BENCHMARK.json``."""
    from traffic import load_mix

    bench = load_benchmark(root)
    w = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], w["config"], "config")
    here = os.path.join(root, NAME)
    cell_file = _load_json(os.path.join(here, "cells", f"{name}.json"))
    mesh = tuple(cell_file.get("mesh", (w["chips"], 1)))
    if len(mesh) != 2 or mesh[0] * mesh[1] != w["chips"]:
        raise ValueError(f"cell {name}: mesh {mesh} does not hold "
                         f"{w['chips']} chips")
    e2e = tuple(m for m in bench["end_to_end"]
                if name in m.get("workloads", (name,)))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if (name in m["workloads"] if "workloads" in m
                          else m["moves"] in e2e_names))
    return Cell(name=name, chips=int(w["chips"]), mesh=mesh,
                config=_load_json(os.path.join(root, conf["file"])),
                mix=load_mix(os.path.join(here, "traffic",
                                          f"{w['traffic']}.json")),
                limits=cell_file["limits"], end_to_end=e2e,
                per_layer=per_layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = os.path.join(root, NAME, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
