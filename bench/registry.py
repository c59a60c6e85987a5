"""Find a cell's pieces by name, from files alone.

A cell is an entry of `BENCHMARK.json`'s `workloads`. It names a
configuration (its `file` in `configs`, a JSON deployment) and a traffic
mix (`bench/traffic/<traffic>.json`, parameters only). The traffic file
names its generator (`bench/generators/<generator>.py`), the general code
that reads those parameters. Each per-layer metric is read by
`bench/metrics/<metric>.py`. Adding a cell, a deployment, a mix or a
metric adds files and entries; no list in code is edited.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from types import ModuleType

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: ModuleType
    end_to_end: list[dict]        # the cell's end-to-end metrics
    per_layer: list[dict]         # the cell's per-layer metrics
    root: str


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, tag: str) -> ModuleType:
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {tag} file {path}")
    name = "bench_" + tag + "_" + re.sub(r"\W", "_", os.path.basename(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_generator(root: str, name: str) -> ModuleType:
    return _load_module(os.path.join(root, "bench", "generators",
                                     name + ".py"), "generator")


def load_metric(root: str, name: str) -> ModuleType:
    return _load_module(os.path.join(root, "bench", "metrics",
                                     name + ".py"), "metric")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Everything `run.py` needs for cell `name`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in layer if m["moves"] in moved]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, generator=load_generator(root, traffic["generator"]),
                end_to_end=e2e, per_layer=layer, root=root)


# -- deployment helpers shared by the generators and the reference -------------
def adjacency_of(graph: dict, num_nodes: int) -> np.ndarray:
    """[J, J] bool adjacency of a configuration's `graph`."""
    if graph["kind"] != "circulant":
        raise ValueError(f"unknown graph kind {graph['kind']!r}")
    a = np.zeros((num_nodes, num_nodes), dtype=bool)
    for j in range(num_nodes):
        for s in graph["offsets"]:
            a[j, (j + s) % num_nodes] = True
            a[j, (j - s) % num_nodes] = True
    return a


def feature_widths(kind: str, dbar: int, sizes: list[int]) -> list[int]:
    """D_j per node: "equal" (D̄ each) or "sqrt_n" (paper §IV-B2:
    D_j = √N_j·J·D̄ / Σ√N_j, rounded, at least 4)."""
    if kind == "equal":
        return [int(dbar)] * len(sizes)
    if kind == "sqrt_n":
        w = np.sqrt(np.asarray(sizes, float))
        d = np.maximum((w * len(sizes) * dbar / w.sum()).round(), 4)
        return [int(v) for v in d]
    raise ValueError(f"unknown feature widths {kind!r}")


def seed32(seed: int) -> int:
    """A 32-bit key seed from any whole-number `--seed`."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
