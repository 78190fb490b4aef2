"""What a cell is made of, found by the names in BENCHMARK.json.

A configuration is the JSON file its `configs` entry names, a traffic mix is
`bench/traffic/<traffic>.json`, a per-layer metric is `bench/metrics/<name>.py`
(a `read(ctx)` function) and the chip peaks are `bench/peaks.json`. A later
cell, mix or metric is added as new files plus new BENCHMARK.json entries;
nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str = ROOT        # checkout whose bench/ holds the metric readers


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of BENCHMARK.json, with its config and traffic read."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(root, conf["file"])),
        traffic=_json(os.path.join(root, "bench", "traffic",
                                   w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root)


def load_metric(name: str, root: str = ROOT) -> ModuleType:
    """The reader module of per-layer metric `name` (its file may hold dots)."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    module_spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """Published peaks of `device_kind`; an unknown kind is an error."""
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
