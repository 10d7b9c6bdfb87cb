"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

    bench/configs/<config>.json      a model configuration (sizes)
    bench/models/<model_type>.py     what the harness knows of a model
                                     type: the program's configuration,
                                     weight shapes and leaf map, and the
                                     sizes the counts of flops.py read
    bench/reference/<model_type>.py  its plain jnp reference
    bench/traffic/<traffic>.json     a traffic mix or training job; a
                                     job may state "mesh" ({"stages",
                                     "data"}), "schedule", "microbatches",
                                     "params_dtype" and "moments_dtype"
    bench/cells/<workload>.json      what belongs to one cell: memory
                                     sizes and the limits of `correct`
    bench/metrics/<metric>.py        the reader of one per-layer metric

A later cell, or a new model type, is added by adding such files and an
entry in ``BENCHMARK.json``; nothing outside ``bench/models`` and
``bench/reference`` names a model type, a cell or a model's leaves.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    settings: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    model: object  # the configuration's bench/models module


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    settings_path = root / "bench" / "cells" / f"{workload}.json"
    settings = _load_json(settings_path) if settings_path.exists() else {}
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), w["config"], config,
                w["traffic"], traffic, settings, e2e, per_layer,
                model_module(config, root))


def _load_module(path: Path, name: str):
    if not path.exists():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` of per-layer metric ``name``."""
    mod = _load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")
    return mod.read


def reference_module(config: dict, root: Path = ROOT):
    """The plain jnp reference of the configuration's ``model_type``."""
    kind = config["model_type"]
    return _load_module(root / "bench" / "reference" / f"{kind}.py",
                        f"bench_reference_{kind}")


def model_module(config: dict, root: Path = ROOT):
    """The harness's module for the configuration's ``model_type``."""
    kind = config["model_type"]
    return _load_module(root / "bench" / "models" / f"{kind}.py",
                        f"bench_model_{kind}")
