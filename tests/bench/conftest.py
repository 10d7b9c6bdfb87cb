"""A benchmark root holding fixture files only: tiny configurations,
their mixes and cells, added as files and entries the way a later PR
adds a cell, beside copies of the real readers, model modules and
references.  A fixture cell ``<config>.<mix>`` runs ``tiny-<mix>.json``
on ``<config>.json``, on as many devices as the mix's mesh holds."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
FIX = HERE / "fixtures"
REPO = HERE.parents[1]
if str(REPO) not in sys.path:  # the benchmark package sits at the root
    sys.path.insert(0, str(REPO))


def make_root(tmp: Path) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "cells").mkdir()
    for d in ("metrics", "models", "reference"):
        shutil.copytree(REPO / "bench" / d, tmp / "bench" / d)
    cells = json.loads((FIX / "tiny-cells.json").read_text())
    for conf in sorted({name.split(".")[0] for name in cells}):
        shutil.copy(FIX / f"{conf}.json",
                    tmp / "bench" / "configs" / f"{conf}.json")
        bench["configs"].append({"name": conf, "source": "fixture",
                                 "file": f"bench/configs/{conf}.json",
                                 "reduced": [], "why": "CPU fixture"})
    for name, settings in cells.items():
        conf, mix = name.split(".")
        shutil.copy(FIX / f"tiny-{mix}.json",
                    tmp / "bench" / "traffic" / f"tiny-{mix}.json")
        (tmp / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(settings))
        grid = json.loads((FIX / f"tiny-{mix}.json").read_text()).get(
            "mesh", {"stages": 1, "data": 1})
        bench["workloads"].append({"name": name, "config": conf,
                                   "traffic": f"tiny-{mix}",
                                   "chips": grid["stages"] * grid["data"],
                                   "why": "CPU fixture"})
        like = {"chat": "qwen3-0.6b.chat", "batch": "qwen3-0.6b.batch",
                "train": "qwen3-0.6b.train", "pipeline": "qwen3-0.6b.train"}[mix]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", [like]) and "workloads" in m:
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("benchroot"))
