"""A benchmark root holding fixture files only: a tiny configuration,
its mixes and cells, added as files and entries the way a later PR adds
a cell, beside copies of the real readers and references."""

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
    for d in ("metrics", "reference"):
        shutil.copytree(REPO / "bench" / d, tmp / "bench" / d)
    shutil.copy(FIX / "tiny.json", tmp / "bench" / "configs" / "tiny.json")
    bench["configs"].append({"name": "tiny", "source": "fixture",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU fixture"})
    cells = json.loads((FIX / "tiny-cells.json").read_text())
    for name, settings in cells.items():
        mix = name.split(".")[1]
        shutil.copy(FIX / f"tiny-{mix}.json",
                    tmp / "bench" / "traffic" / f"tiny-{mix}.json")
        (tmp / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(settings))
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": f"tiny-{mix}", "chips": 1,
                                   "why": "CPU fixture"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            like = {"chat": "qwen3-0.6b.chat", "batch": "qwen3-0.6b.batch",
                    "train": "qwen3-0.6b.train"}[mix]
            if like in m.get("workloads", [like]) and "workloads" in m:
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("benchroot"))
