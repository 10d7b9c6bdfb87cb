"""The pipelined training job on four CPU devices, in a child process
that asks for them before JAX starts (as ``tests/test_dist.py`` does):
the check's readings lie under their limits for the sound program and
the control reads over one, and `correct` comes out false with each
fault a pipeline can have planted under the timed path."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

CHILD = Path(__file__).resolve().parent / "pipeline_child.py"
FAULTS = ("unchanged", "half_batch", "no_send", "no_allreduce")


@pytest.fixture(scope="module")
def runs(fixture_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(CHILD), str(fixture_root), "sound",
                        "control", *FAULTS], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["run"]: r for r in map(json.loads, p.stdout.splitlines())}


def test_pipeline_cell_takes_its_chips_from_the_mesh(fixture_root):
    cell = spec.load_cell("tiny2.pipeline", fixture_root)
    assert cell.chips == 4
    assert cell.config["model_type"] == "qwen2"
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s", "setup_s"]


def test_sound_pipeline_run_is_correct(runs):
    sound = runs["sound"]
    assert sound["correct"] is True, sound["checks"]
    assert sound["count"] == 4
    assert all(c["value"] <= c["limit"] for c in sound["checks"].values())


def test_pipeline_control_reads_over_a_limit(runs):
    got = runs["control"]
    limits = got["limits"]
    assert all(v <= limits[k] for k, v in got["checks"].items())
    for name, readings in got["control"].items():
        assert any(readings[k] > limits[k] for k in readings), name


@pytest.mark.parametrize("fault", FAULTS)
def test_pipeline_fault_is_not_correct(runs, fault):
    line = runs[fault]
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
