"""The benchmark refuses to run, and prints no result, without a TPU or
outside a full checkout."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
ARGS = ["-m", "bench.run", "--workload", "qwen3-0.6b.batch", "--seed",
        "4294967301", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_on_cpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_refuses_jnp_dispatch(monkeypatch):
    import pytest

    from bench import program
    from repro.models import layers

    prev = layers.set_attention_impl("jnp")
    try:
        with pytest.raises(SystemExit, match="jnp"):
            program.refuse_unless_kernels()
    finally:
        layers.set_attention_impl(prev)
