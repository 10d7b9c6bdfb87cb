"""Runs of the fixture pipeline cell on four CPU devices, one process:
the sound program, its control, and the program with each fault a
pipeline can have planted under the timed path.  Prints one JSON line
per run.  Started by ``test_bench_pipeline.py`` with

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/pipeline_child.py <benchmark root> <run> ...

where a run is ``sound``, ``control``, ``unchanged``, ``half_batch``,
``no_send`` (the stage-to-stage sends left out) or ``no_allreduce``
(the data-parallel mean of gradients and loss left out).
"""

from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

WORKLOAD = "tiny2.pipeline"
SEED = 2**33 + 91


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted, for the runs inside."""
    import jax

    import repro.train.step as tstep

    real_make = tstep.make_pipeline_train_step
    real_ppermute, real_pmean = jax.lax.ppermute, jax.lax.pmean

    def make(cfg, opt, mesh, **kw):
        step = real_make(cfg, opt, mesh, **kw)

        def faulty(state, batch):
            if fault == "unchanged":
                return state, step(state, batch)[1]
            half = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:half]})

        return faulty

    if fault in ("unchanged", "half_batch"):
        tstep.make_pipeline_train_step = make
    elif fault == "no_send":
        jax.lax.ppermute = lambda x, *a, **k: x
    elif fault == "no_allreduce":
        jax.lax.pmean = lambda x, *a, **k: x
    try:
        yield
    finally:
        tstep.make_pipeline_train_step = real_make
        jax.lax.ppermute, jax.lax.pmean = real_ppermute, real_pmean


def main(argv) -> None:
    from bench import program  # noqa: F401  (puts the program on the path)
    from bench import run as bench_run
    from bench import spec, train

    root = Path(argv[0])
    for what in argv[1:]:
        if what == "control":
            cell = spec.load_cell(WORKLOAD, root)
            res = train.run_cell(cell, SEED, 1.0, None, time.monotonic(),
                                 bench_run.CompileCounter(), check="control")
            out = {"checks": res["checks"], "control": res["control"],
                   "limits": cell.settings["limits"]}
        else:
            with planted(what):
                line = bench_run.run(WORKLOAD, SEED, 1.0, False, root=root,
                                     require_chip=False)
            out = {"correct": line["correct"], "checks": line["checks"],
                   "count": line["device"]["count"]}
        print(json.dumps({"run": what, **out}, default=float), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
