"""Readings that set the limits of `correct` (chip only, not part of a
benchmark run): for each seed, one short window of the cell through its
timed path and the reference's comparison; for the first
``--control-seeds`` seeds also the control's readings, computed on the
same inputs.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 10 [--control-seeds 3]

Serving: the control is the reference with float8 (e4m3) weights and
activations (the precision below the bfloat16 the cell serves in), read at each
served position as the reference's gap of the token the control puts
first.  Training: the control is the reference with float8 matmuls in the
program's place (the job's float32 runs its matmuls on bfloat16 operands
on a TPU), scaled per row and column in the backward pass as in the
forward; the half-batch fault is the reference on half of each batch.
A state left unchanged reads 1 on ``grad_gap``, ``delta_gap`` and
``grad_err`` by definition.
Prints one JSON line per seed; everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import time

from bench import run as bench_run
from bench import spec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    from bench import program

    program.use_compile_cache()
    bench_run.refuse_without_chips(cell.chips)
    program.refuse_unless_kernels()
    if cell.traffic["kind"] == "serve":
        from bench import serve as runner
    else:
        from bench import train as runner
    compiles = bench_run.CompileCounter()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        mode = "control" if i < args.control_seeds else "program"
        t = time.monotonic()
        res = runner.run_cell(cell, seed, args.seconds, None, t, compiles,
                              check=mode)
        print(json.dumps({"seed": seed, "checks": res["checks"],
                          "control": res["control"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "e2e": res["e2e"],
                          "setup_s": res["setup_s"],
                          "wall_s": time.monotonic() - t,
                          "notes": res["notes"]}, default=float),
              flush=True)


if __name__ == "__main__":
    main()
