"""Production training launcher.

``python -m repro.launch.train --arch <id> [--steps N] [--smoke]``

On a real pod this builds the production mesh, shards state per the
chosen strategy, and runs the fault-tolerant loop (async checkpoints,
straggler monitor, restore-on-restart).  ``--smoke`` runs the same code
path on whatever devices exist with a reduced config — the CI check.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.ft.checkpoint import AsyncCheckpointer
from repro.ft.elastic import make_mesh_for, state_shardings
from repro.ft.straggler import StragglerMonitor
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.step import (
    init_pipeline_state,
    init_state,
    make_pipeline_train_step,
    make_train_step,
)


def jit_train_step(step_fn, state, mesh, strategy: str):
    """Place ``state`` on ``mesh`` per ``strategy`` and jit ``step_fn``
    with matching state shardings, the state donated.  Returns
    (placed state, jitted step, state shardings)."""
    sshard = state_shardings(state, mesh, strategy)
    state = jax.tree.map(jax.device_put, state, sshard)
    jitted = jax.jit(step_fn, in_shardings=(sshard, None),
                     out_shardings=(sshard, None), donate_argnums=(0,))
    return state, jitted, sshard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0p6b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--strategy", default="fused",
                    choices=["fused", "ai_core_assignment", "scatter_gather",
                             "pipeline"])
    ap.add_argument("--pipeline-schedule", default="1f1b",
                    choices=["gpipe", "1f1b"])
    ap.add_argument("--microbatches", type=int, default=0,
                    help="pipeline microbatches (0 -> bubble-tuned)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20,
                    help="checkpoint period in steps (supervised mode)")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the fault-tolerant TrainSupervisor "
                         "(straggler re-cut, elastic restore, NaN rollback)")
    ap.add_argument("--fault-plan", default="",
                    help="injected faults, e.g. "
                         "'slowdown:step=6,stage=2,factor=3;kill:step=20'")
    ap.add_argument("--tuning-file", default=None,
                    help="TuningTable JSON to load before building the "
                         "step (tuned flash/GEMM blocks); with --autotune, "
                         "where to save the search result")
    ap.add_argument("--autotune", action="store_true",
                    help="run the measured-cost kernel knob search "
                         "(core.autotune.tune_runtime) before training")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled_down()

    if args.autotune:
        from repro.core.autotune import tune_runtime
        from repro.models.layers import set_tuning

        rep = tune_runtime(cfg=cfg,
                           kinds=("flash_prefill", "decode", "gemm_int8"),
                           save_path=args.tuning_file, verbose=True)
        set_tuning(rep.table)
    elif args.tuning_file:
        from repro.core.autotune import TuningTable
        from repro.models.layers import set_tuning

        set_tuning(TuningTable.load(args.tuning_file))
        print(f"loaded tuning table {args.tuning_file}")

    if args.supervise or args.fault_plan:
        from repro.ft.faults import FaultPlan
        from repro.ft.supervisor import TrainSupervisor

        plan = (FaultPlan.parse(args.fault_plan)
                if args.fault_plan else None)
        sup = TrainSupervisor(
            cfg,
            AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps),
            steps=args.steps, seq=args.seq, batch=args.batch,
            strategy=args.strategy, schedule=args.pipeline_schedule,
            microbatches=args.microbatches, grad_accum=args.grad_accum,
            ckpt_dir=args.ckpt or None, ckpt_every=args.ckpt_every,
            fault_plan=plan, verbose=True,
        )
        res = sup.run()
        print(f"final loss {res.final_loss:.4f}  "
              f"mean step {1e3 * sum(res.step_times) / len(res.step_times):.1f}ms  "
              f"events {len(res.events)}")
        for ev in res.events:
            print(f"  [{ev.kind}] at step {ev.step}: lost {ev.steps_lost} "
                  f"steps, recovered in {ev.recovery_s * 1e3:.0f}ms  "
                  f"{ev.detail}")
        print("done")
        return
    mesh = (
        make_production_mesh()
        if args.production_mesh
        else make_mesh_for(jax.devices())
    )
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name}  strategy {args.strategy}")

    opt = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    boundaries = None
    if args.strategy == "pipeline":
        # close the planner->runtime loop: cost-balanced cuts from the
        # config's per-layer cost graph, bubble-tuned microbatch count
        from repro.core.autotune import tune_microbatches
        from repro.core.placement import pipeline_boundaries

        stages = mesh.shape.get("model", 1)
        boundaries = pipeline_boundaries(cfg, args.seq, stages)
        microbatches = args.microbatches or tune_microbatches(
            stages, args.batch, args.pipeline_schedule
        )
        print(f"pipeline stages {stages}  boundaries {boundaries}  "
              f"microbatches {microbatches}  schedule {args.pipeline_schedule}")
        step_fn = make_pipeline_train_step(
            cfg, opt, mesh, num_microbatches=microbatches,
            boundaries=boundaries, schedule=args.pipeline_schedule,
        )
    else:
        step_fn = make_train_step(cfg, opt, grad_accum=args.grad_accum)

    with mesh:
        if args.strategy == "pipeline":
            state = init_pipeline_state(
                jax.random.PRNGKey(0), cfg, boundaries, jnp.float32
            )
        else:
            state = init_state(jax.random.PRNGKey(0), cfg, jnp.float32)
        state, jitted, sshard = jit_train_step(step_fn, state, mesh,
                                               args.strategy)

        ckpt = AsyncCheckpointer(args.ckpt, keep=2) if args.ckpt else None
        start = 0
        if ckpt:
            restored, at = ckpt.restore_latest(state, sshard)
            if restored is not None:
                state, start = restored, at
                print(f"resumed at step {start}")

        data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=0)
        pf = Prefetcher(data, start_step=start)
        mon = StragglerMonitor()
        try:
            for step in range(start, args.steps):
                t0 = time.time()
                state, metrics = jitted(state, pf.next())
                mon.record(jax.process_index(), time.time() - t0)
                if (step + 1) % 20 == 0:
                    print(f"step {step+1:>5} loss {float(metrics['loss']):.4f} "
                          f"gnorm {float(metrics['grad_norm']):.2f} "
                          f"stragglers {mon.report().stragglers}")
                    if ckpt:
                        ckpt.save(state, step + 1)
        finally:
            pf.close()
            if ckpt:
                ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
