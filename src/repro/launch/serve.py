"""Production serving launcher: batched prefill + decode loop.

``python -m repro.launch.serve --arch <id> --smoke`` runs the chunked
prefill + KV-cache decode loop on local devices with a reduced config;
on a pod the same code path shards params/caches per the serving
strategy (TP-biased by default — see EXPERIMENTS.md §Perf iteration A).

``--engine paged`` runs the continuous-batching engine instead: paged
KV cache, request-level admission, mixed prompt/generation lengths in
one decode batch (EXPERIMENTS.md §Serving).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config
from repro.dist.sharding import cache_specs, param_specs
from repro.ft.elastic import make_mesh_for
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as tf
from repro.serve.step import make_prefill_step, make_serve_step


def _run_paged_engine(params, cfg, args):
    from repro.models.layers import tuned
    from repro.serve.engine import ServingEngine, latency_stats

    # explicit flag > tuning table (--autotune / --tuning-file) > default
    page_size = args.page_size or int(tuned("serving").get("page_size", 16))
    max_len = args.prompt + args.new_tokens
    draft_params = draft_cfg = None
    if args.draft:
        draft_cfg = get_config(args.draft)
        if args.smoke:
            draft_cfg = draft_cfg.scaled_down()
        draft_cfg = dataclasses.replace(draft_cfg, vocab=cfg.vocab)
        draft_params = tf.init(jax.random.PRNGKey(2), draft_cfg, jnp.float32)
    # with the prefix cache on, a zero-slack pool evicts every retired
    # prefix before its sharer arrives — double it so pages can linger
    pages = -(-max_len // page_size) * args.batch
    engine_kw = dict(
        max_slots=args.batch, max_len=max_len,
        page_size=page_size, kv_dtype=args.kv_dtype,
        num_pages=2 * pages if args.prefix_cache else pages,
        prefill_chunk=max(16, args.prompt // 4),
        prefix_cache=args.prefix_cache,
        draft_params=draft_params, draft_cfg=draft_cfg, spec_k=args.spec_k,
        prefill_budget=args.prefill_budget, slo_ms=args.slo_ms)
    sup = None
    if args.supervise or args.fault_plan or args.deadline_ms:
        from repro.ft.faults import FaultPlan
        from repro.serve.supervisor import ServeSupervisor

        plan = (FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
                if args.fault_plan else None)
        sup = ServeSupervisor(params, cfg, engine_kw=engine_kw,
                              fault_plan=plan, verbose=True)
        eng = sup.engine
    else:
        eng = ServingEngine(params, cfg, **engine_kw)
    priorities = ([int(p) for p in args.priority.split(",")]
                  if args.priority else [0])
    rng = jax.random.PRNGKey(1)
    # mixed-length trace: prompts at the configured length, generation
    # lengths spread 1/4x..1x so slots actually churn; with the prefix
    # cache on, half the requests share one prompt prefix
    rng, ks = jax.random.split(rng)
    shared = jax.random.randint(ks, (args.prompt // 2,), 0, cfg.vocab)
    for i in range(2 * args.batch):
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (args.prompt,), 0, cfg.vocab)
        if args.prefix_cache and i % 2:
            prompt = jnp.concatenate([shared, prompt[args.prompt // 2:]])
        new = max(1, args.new_tokens // (1 + i % 4))
        if sup is not None:
            sup.submit(jnp.asarray(prompt), new,
                       priority=priorities[i % len(priorities)],
                       deadline_ms=args.deadline_ms)
        else:
            eng.submit(jnp.asarray(prompt), new,
                       priority=priorities[i % len(priorities)])
    t0 = time.monotonic()
    if sup is not None:
        done = sup.run()
        eng = sup.engine  # recoveries may have rebuilt it
    else:
        done = eng.run()
    dt = time.monotonic() - t0
    if sup is not None:
        kinds = {}
        for ev in sup.events:
            kinds[ev.kind] = kinds.get(ev.kind, 0) + 1
        print(f"supervisor: {sup.steps} supervised steps, "
              f"{sup.recoveries} recoveries ({sup.rebuilds} rebuilds), "
              f"events {kinds or '{}'}"
              + (", DEGRADED to jnp dispatch" if sup.degraded else ""))
        for ev in sup.events:
            print(f"  step {ev.step}: {ev.kind} {ev.detail} "
                  f"({ev.recovery_s * 1e3:.1f} ms)")
        sup.restore_dispatchers()
    finished = [r for r in done if not r.cancelled]
    if len(finished) < len(done):
        print(f"  {len(done) - len(finished)} requests cancelled "
              "(deadline/shed)")
    if not finished:
        raise SystemExit("paged engine: no requests finished")
    done = finished
    stats = latency_stats(done)
    print(f"paged engine: {len(done)} requests, {stats['tokens']} tokens "
          f"in {dt*1e3:.0f} ms over {eng.steps} decode steps "
          f"({stats['tokens']/dt:.0f} tok/s)")
    print(f"  token latency p50 {stats['token_p50_s']*1e3:.1f} ms, "
          f"p99 {stats['token_p99_s']*1e3:.1f} ms; "
          f"ttft p50 {stats['ttft_p50_s']*1e3:.1f} ms, "
          f"p99 {stats['ttft_p99_s']*1e3:.1f} ms; "
          f"queue wait p99 {stats['queue_p99_s']*1e3:.1f} ms; "
          f"pool {eng.num_pages} pages x {eng.page_size} slots "
          f"({eng.kv_dtype}, {eng.pool_bytes/2**10:.0f} KiB)")
    es = eng.stats()
    print(f"  admitted {es['admitted']}, rejected {es['rejected']}; "
          f"prefilled {es['prefilled_tokens']}/{es['prompt_tokens']} "
          "prompt tokens")
    if eng.prefill_budget is not None:
        print(f"  scheduler: budget {es['prefill_budget']} tok/step over "
              f"{es['prefill_chunk_calls']} chunk calls; "
              f"{es['preemptions']} preemptions "
              f"({es['preempt_pages_saved']} pages saved to prefix)")
    if eng.slo_s is not None:
        print(f"  slo {es['slo_ms']:.1f} ms: deferred "
              f"{es['slo_deferred_steps']} admissions, throttled "
              f"{es['slo_throttled_steps']} steps "
              f"(chunk {es.get('chunk_cost_ms', 0):.2f} ms, decode "
              f"{es.get('decode_cost_ms', 0):.2f} ms EWMA)")
    if args.prefix_cache:
        print(f"  prefix cache: {es['prefix_hits']}/{es['prefix_lookups']} "
              f"hits, {es['prefix_hit_tokens']} tokens served from shared "
              f"pages, {es['prefix_evicted_pages']} evicted, "
              f"{es['prefix_nodes']} resident nodes")
    if eng.spec_k:
        print(f"  speculative k={es['spec_k']}: "
              f"{es['accepted_per_spec_step']:.2f} tokens/slot-step "
              f"over {es['spec_steps']} verify steps")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0p6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--strategy", default="fused")
    ap.add_argument("--engine", choices=["static", "paged"], default="static",
                    help="static: one fixed batch to completion; paged: "
                         "continuous batching over the paged KV cache")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged-engine page size; default resolves through "
                         "the tuning table (--autotune/--tuning-file), "
                         "else 16")
    ap.add_argument("--tuning-file", default=None,
                    help="TuningTable JSON (core.autotune.tune_runtime) to "
                         "load; with --autotune, where to save the search "
                         "result")
    ap.add_argument("--autotune", action="store_true",
                    help="run the measured-cost knob search (tune_runtime) "
                         "before serving and deploy the winning blocks/"
                         "page size via set_tuning")
    ap.add_argument("--kv-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="paged-engine pool precision; int8 stores "
                         "quarter-size pages + per-page scales, so the "
                         "same pool bytes admit ~4x the sequences")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged engine: share prompt-prefix KV pages "
                         "across requests via the radix prefix cache")
    ap.add_argument("--draft", default=None,
                    help="paged engine: arch id of a draft model — turns "
                         "on speculative decoding (vocab coerced to the "
                         "target's)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative tokens proposed per slot per step")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="paged engine: max prompt tokens prefilled per "
                         "engine step (decode-interleaved chunked "
                         "prefill); default runs each prefill to "
                         "completion inside admission")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="paged engine: per-token decode latency target — "
                         "throttles per-step prefill and defers admission "
                         "when in-flight decoders would miss it (needs "
                         "--prefill-budget)")
    ap.add_argument("--priority", default=None,
                    help="comma-separated priority classes cycled over "
                         "the trace (e.g. '0,1'); higher preempts lower "
                         "under pool pressure")
    ap.add_argument("--supervise", action="store_true",
                    help="paged engine: run under the fault-tolerant "
                         "ServeSupervisor (heartbeats, pool audits, "
                         "deadline enforcement, recovery)")
    ap.add_argument("--fault-plan", default=None,
                    help="inject serving faults, e.g. 'device_loss:step=6,"
                         "lose=1;decode_nan:step=14' (implies --supervise; "
                         "see repro.ft.faults for the grammar)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault plan's randomized choices")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired requests are "
                         "cancelled within one supervised step (implies "
                         "--supervise)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled_down()
    if cfg.is_enc_dec or cfg.frontend:
        raise SystemExit("use examples/serve_batched.py variants for "
                         "frontend/enc-dec archs")
    if args.autotune:
        from repro.core.autotune import tune_runtime
        from repro.models.layers import set_tuning

        kinds = ["flash_prefill", "decode", "gemm_int8"]
        if args.engine == "paged":
            kinds.append("paged_decode")
        rep = tune_runtime(cfg=cfg, kinds=tuple(kinds),
                           save_path=args.tuning_file, verbose=True)
        set_tuning(rep.table)
        if args.tuning_file:
            print(f"autotune: saved tuning table to {args.tuning_file}")
    elif args.tuning_file:
        from repro.core.autotune import TuningTable
        from repro.models.layers import set_tuning

        set_tuning(TuningTable.load(args.tuning_file))
        print(f"loaded tuning table {args.tuning_file}")
    if args.engine == "paged":
        params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
        _run_paged_engine(params, cfg, args)
        return
    mesh = make_mesh_for(jax.devices())
    max_len = args.prompt + args.new_tokens

    with mesh:
        params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
        params = jax.tree.map(
            jax.device_put, params,
            jax.tree.map(lambda s: NamedSharding(mesh, s),
                         param_specs(params, mesh, args.strategy),
                         is_leaf=lambda x: isinstance(x, P)),
        )
        caches = tf.init_caches(cfg, args.batch, max_len, jnp.float32)
        caches = jax.tree.map(
            jax.device_put, caches,
            jax.tree.map(lambda s: NamedSharding(mesh, s),
                         cache_specs(caches, mesh),
                         is_leaf=lambda x: isinstance(x, P)),
        )
        prefill = jax.jit(make_prefill_step(cfg, chunk=max(16, args.prompt // 4)))
        decode = jax.jit(make_serve_step(cfg))

        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt), 0, cfg.vocab
        )
        t0 = time.monotonic()
        tok, caches = prefill(params, prompts, caches)
        tok = tok[:, None]
        print(f"prefill {args.batch}x{args.prompt} in {(time.monotonic()-t0)*1e3:.0f} ms")
        t0 = time.monotonic()
        for _ in range(args.new_tokens - 1):
            tok, caches = decode(params, tok, caches)
        jax.block_until_ready(tok)
        dt = time.monotonic() - t0
        print(f"decode {args.new_tokens} steps: "
              f"{args.batch * args.new_tokens / dt:.0f} tok/s")


if __name__ == "__main__":
    main()
