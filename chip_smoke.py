#!/usr/bin/env python3
"""Smoke run of the main path on a TPU at full Qwen3-0.6B width.

    python chip_smoke.py [--seed N]    # one chip: every phase below
    python chip_smoke.py --four-chips  # 2x2 mesh: pipeline vs plain train

Everything runs in this one process (a chip belongs to one process), at
the published width and depth of ``configs/qwen3_0p6b.py`` with random
weights from ``--seed``.  One chip, in order:

  serve        ``ServingEngine``: 8 slots, default page size, f32 KV pages,
               16 requests (prompts 128-512, 16-64 new tokens), run to
               completion twice (cold, then warm); every request finishes
  serve int8   the same with int8 KV pages (the int8-page decode kernel)
  int8 weights ``serve.step.generate`` on ``quantize_params`` weights (the
               VTA GEMM, flash prefill and split-KV decode kernels)
  correctness  logits of one prefill chunk, then of one cached decode
               step through the dense and the paged cache, against the
               jnp reference, both at the highest matmul precision; the
               VTA GEMM against the jnp int8 GEMM on identical operands;
               int8-weight logits printed against both references
  train        5 steps of ``make_train_step`` (fused) placed and jitted by
               ``launch/train.py``'s ``jit_train_step``; the loss is
               finite and goes down

``--four-chips`` runs only ``make_pipeline_train_step`` on a 2x2
(data, model) mesh and its comparison, ``make_train_step`` on the same
mesh from the same init and batches; their first-step losses must agree.

Every stdout line but the last is an observation, not a metric.  The
last line is the verdict, printed only when every phase passed:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, with no verdict, when JAX finds no TPU, when
a kernel path is forced to jnp or to Pallas interpret mode, and when any
phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

N_REQUESTS, MAX_SLOTS = 16, 8
PROMPT_RANGE, NEW_RANGE = (128, 512), (16, 64)
PROBE_PROMPT = 512  # >= FLASH_MIN_SEQ: the prefill runs the flash kernel
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 5
# Logits of the kernel path against the jnp reference, both at the
# highest matmul precision: only kernel arithmetic (online softmax,
# partition combine) separates them, ~1e-6 relative per layer.  bf16
# matmul passes leave ~1e-2 of the logit scale after 28 layers, so the
# bound also rejects a path that drops to lower precision.
LOGIT_RTOL = 2e-3
# One int8 GEMM, kernel against jnp on the same int8 operands: the int32
# products are exact, the f32 dequant/silu epilogue rounds alike.  The
# GEMM is held here and not through int8-weight logits: there a last-ulp
# difference anywhere (XLA fuses the jnp epilogue into its neighbours)
# flips the rounding of some dynamically quantized activations, and over
# 28 layers kernel and jnp paths become separate draws of the
# quantization noise.  Those logits are printed, not gated.
GEMM_RTOL = 1e-4
# Pipeline and plain step see the same params and batch; they differ in
# microbatching and reduction order only.
LOSS_RTOL = 1e-3

_COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache) since the process started."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.total += duration


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def memory_stat(key: str, device=None) -> int:
    return (device or jax.devices()[0]).memory_stats()[key]


def phase(clock: CompileClock, name: str, fn, *args):
    """Run one phase; print its compile and wall seconds."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn(*args)
    print(f"[{name}] compile_s={clock.total - c0:.3f} "
          f"wall_s={time.perf_counter() - t0:.3f} "
          f"peak_bytes_in_use={memory_stat('peak_bytes_in_use')}", flush=True)
    return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def make_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, cfg.vocab, int(rng.integers(*PROMPT_RANGE,
                                                     endpoint=True))),
         int(rng.integers(*NEW_RANGE, endpoint=True)))
        for _ in range(N_REQUESTS)
    ]


def serve_once(params, cfg, requests, kv_dtype: str) -> str:
    """One engine run over ``requests``; every request must finish."""
    from repro.serve.engine import ServingEngine

    max_len = PROMPT_RANGE[1] + NEW_RANGE[1]
    eng = ServingEngine(params, cfg, max_slots=MAX_SLOTS, max_len=max_len,
                        kv_dtype=kv_dtype)
    for prompt, new in requests:
        eng.submit(prompt, new)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    check(len(done) == len(requests),
          f"serve {kv_dtype}: {len(done)}/{len(requests)} requests ended")
    for r in done:
        check(not r.cancelled and len(r.tokens) == r.max_new,
              f"serve {kv_dtype}: request {r.rid} ended with "
              f"{len(r.tokens)}/{r.max_new} tokens")
        check(all(0 <= t < cfg.vocab for t in r.tokens),
              f"serve {kv_dtype}: request {r.rid} emitted an id outside "
              "the vocabulary")
    # prefill runs inside admission, request by request: admit -> first
    # token is one request's prefill; the rest of the wall is decode
    prefill_s = [r.t_first - r.t_admit for r in done]
    decoded = sum(len(r.tokens) - 1 for r in done)
    return (f"{len(done)}/{len(requests)} requests finished, "
            f"{sum(len(r.tokens) for r in done)} tokens in {eng.steps} "
            f"decode steps; prefill {1e3 * np.mean(prefill_s):.3f} ms/request"
            f" (mean over prompts of {np.mean([len(p) for p, _ in requests]):.1f}"
            f" tokens); decode {decoded / (wall - sum(prefill_s)):.3f} tok/s")


def serve_phase(params, cfg, requests, kv_dtype: str) -> None:
    print(f"  cold: {serve_once(params, cfg, requests, kv_dtype)}")
    print(f"  warm: {serve_once(params, cfg, requests, kv_dtype)}")


def decode_has_kernel(params, cfg, kv_dtype: str) -> bool:
    """Whether the engine's decode step (``make_serve_step`` over paged
    caches, what ``ServingEngine`` jits) lowers to a Pallas kernel."""
    from repro.serve.step import make_serve_step

    max_len = PROMPT_RANGE[1] + NEW_RANGE[1]
    caches = jax.eval_shape(lambda: tf.init_caches(
        cfg, MAX_SLOTS, max_len, jnp.float32, cache_layout="paged",
        kv_dtype=kv_dtype))
    tok = jax.ShapeDtypeStruct((MAX_SLOTS, 1), jnp.int32)
    text = jax.jit(make_serve_step(cfg)).lower(params, tok, caches).as_text()
    return "tpu_custom_call" in text


def int8_weights_phase(qparams, cfg, seed: int) -> None:
    from repro.serve.step import generate

    rng = np.random.default_rng(seed + 1)
    new = NEW_RANGE[0]
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (4, PROBE_PROMPT)),
                         jnp.int32)
    t0 = time.perf_counter()
    out = np.asarray(generate(qparams, cfg, prompt, max_new=new,
                              max_len=PROBE_PROMPT + new, dtype=jnp.float32))
    check(out.shape == (4, new), f"int8 weights: tokens shape {out.shape}")
    check(bool(((out >= 0) & (out < cfg.vocab)).all()),
          "int8 weights: token ids outside the vocabulary")
    print(f"  generate 4x{PROBE_PROMPT} prompt + {new} tokens in "
          f"{time.perf_counter() - t0:.3f} s (compile included)")


def prefill_then_decode(params, cfg, prompt, token):
    """Last-position logits of one prefill chunk, then of one decode step
    through the dense cache and through the engine's paged cache."""
    from repro.serve import kv_cache

    n = prompt.shape[1]
    dense = tf.init_caches(cfg, 1, n + 1, jnp.float32)
    lp, dense = tf.prefill(params, cfg, prompt, dense)
    ld, _ = tf.decode_step(params, cfg, token, dense)
    paged = tf.init_caches(cfg, 1, n + 1, jnp.float32, cache_layout="paged")
    row = jnp.arange(paged["block_tables"].shape[1], dtype=jnp.int32)
    paged = {
        "blocks": kv_cache.write_prompt_pages(paged["blocks"],
                                              dense["blocks"], row, n),
        "block_tables": row[None],
        "lens": jnp.full((1,), n, jnp.int32),
    }
    lg, _ = tf.decode_step(params, cfg, token, paged)
    return lp[0, -1], ld[0, -1], lg[0, -1]


def with_impls(attn: str, gemm: str, precision: str | None, fn, *args):
    """``jax.jit(fn)(*args)`` as numpy, under the given kernel dispatch
    and matmul precision."""
    prev = layers.set_attention_impl(attn), layers.set_gemm_impl(gemm)
    try:
        # a fresh jit per setting: the dispatch is read at trace time
        with jax.default_matmul_precision(precision):
            return jax.tree.map(np.asarray, jax.jit(fn)(*args))
    finally:
        layers.set_attention_impl(prev[0])
        layers.set_gemm_impl(prev[1])


def compare(label: str, got, want, rtol: float | None = None) -> None:
    """Print max|got - want|; with ``rtol``, fail unless it is within
    ``rtol`` x max|want|.  ``got`` must be finite either way."""
    check(bool(np.isfinite(got).all()), f"{label}: non-finite")
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    if rtol is None:
        print(f"  {label}: max|diff| {diff:.6g} = {diff / scale:.6g} x "
              "max|ref| (observation)")
        return
    print(f"  {label}: max|diff| {diff:.6g} vs tolerance {rtol * scale:.6g}"
          f" ({rtol} x max|ref|)")
    check(diff <= rtol * scale, f"{label}: off the reference")


def gemm_check(qparams, cfg, seed: int) -> None:
    """The VTA GEMM against the jnp int8 GEMM on identical inputs, for
    layer 0's MLP projections at ``PROBE_PROMPT`` rows: the int32
    products are exact on both, only the f32 dequant epilogue differs."""
    ffn = jax.tree.map(lambda a: a[0], qparams["blocks"]["ffn"])
    key = jax.random.PRNGKey(seed + 3)
    for name, act in (("w_gate", "silu"), ("w_up", None), ("w_down", None)):
        p = ffn[name]
        x = jax.random.normal(key, (PROBE_PROMPT, p["qw"].shape[0]))

        def fn(p, x, act=act):
            return layers.quant_dense_apply(p, x, act=act)

        want = with_impls("jnp", "jnp", "highest", fn, p, x)
        got = with_impls("jnp", "auto", "highest", fn, p, x)
        compare(f"int8 GEMM {name} {tuple(p['qw'].shape)}"
                + (f" + {act}" if act else ""), got, want, GEMM_RTOL)


def correctness_phase(params, qparams, cfg, seed: int) -> None:
    rng = np.random.default_rng(seed + 2)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, PROBE_PROMPT)),
                         jnp.int32)
    token = jnp.asarray(rng.integers(0, cfg.vocab, (1, 1)), jnp.int32)
    names = ("prefill", "dense decode", "paged decode")

    def probe(p, impl, precision="highest"):
        return with_impls(impl, impl, precision,
                          lambda p, x, t: prefill_then_decode(p, cfg, x, t),
                          p, prompt, token)

    ref = probe(params, "jnp")
    for name, g, w in zip(names, probe(params, "auto"), ref):
        compare(f"f32 weights {name}", g, w, LOGIT_RTOL)
    gemm_check(qparams, cfg, seed)
    qgot, qref = probe(qparams, "auto"), probe(qparams, "jnp")
    for name, g, qw, w in zip(names, qgot, qref, ref):
        compare(f"int8 weights {name}, against f32 weights", g, w)
        compare(f"int8 weights {name}, jnp int8 against f32 weights", qw, w)
        compare(f"int8 weights {name}, against jnp int8", g, qw)
    # how far the kernel path at the default (bf16-pass) precision
    # lands: the error LOGIT_RTOL is meant to reject
    for name, g, w in zip(names, probe(params, "auto", None), ref):
        compare(f"f32 weights {name} at default precision", g, w)


def train_phase(cfg, seed: int) -> None:
    from repro.data.pipeline import SyntheticLM
    from repro.ft.elastic import make_mesh_for
    from repro.launch.train import jit_train_step
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import init_state, make_train_step

    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=TRAIN_STEPS)
    mesh = make_mesh_for(jax.devices())
    data = SyntheticLM(cfg.vocab, TRAIN_SEQ, TRAIN_BATCH, seed=seed)
    with mesh:
        state = init_state(jax.random.PRNGKey(seed), cfg, jnp.float32)
        state, step, _ = jit_train_step(make_train_step(cfg, opt), state,
                                        mesh, "fused")
        compiled = step.lower(state, data.batch(0)).compile()
        print(f"  {TRAIN_BATCH}x{TRAIN_SEQ} tokens/step; "
              f"{compiled.memory_analysis()}")
        losses = []
        for i in range(TRAIN_STEPS):
            state, metrics = compiled(state, data.batch(i))
            losses.append(float(metrics["loss"]))
    print(f"  losses {losses}")
    check(all(np.isfinite(losses)), "train: non-finite loss")
    check(losses[-1] < losses[0], "train: loss did not go down")


def one_chip(args, clock: CompileClock) -> None:
    from repro.optim.quant import quantize_params

    cfg = get_config("qwen3_0p6b")
    print(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.kv_heads} x "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}", flush=True)
    params = tf.init(jax.random.PRNGKey(args.seed), cfg, jnp.float32)
    requests = make_requests(cfg, args.seed)
    phase(clock, "serve f32 kv", serve_phase, params, cfg, requests, "f32")
    phase(clock, "serve int8 kv", serve_phase, params, cfg, requests, "int8")
    for kv in ("f32", "int8"):
        has = decode_has_kernel(params, cfg, kv)
        print(f"decode step ({kv} pages) contains tpu_custom_call: {has}")
        check(has, f"the {kv}-page decode step runs no Pallas kernel")
    qparams = quantize_params(params)
    phase(clock, "int8 weights", int8_weights_phase, qparams, cfg, args.seed)
    phase(clock, "correctness", correctness_phase, params, qparams, cfg,
          args.seed)
    del params, qparams  # the train state needs the memory
    phase(clock, "train", train_phase, cfg, args.seed)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips(args, clock: CompileClock) -> None:
    from repro.core.autotune import tune_microbatches
    from repro.core.placement import pipeline_boundaries
    from repro.data.pipeline import SyntheticLM
    from repro.ft.elastic import make_mesh_for
    from repro.launch.train import jit_train_step
    from repro.optim.adamw import AdamWConfig
    from repro.train.step import (init_pipeline_state, init_state,
                                  make_pipeline_train_step, make_train_step)

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = get_config("qwen3_0p6b")
    mesh = make_mesh_for(jax.devices())
    stages = mesh.shape["model"]
    batch, seq, steps = 16, TRAIN_SEQ, 3
    bounds = pipeline_boundaries(cfg, seq, stages)
    micro = tune_microbatches(stages, batch, "1f1b")
    print(f"mesh {dict(mesh.shape)}; pipeline boundaries {bounds}, "
          f"{micro} microbatches, 1f1b; {batch}x{seq} tokens/step",
          flush=True)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps)
    data = SyntheticLM(cfg.vocab, seq, batch, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)

    def run(name, init, step_fn, strategy):
        with mesh:
            state, step, _ = jit_train_step(step_fn, init(), mesh, strategy)
            losses = []
            for i in range(steps):
                state, metrics = step(state, data.batch(i))
                losses.append(float(metrics["loss"]))
        in_use = [memory_stat("bytes_in_use", d) for d in jax.devices()]
        print(f"  {name}: losses {losses}; bytes_in_use per device {in_use}")
        check(all(np.isfinite(losses)), f"{name}: non-finite loss")
        check(min(in_use) >= max(in_use) / 4,
              f"{name}: state is not spread over the devices: {in_use}")
        return losses

    piped = phase(
        clock, "pipeline train", run, "pipeline",
        lambda: init_pipeline_state(key, cfg, bounds, jnp.float32),
        make_pipeline_train_step(cfg, opt, mesh, num_microbatches=micro,
                                 boundaries=bounds, schedule="1f1b"),
        "pipeline")
    plain = phase(clock, "plain train", run, "plain",
                  lambda: init_state(key, cfg, jnp.float32),
                  make_train_step(cfg, opt), "fused")
    diff = abs(piped[0] - plain[0])
    tol = LOSS_RTOL * abs(plain[0])
    print(f"first-step loss: pipeline {piped[0]!r} plain {plain[0]!r}; "
          f"|diff| {diff:.6g} vs tolerance {tol:.6g} ({LOSS_RTOL} relative)")
    check(diff <= tol, "pipeline and plain first-step losses disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh pipeline vs plain train "
                         "step comparison")
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    if jax.default_backend() != "tpu":
        fail(f"no TPU found: JAX's backend is {jax.default_backend()!r}")
    if "jnp" in (layers.attention_impl(), layers.gemm_impl()):
        fail("a kernel path is forced to jnp (REPRO_ATTN_IMPL / "
             "REPRO_GEMM_IMPL); the smoke must run the Pallas kernels")
    if layers._pallas_interpret():
        fail("the Pallas kernels would run in interpret mode")
    print(f"devices {jax.devices()}")
    print(f"compile cache {cache_dir}", flush=True)
    clock = CompileClock()
    (four_chips if args.four_chips else one_chip)(args, clock)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
