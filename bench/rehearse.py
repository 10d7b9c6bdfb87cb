"""Compile a cell's programs for a TPU v5e that is described, not
attached, and print what the chip's compiler says of each: refused, or
its argument, output and temporary bytes.  Nothing runs.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse --workload <cell> [--batches 2,4,8]

Serving cells compile the engine's paged decode step at the cell's
slots, pool and dtypes, and its prefill and page scatter at the
smallest and the largest dense-cache bucket; the training cell compiles
its train step at each batch of ``--batches`` (the largest that fits is
the one to state in the job file), on the job's mesh of described chips
where it states one.
"""

from __future__ import annotations

import argparse
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import program, spec, traffic, weights  # noqa: E402


def _steer_to_kernels():
    """Make the program lower its Pallas kernels for the described chip
    (this process sees only the CPU backend)."""
    from repro.models import layers

    layers.set_attention_impl("pallas")
    layers._pallas_interpret = lambda: False


def _report(name, fn, *args):
    try:
        c = jax.jit(fn).lower(*args).compile() if not hasattr(fn, "lower") \
            else fn.lower(*args).compile()
    except Exception as e:  # the compiler's refusal is the finding
        print(f"{name}: REFUSED {type(e).__name__}: {str(e)[:600]}",
              flush=True)
        return
    m = c.memory_analysis()
    kern = "tpu_custom_call" in c.as_text()
    print(f"{name}: ok args={m.argument_size_in_bytes} "
          f"out={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
          f"alias={m.alias_size_in_bytes} kernel={kern}", flush=True)


def _shaped(tree, sh):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)


def serve(cell, sh):
    from repro.models import transformer as tf
    from repro.serve import kv_cache
    from repro.serve.step import make_prefill_step, make_serve_step

    mix, config, model = cell.traffic, cell.config, cell.model
    eng = mix["engine"]
    cfg = program.model_config(model, config)
    dt = jnp.dtype(eng["weights_dtype"])
    w = jax.eval_shape(lambda: weights._make(
        weights.key_for(0), model, config, dt))
    params = _shaped(program.to_program(model, w), sh)
    slots, chunk = int(eng["max_slots"]), int(eng["prefill_chunk"])
    max_len = int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
    pg = int(eng["page_size"])
    pages = kv_cache.pool_pages_for_bytes(
        cfg, int(cell.settings["kv_pool_bytes"]), pg, eng["kv_dtype"])
    caches = _shaped(jax.eval_shape(lambda: tf.init_caches(
        cfg, slots, max_len, dt, cache_layout="paged", page_size=pg,
        num_pages=pages, kv_dtype=eng["kv_dtype"])), sh)
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=sh)
    print(f"{cell.name}: pool {pages} pages of {pg} = "
          f"{pages * kv_cache.page_bytes(cfg, pg, eng['kv_dtype'])} bytes",
          flush=True)
    _report("decode step", jax.jit(make_serve_step(cfg),
                                   donate_argnums=(2,)), params, tok, caches)
    p = mix["prompt_tokens"]
    bs = traffic.buckets(int(p["min"]), int(p["max"]), chunk)
    for b in (bs[0], bs[-1]):
        dense = _shaped(jax.eval_shape(
            lambda b=b: tf.init_caches(cfg, 1, b, dt)), sh)
        piece = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=sh)
        n = jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)
        pre = jax.jit(lambda prm, t, c, k: make_prefill_step(
            cfg, chunk=chunk)(prm, t, c, n_tokens=k), donate_argnums=(2,))
        _report(f"prefill bucket {b}", pre, params, piece, dense, n)
        row = jax.ShapeDtypeStruct((-(-max_len // pg),), jnp.int32,
                                   sharding=sh)
        copy = jax.jit(kv_cache.write_prompt_pages, donate_argnums=(0,))
        _report(f"page scatter bucket {b}", copy, caches["blocks"],
                dense["blocks"], row, n, n, n)


def train(cell, topo, batches):
    """The job's train step, placed as the harness places it: one chip
    for the fused step, the job's mesh of described chips for the
    pipeline."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ft.elastic import state_shardings

    job, config, model = cell.traffic, cell.config, cell.model
    cfg = program.model_config(model, config)
    w = jax.eval_shape(lambda: weights._make(
        weights.key_for(0), model, config, jnp.dtype(job["params_dtype"])))
    mesh, fn, build, bounds, strategy = program.train_parts(
        cfg, job, topo.devices)
    like = jax.eval_shape(build, program.to_program(model, w))
    sshard = state_shardings(like, mesh, strategy)
    state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), like, sshard)
    data = NamedSharding(mesh, P("data") if bounds else P())
    for b in batches:
        batch = {"tokens": jax.ShapeDtypeStruct(
            (b, int(job["seq_len"]) + 1), jnp.int32, sharding=data)}
        with mesh:
            _report(f"train step batch {b}", jax.jit(
                fn, in_shardings=(sshard, {"tokens": data}),
                out_shardings=(sshard, None), donate_argnums=(0,)),
                state, batch)


def main(argv=None):
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])
    _steer_to_kernels()
    cell = spec.load_cell(args.workload)
    if cell.traffic["kind"] == "serve":
        serve(cell, sh)
    else:
        batches = [int(x) for x in args.batches.split(",") if x] or \
            [int(cell.traffic["batch"])]
        train(cell, topo, batches)


if __name__ == "__main__":
    main()
