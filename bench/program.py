"""The harness's one door into the system under test (``src/repro``):
its model configuration, parameter layout, serving engine and training
step.  The references never import this module."""

from __future__ import annotations

import dataclasses
import sys

from bench.spec import ROOT

_SRC = str(ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402

_CONFIGS: dict = {}


def model_config(model, config: dict):
    """The program's ``ModelConfig`` for a configuration file (built by
    the model type's module); one object per configuration in a process,
    so the engine's jitted steps (kept per config object) are traced
    once."""
    key = repr(sorted((k, repr(v)) for k, v in config.items()))
    if key not in _CONFIGS:
        _CONFIGS[key] = model.program_config(config)
    return _CONFIGS[key]


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def to_program(model, w: dict) -> dict:
    """Reference-layout weights as the program's parameter tree, by the
    module's ``LEAVES`` ("group/leaf" names inside a stacked group)."""
    out: dict = {}
    for name, leaf in w.items():
        if isinstance(leaf, dict):
            for k, v in leaf.items():
                _set(out, model.LEAVES[f"{name}/{k}"], v)
        else:
            _set(out, model.LEAVES[name], leaf)
    return out


def from_program(model, tree: dict) -> dict:
    """The inverse of :func:`to_program` (for optimizer moments too)."""
    out: dict = {}
    for name, path in model.LEAVES.items():
        try:
            leaf = _get(tree, path)
        except KeyError:
            continue
        if "/" in name:
            group, k = name.split("/")
            out.setdefault(group, {})[k] = leaf
        else:
            out[name] = leaf
    return out


def check_layout(model, w: dict, cfg) -> None:
    """The mapped weights have exactly the program's parameter shapes."""
    from repro.models import transformer as tf

    want = jax.eval_shape(lambda: tf.init(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda a: a.shape, to_program(model, w))
    want = jax.tree.map(lambda a: a.shape, want)
    if got != want:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"{got}\nvs\n{want}")


def refuse_unless_kernels() -> None:
    """Exit unless JAX runs on a TPU with the Pallas kernels compiled
    for it: no jnp fallback forced, no interpret mode."""
    from repro.models import layers

    problems = []
    if jax.default_backend() != "tpu":
        problems.append(f"JAX backend is {jax.default_backend()!r}, not tpu")
    if layers.attention_impl() == "jnp":
        problems.append("attention forced to jnp (REPRO_ATTN_IMPL)")
    if layers.gemm_impl() == "jnp":
        problems.append("GEMM forced to jnp (REPRO_GEMM_IMPL)")
    if jax.default_backend() == "tpu" and layers._pallas_interpret():
        problems.append("Pallas kernels would run in interpret mode")
    if problems:
        raise SystemExit("bench: refusing to run: " + "; ".join(problems))


def serving_engine(params, cfg, engine: dict, max_len: int, pool_bytes):
    import jax.numpy as jnp

    from repro.serve.engine import ServingEngine

    return ServingEngine(
        params, cfg, max_slots=int(engine["max_slots"]), max_len=max_len,
        page_size=int(engine["page_size"]),
        prefill_chunk=int(engine["prefill_chunk"]),
        dtype=jnp.dtype(engine["weights_dtype"]),
        kv_dtype=engine["kv_dtype"], pool_bytes=pool_bytes)


def holds(eng, req) -> bool:
    """The engine still has ``req`` queued or in a slot."""
    return (any(r is req for r in eng._queue)
            or any(s.req is req for s in eng.slots))


def use_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_
    CACHE_DIR``, else a fixed directory inside the checkout), with every
    program kept, however small or quick to compile: a run's set-up
    then finds all it compiled in the cache of the run before."""
    from repro.launch.compile_cache import use_compile_cache as use

    path = use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Trainer:
    """The program's training step as a job states it: ``step`` jitted
    with the state's placement, ``mesh`` to run it under."""
    model: object
    cfg: object
    mesh: object
    step: object
    bounds: tuple | None  # the pipeline's layer cuts, None when fused
    batch_sharding: object | None

    def feed(self, tokens) -> dict:
        import jax.numpy as jnp

        if self.batch_sharding is None:
            return {"tokens": jnp.asarray(tokens)}
        return {"tokens": jax.device_put(tokens, self.batch_sharding)}

    def _canonical(self, tree):
        if self.bounds is None:
            return tree
        from repro.dist.pipeline import unpad_pipeline_params

        return unpad_pipeline_params(tree, self.cfg, self.bounds)

    def params(self, state) -> dict:
        """The state's parameters in the reference layout."""
        return from_program(self.model, self._canonical(state["params"]))

    def moments(self, state) -> dict:
        """First moments of a train state, in the reference layout."""
        return from_program(self.model, self._canonical(state["opt"].mu))


def _adamw(job: dict):
    from repro.optim import adamw

    opt = job["optimizer"]
    return adamw.AdamWConfig(
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        min_lr_ratio=opt["min_lr_ratio"],
        moments_dtype=job.get("moments_dtype", "float32"))


def train_parts(cfg, job: dict, devices):
    """What the job's training step is made of, on ``devices``:
    (mesh, step function, build(params) -> train state, the pipeline's
    layer cuts or None, the state's sharding strategy).  Without
    ``mesh`` in the job: the fused step on a one-device mesh.  With
    ``"mesh": {"stages": S, "data": D}``: the pipeline step
    (``schedule``, ``microbatches``) on S x D devices, the layers cut
    evenly and padded into the stage layout."""
    import jax.numpy as jnp

    import repro.train.step as tstep
    from repro.optim import adamw

    acfg = _adamw(job)
    mdt = jnp.dtype(acfg.moments_dtype)
    grid = job.get("mesh")
    if grid is None:
        from repro.ft.elastic import make_mesh_for

        mesh = make_mesh_for(list(devices)[:1])
        fn = tstep.make_train_step(cfg, acfg)
        bounds, strategy = None, "fused"
    else:
        from repro.dist.pipeline import even_boundaries
        from repro.launch.mesh import make_mesh

        stages, data = int(grid["stages"]), int(grid["data"])
        mesh = make_mesh((data, stages), ("data", "model"),
                         devices=list(devices)[:stages * data])
        bounds = even_boundaries(cfg.num_layers, stages)
        fn = tstep.make_pipeline_train_step(
            cfg, acfg, mesh, num_microbatches=int(job["microbatches"]),
            boundaries=bounds, schedule=job.get("schedule", "1f1b"))
        strategy = "pipeline"

    def build(params):
        if bounds is not None:
            from repro.dist.pipeline import pad_pipeline_params

            params = pad_pipeline_params(params, cfg, bounds)
        return {"params": params, "opt": adamw.init(params, mdt),
                "step": jnp.zeros((), jnp.int32)}

    return mesh, fn, build, bounds, strategy


def trainer(model, cfg, job: dict, params) -> tuple[Trainer, dict]:
    """The program's training step and its AdamW as the job states them
    (:func:`train_parts`), jitted and placed as its own launcher does,
    with the state built from ``params`` (the program's tree).  The
    pipeline's state is built in place on its mesh and its batch split
    over the data axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ft.elastic import state_shardings
    from repro.launch.train import jit_train_step

    mesh, fn, build, bounds, strategy = train_parts(cfg, job, jax.devices())
    if bounds is None:
        with mesh:
            state, step, _ = jit_train_step(fn, build(params), mesh, strategy)
        return Trainer(model, cfg, mesh, step, None, None), state
    sshard = state_shardings(jax.eval_shape(build, params), mesh, strategy)
    batch = NamedSharding(mesh, P("data"))
    with mesh:
        state = jax.jit(build, out_shardings=sshard)(params)
        step = jax.jit(fn, in_shardings=(sshard, {"tokens": batch}),
                       out_shardings=(sshard, None), donate_argnums=(0,))
    return Trainer(model, cfg, mesh, step, bounds, batch), state
