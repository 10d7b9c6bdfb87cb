"""The harness's one door into the system under test (``src/repro``):
its model configuration, parameter layout, serving engine and training
step.  The references never import this module."""

from __future__ import annotations

import sys

from bench.spec import ROOT

_SRC = str(ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402

# reference layout name -> path in the program's parameter tree
_TOP = {
    "embed": ("embed", "table"),
    "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
}
_LAYER = {
    "norm1": ("norm1", "scale"),
    "wq": ("mixer", "wq", "w"),
    "wk": ("mixer", "wk", "w"),
    "wv": ("mixer", "wv", "w"),
    "wo": ("mixer", "wo", "w"),
    "bq": ("mixer", "wq", "b"),
    "bk": ("mixer", "wk", "b"),
    "bv": ("mixer", "wv", "b"),
    "q_norm": ("mixer", "q_norm", "scale"),
    "k_norm": ("mixer", "k_norm", "scale"),
    "norm2": ("norm2", "scale"),
    "w_gate": ("ffn", "w_gate", "w"),
    "w_up": ("ffn", "w_up", "w"),
    "w_down": ("ffn", "w_down", "w"),
}


_CONFIGS: dict = {}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file; one object
    per configuration in a process, so the engine's jitted steps (kept
    per config object) are traced once."""
    key = repr(sorted((k, repr(v)) for k, v in config.items()))
    if key not in _CONFIGS:
        _CONFIGS[key] = _model_config(config)
    return _CONFIGS[key]


def _model_config(config: dict):
    from repro.configs.base import ModelConfig

    if config["model_type"] not in ("qwen2", "qwen3"):
        raise ValueError(f"no program mapping for {config['model_type']!r}")
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        qkv_bias=bool(config.get("attention_bias", False)),
        qk_norm=bool(config.get("qk_norm", False)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        max_seq_len=int(config["max_position_embeddings"]),
    )


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def _get(tree: dict, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def to_program(w: dict) -> dict:
    """Reference-layout weights as the program's parameter tree."""
    out: dict = {}
    for name, path in _TOP.items():
        if name in w:
            _set(out, path, w[name])
    for name, leaf in w["layers"].items():
        _set(out, ("blocks",) + _LAYER[name], leaf)
    return out


def from_program(tree: dict) -> dict:
    """The inverse of :func:`to_program` (for optimizer moments too)."""
    out: dict = {"layers": {}}
    for name, path in _TOP.items():
        try:
            out[name] = _get(tree, path)
        except KeyError:
            pass
    for name, path in _LAYER.items():
        try:
            out["layers"][name] = _get(tree["blocks"], path)
        except KeyError:
            pass
    return out


def check_layout(w: dict, cfg) -> None:
    """The mapped weights have exactly the program's parameter shapes."""
    from repro.models import transformer as tf

    want = jax.eval_shape(lambda: tf.init(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda a: a.shape, to_program(w))
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


def train_step(cfg, opt: dict):
    """The program's fused training step and its AdamW, jitted and
    placed as its own launcher does on a one-device mesh; returns
    (init_state(params) -> placed state, jitted step, mesh)."""
    from repro.ft.elastic import make_mesh_for
    from repro.launch.train import jit_train_step
    from repro.optim import adamw
    from repro.train.step import make_train_step

    acfg = adamw.AdamWConfig(
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        min_lr_ratio=opt["min_lr_ratio"])
    mesh = make_mesh_for(jax.devices()[:1])
    fn = make_train_step(cfg, acfg)

    def place(params):
        import jax.numpy as jnp

        state = {"params": params, "opt": adamw.init(params, jnp.float32),
                 "step": jnp.zeros((), jnp.int32)}
        with mesh:
            return jit_train_step(fn, state, mesh, "fused")

    return place, mesh


def adam_moments(state) -> dict:
    """First moments of a train state, in the reference layout."""
    return from_program(state["opt"].mu)
