"""Weights of a configuration, made on the device from ``--seed`` in one
jitted call, in the layout of the plain references (``bench/reference``).

Dense weights are normal with variance 1/fan_in, the embedding normal
with std 0.02, norm scales 1 + 0.1 * normal (so that a scale wired to
the wrong place shows), biases normal with std 0.02.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import Dims


def key_for(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (more than 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def shapes(config: dict) -> dict:
    d = Dims.of(config)
    L, D, hd = d.layers, d.d_model, d.head_dim
    layers = {
        "norm1": (L, D),
        "wq": (L, D, d.heads * hd),
        "wk": (L, D, d.kv_heads * hd),
        "wv": (L, D, d.kv_heads * hd),
        "wo": (L, d.heads * hd, D),
        "norm2": (L, D),
        "w_gate": (L, D, d.d_ff),
        "w_up": (L, D, d.d_ff),
        "w_down": (L, d.d_ff, D),
    }
    if config.get("qk_norm"):
        layers.update(q_norm=(L, hd), k_norm=(L, hd))
    if config.get("attention_bias"):
        layers.update(bq=(L, d.heads * hd), bk=(L, d.kv_heads * hd),
                      bv=(L, d.kv_heads * hd))
    out = {"embed": (d.vocab, D), "final_norm": (D,), "layers": layers}
    if not d.tied:
        out["lm_head"] = (D, d.vocab)
    return out


def _draw(key, name: str, shape, dtype):
    base = name.split("/")[-1]
    if base.startswith("norm") or base.endswith("_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif base == "embed":
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif base in ("bq", "bk", "bv"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:  # (..., fan_in, fan_out)
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    return x.astype(dtype)


def _make(key, config, dtype):
    flat = []
    for name, shape in shapes(config).items():
        if isinstance(shape, dict):
            flat += [(f"{name}/{k}", v) for k, v in shape.items()]
        else:
            flat.append((name, shape))
    out: dict = {}
    for i, (name, shape) in enumerate(sorted(flat)):
        leaf = _draw(jax.random.fold_in(key, i), name, shape, dtype)
        if "/" in name:
            group, k = name.split("/")
            out.setdefault(group, {})[k] = leaf
        else:
            out[name] = leaf
    return out


@functools.lru_cache(maxsize=None)
def _maker(config_items: tuple, dtype_name: str):
    config = dict(config_items)
    return jax.jit(functools.partial(_make, config=config,
                                     dtype=jnp.dtype(dtype_name)))


def make(config: dict, seed: int, dtype) -> dict:
    """All weights of ``config`` for ``seed``, as ``dtype``, on the
    default device."""
    items = tuple(sorted((k, v) for k, v in config.items()
                         if isinstance(v, (int, float, str, bool))))
    return _maker(items, jnp.dtype(dtype).name)(key_for(seed))
