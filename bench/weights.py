"""Weights of a configuration, made on the device from ``--seed`` in one
jitted call, in the layout of the plain references (``bench/reference``)
that the model type's module (``bench/models``) gives as shapes.

Dense weights are normal with variance 1/fan_in, the embedding normal
with std 0.02, norm scales 1 + 0.1 * normal (so that a scale wired to
the wrong place shows), biases normal with std 0.02; the module's
``draw(name)`` says which a weight is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_for(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (more than 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 62) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def _draw(key, kind: str, shape, dtype):
    if kind == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind in ("embedding", "bias"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "matrix":  # (..., fan_in, fan_out)
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    else:
        raise ValueError(f"unknown way to draw a weight: {kind!r}")
    return x.astype(dtype)


def _flat(model, config) -> list[tuple[str, tuple]]:
    """(name, shape) of every weight, a stacked group's as "group/leaf"."""
    flat = []
    for name, shape in model.shapes(config).items():
        if isinstance(shape, dict):
            flat += [(f"{name}/{k}", v) for k, v in shape.items()]
        else:
            flat.append((name, shape))
    return sorted(flat)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, leaf in flat.items():
        if "/" in name:
            group, k = name.split("/")
            out.setdefault(group, {})[k] = leaf
        else:
            out[name] = leaf
    return out


def _make(key, model, config, dtype):
    return _nest({name: _draw(jax.random.fold_in(key, i), model.draw(name),
                              shape, dtype)
                  for i, (name, shape) in enumerate(_flat(model, config))})


def spread(mesh, shape):
    """A weight's placement over every device of a one-axis ``mesh``:
    its largest dimension that the devices divide is split among them,
    and the weight is copied whole where none is."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    dims = [d for d in range(len(shape)) if shape[d] % n == 0]
    spec = [None] * len(shape)
    if dims:
        spec[max(dims, key=lambda d: shape[d])] = mesh.axis_names[0]
    return NamedSharding(mesh, P(*spec))


@functools.lru_cache(maxsize=None)
def _maker(model, config_items: tuple, dtype_name: str, mesh):
    config = dict(config_items)
    out = None
    if mesh is not None:
        out = _nest({name: spread(mesh, shape)
                     for name, shape in _flat(model, config)})
    return jax.jit(functools.partial(_make, model=model, config=config,
                                     dtype=jnp.dtype(dtype_name)),
                   out_shardings=out)


def make(model, config: dict, seed: int, dtype, mesh=None) -> dict:
    """All weights of ``config`` for ``seed``, as ``dtype``: on the
    default device, or with a one-axis ``mesh`` spread over its devices
    (:func:`spread`)."""
    items = tuple(sorted((k, v) for k, v in config.items()
                         if isinstance(v, (int, float, str, bool))))
    return _maker(model, items, jnp.dtype(dtype).name, mesh)(key_for(seed))
