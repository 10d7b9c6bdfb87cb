"""AdamW as the training job states it, in plain jax.numpy: the
reference's optimizer.  Gradients clipped to a global norm, bias-
corrected moments, decoupled weight decay on every parameter, learning
rate warmed up linearly and then decayed on a cosine to ``min_lr_ratio``.
The arithmetic is float32; parameters are rounded back to their own
dtype after each update and moments to the dtype the job holds them in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lr_at(opt: dict, step):
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    t = jnp.clip((step - opt["warmup_steps"])
                 / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def init(params, moments_dtype="float32"):
    """Zero moments, placed as the parameters are."""
    def zeros(p):
        return jnp.zeros_like(p, dtype=moments_dtype)

    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params)}


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def update(opt: dict, params, grads, state, step: int):
    """One step (``step`` counts from 1); returns (params, state,
    clipped grads)."""
    g = clip(grads, opt["grad_clip"])
    b1, b2 = opt["b1"], opt["b2"]
    f32 = jnp.float32
    mu = jax.tree.map(lambda m, x: (b1 * m.astype(f32) + (1 - b1) * x)
                      .astype(m.dtype), state["mu"], g)
    nu = jax.tree.map(lambda v, x: (b2 * v.astype(f32) + (1 - b2) * x * x)
                      .astype(v.dtype), state["nu"], g)
    lr = lr_at(opt, step)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        m, v, pf = m.astype(f32), v.astype(f32), p.astype(f32)
        d = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        return (pf - lr * (d + opt["weight_decay"] * pf)).astype(p.dtype)

    return jax.tree.map(upd, params, mu, nu), {"mu": mu, "nu": nu}, g
