"""The reference's float8 control: scaled in the backward pass as in
the forward, so its gradients carry float8 rounding, not underflow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import qwen3 as ref


@pytest.mark.parametrize("scale", [1.0, 1e-6])
def test_fp8_gradient_is_rounded_not_lost(scale):
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (4, 32, 64))
    w = jax.random.normal(kw, (64, 48)) / 8
    g = jax.random.normal(kg, (4, 32, 48)) * scale  # a small cotangent

    def grads(mode):
        return jax.grad(lambda x, w: jnp.sum(ref.mm(x, w, mode) * g),
                        argnums=(0, 1))(x, w)

    for exact, f8 in zip(grads("f32"), grads("fp8")):
        err = float(jnp.linalg.norm(f8 - exact) / jnp.linalg.norm(exact))
        assert 1e-3 < err < 0.1, err
        assert float(jnp.linalg.norm(f8) / jnp.linalg.norm(exact)) == \
            pytest.approx(1.0, abs=0.05)
    y8, y = ref.mm(x, w, "fp8"), ref.mm(x, w, "f32")
    assert np.isfinite(np.asarray(y8)).all()
    assert float(jnp.linalg.norm(y8 - y) / jnp.linalg.norm(y)) < 0.1
