"""VTA GEMM core as a Pallas TPU kernel.

The paper's accelerator computes int8 x int8 -> int32 GEMMs with a
(BATCH, BLOCK) x (BLOCK, BLOCK) tensor intrinsic fed from on-chip SRAM
buffers by decoupled load/compute/store modules (RAW/WAR queues).

TPU adaptation (DESIGN.md §2): the intrinsic becomes an MXU matmul over
VMEM tiles; the decoupled load/compute/store pipeline IS the Pallas grid
pipeline (the compiler double-buffers tiles between HBM and VMEM
automatically, which is exactly what VTA's dependency queues do by
hand); the SRAM buffer sizes of Table I become the BlockSpec tile sizes.
VTA's 16x16 native block is kept as the *minimum* tile; production tiles
are 128-multiples so the 128x128 MXU runs full.

The ALU stage (paper: 'addition, activation, pooling') appears here as
the fused epilogue: bias add, right-shift requantization (VTA's fixed
point path) or f32 scale dequantization, ReLU, int8 clip.

Validated in interpret mode against ``ref.py`` over shape/dtype sweeps
(tests/test_kernels.py), including the Table I and §IV (BLOCK=32)
configurations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _int8_dot(a, w):
    """int8 operands straight into the MXU, int32 accumulation.  The
    product is exact, so precision is pinned to DEFAULT: under
    ``default_matmul_precision("highest")`` Mosaic would otherwise be
    asked for an fp32 contraction, which it refuses for int8."""
    return jnp.dot(a, w, precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.int32)


def _gemm_kernel(a_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    """Tiled int8 GEMM with int32 VMEM accumulator."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _int8_dot(a_ref[...], w_ref[...])

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...]


def _gemm_epilogue_kernel(
    a_ref, w_ref, bias_ref, out_ref, acc_ref, *, n_k: int, shift: int, relu: bool
):
    """GEMM + VTA ALU epilogue: bias, right-shift requant, ReLU, int8 clip."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _int8_dot(a_ref[...], w_ref[...])

    @pl.when(k == n_k - 1)
    def _store():
        acc = acc_ref[...] + bias_ref[...].astype(jnp.int32)
        # VTA requantization: arithmetic right shift (round toward -inf)
        acc = jax.lax.shift_right_arithmetic(acc, shift)
        if relu:
            acc = jnp.maximum(acc, 0)
        out_ref[...] = jnp.clip(acc, -128, 127).astype(jnp.int8)


def _apply_act(y, act):
    """Static-act epilogue nonlinearity (f32 in, f32 out)."""
    if act is None or act == "none":
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "silu":
        return jax.nn.silu(y)
    if act == "gelu":
        return jax.nn.gelu(y)
    raise ValueError(f"unknown epilogue act {act!r}")


def _gemm_dequant_kernel(
    a_ref, w_ref, scale_ref, *refs, n_k: int, act, with_bias: bool
):
    """GEMM + f32 per-output-channel dequant -> bias -> activation
    (serving path): the whole int8-GEMM epilogue is one kernel, so the
    f32 pre-activation never round-trips through HBM."""
    bias_ref = refs[0] if with_bias else None
    out_ref, acc_ref = refs[1 if with_bias else 0:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _int8_dot(a_ref[...], w_ref[...])

    @pl.when(k == n_k - 1)
    def _store():
        y = acc_ref[...].astype(jnp.float32) * scale_ref[...]
        if with_bias:
            y = y + bias_ref[...]
        out_ref[...] = _apply_act(y, act)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "epilogue", "shift",
                     "relu", "act", "interpret"),
)
def vta_gemm(
    a: jax.Array,  # (M, K) int8
    w: jax.Array,  # (K, N) int8
    bias: jax.Array | None = None,  # (N,) int32 [requant] / f32 [dequant]
    scale: jax.Array | None = None,  # (N,) f32    [epilogue="dequant"]
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    epilogue: str = "none",  # none | requant | dequant
    shift: int = 8,
    relu: bool = True,
    act: str | None = None,  # dequant epilogue: none | relu | silu | gelu
    interpret: bool = False,
) -> jax.Array:
    """Blocked VTA GEMM.  M/N/K must be multiples of the block sizes
    (``ops.py`` pads arbitrary shapes)."""
    m, k = a.shape
    k2, n = w.shape
    assert k == k2, (a.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"{(m, n, k)} not multiples of {(block_m, block_n, block_k)}"
    )
    grid = (m // block_m, n // block_n, k // block_k)
    n_k = grid[2]

    a_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    w_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
    out_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
    acc = pltpu_scratch((block_m, block_n), jnp.int32)

    common = dict(
        grid=grid,
        scratch_shapes=[acc],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )

    if epilogue == "none":
        return pl.pallas_call(
            functools.partial(_gemm_kernel, n_k=n_k),
            name="vta_gemm",
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
            in_specs=[a_spec, w_spec],
            out_specs=out_spec,
            **common,
        )(a, w)
    if epilogue == "requant":
        assert bias is not None
        bias2d = jnp.broadcast_to(bias[None, :], (1, n))
        bias_spec = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
        return pl.pallas_call(
            functools.partial(_gemm_epilogue_kernel, n_k=n_k, shift=shift, relu=relu),
            name="vta_gemm_requant",
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
            in_specs=[a_spec, w_spec, bias_spec],
            out_specs=out_spec,
            **common,
        )(a, w, bias2d)
    if epilogue == "dequant":
        assert scale is not None
        scale2d = jnp.broadcast_to(scale[None, :], (1, n))
        row_spec = pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j))
        in_specs = [a_spec, w_spec, row_spec]
        operands = [a, w, scale2d]
        if bias is not None:
            in_specs.append(row_spec)
            operands.append(
                jnp.broadcast_to(bias.astype(jnp.float32)[None, :], (1, n)))
        return pl.pallas_call(
            functools.partial(_gemm_dequant_kernel, n_k=n_k, act=act,
                              with_bias=bias is not None),
            name="vta_gemm_dequant",
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            in_specs=in_specs,
            out_specs=out_spec,
            **common,
        )(*operands)
    raise ValueError(f"unknown epilogue {epilogue!r}")


def pltpu_scratch(shape, dtype):
    """VMEM scratch allocation (interpret-mode compatible)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def vmem_footprint_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """Working set one grid step claims in VMEM (A+W tiles, int8; out +
    acc tiles, int32/int8) — must fit the 16 MiB/core budget with 2x for
    the pipeline's double buffering."""
    a = block_m * block_k
    w = block_k * block_n
    out = block_m * block_n * 4
    acc = block_m * block_n * 4
    return 2 * (a + w) + out + acc
