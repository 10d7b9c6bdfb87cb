"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at Qwen3-0.6B widths (16 query heads, 8 KV heads, head_dim
128; d_model 1024, d_ff 3072).

Interpret mode does not apply the chip compiler's tiling and memory
rules; these compiles do, with no chip.  Nothing here runs a kernel, so
nothing here says anything about results or times.  The topology is
described inside a fixture, never at import: only the process that
loads the TPU compiler may hold it, and every test that needs it lives
in this one file.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

H, HKV, D = 16, 8, 128
D_MODEL, D_FF = 1024, 3072
SLOTS, PAGE, POOL_PAGES, PAGES_PER_SEQ = 8, 16, 512, 36
# the serving cells' engine: 64 slots of 4,096 tokens over 391 pages of 256
SERVE_SLOTS, SERVE_PAGE, SERVE_POOL_PAGES, SERVE_MAX_LEN = 64, 256, 391, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # entries compiled for a described chip cannot be read back here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_prefill(one_chip):
    from repro.kernels.flash_attention import flash_attention

    q = _spec((1, 1024, H, D), jnp.float32, one_chip)
    kv = _spec((1, 1024, HKV, D), jnp.float32, one_chip)
    _compiles_to_kernel(
        lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=128),
        q, kv, kv)


def test_split_kv_decode(one_chip):
    from repro.kernels.decode_attention import decode_attention

    q = _spec((SLOTS, 1, H, D), jnp.float32, one_chip)
    kv = _spec((SLOTS, 1024, HKV, D), jnp.float32, one_chip)
    n = _spec((), jnp.int32, one_chip)
    _compiles_to_kernel(
        lambda q, k, v, n: decode_attention(q, k, v, kv_len=n), q, kv, kv, n)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
def test_paged_decode(one_chip, dtype):
    from repro.kernels.decode_attention import paged_decode_attention

    q = _spec((SLOTS, 1, H, D), jnp.float32, one_chip)
    pages = _spec((HKV, POOL_PAGES, PAGE, D), dtype, one_chip)
    bt = _spec((SLOTS, PAGES_PER_SEQ), jnp.int32, one_chip)
    lens = _spec((SLOTS,), jnp.int32, one_chip)
    if dtype == jnp.int8:
        scales = _spec((HKV, POOL_PAGES), jnp.float32, one_chip)
        _compiles_to_kernel(
            lambda q, k, v, bt, n, ks, vs: paged_decode_attention(
                q, k, v, bt, n, k_scales=ks, v_scales=vs),
            q, pages, pages, bt, lens, scales, scales)
    else:
        _compiles_to_kernel(paged_decode_attention, q, pages, pages, bt, lens)


@pytest.mark.parametrize("precision", [None, "highest"])
def test_vta_gemm_dequant_epilogue(one_chip, precision):
    """The int8 GEMM with its dequant + silu epilogue (the serving MLP's
    gate); also under the highest matmul precision the chip logits check
    uses."""
    from repro.kernels.ops import dense_int8

    a = _spec((64, D_MODEL), jnp.int8, one_chip)
    w = _spec((D_MODEL, D_FF), jnp.int8, one_chip)
    s = _spec((D_FF,), jnp.float32, one_chip)
    with jax.default_matmul_precision(precision):
        _compiles_to_kernel(lambda a, w, s: dense_int8(a, w, s, act="silu"),
                            a, w, s)


def test_flash_per_shard_on_2x2_mesh(topo):
    """Under a 2x2 (data, model) mesh the flash kernel compiles through
    ``per_shard``; called bare, Mosaic refuses to be partitioned."""
    from repro.dist.sharding import per_shard
    from repro.kernels.flash_attention import flash_attention
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    sh = NamedSharding(mesh, P("data", None, "model"))
    q = _spec((4, 512, H, D), jnp.float32, sh)
    kv = _spec((4, 512, HKV, D), jnp.float32, sh)

    def fn(q, k, v):
        return per_shard(
            lambda q, k, v, n: flash_attention(q, k, v, kv_len=n),
            (q, k, v), (512,))

    with mesh:
        _compiles_to_kernel(fn, q, kv, kv)


@pytest.fixture
def chip_kernels(monkeypatch):
    """Make the model dispatch its Pallas kernels, lowered for the
    described chip (this process sees only the CPU backend)."""
    from repro.models import layers

    monkeypatch.setattr(layers, "_pallas_interpret", lambda: False)
    prev = layers.set_attention_impl("pallas")
    yield
    layers.set_attention_impl(prev)


def _pool_sized_copies(text, n_elements):
    """Every ``copy`` in compiled HLO whose result holds as many elements
    as a KV pool: the pool relaid out, whatever shape it is viewed in."""
    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* copy\(", text):
        dims = [int(x) for x in m.group(1).split(",") if x]
        if math.prod(dims) == n_elements:
            found.append(m.group(0))
    return found


@pytest.mark.parametrize("program", ["serve_step", "write_prompt_pages"])
def test_paged_kv_write_keeps_pool_in_place(one_chip, chip_kernels, program):
    """The KV write of a decode step and of an admission's prompt write
    leaves the donated bf16 pools where they are: no copy of a whole
    pool, in its own shape or its flat row view (2 layers at Qwen3-0.6B
    widths, the serving cells' slots, pages and pool)."""
    from repro.configs.base import get_config
    from repro.models import transformer as tf
    from repro.serve import kv_cache
    from repro.serve.step import make_serve_step

    cfg = dataclasses.replace(get_config("qwen3_0p6b"), num_layers=2)

    def shaped(fn):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            jax.eval_shape(fn))

    caches = shaped(lambda: tf.init_caches(
        cfg, SERVE_SLOTS, SERVE_MAX_LEN, jnp.bfloat16, cache_layout="paged",
        page_size=SERVE_PAGE, num_pages=SERVE_POOL_PAGES))
    if program == "serve_step":
        params = shaped(lambda: tf.init(jax.random.PRNGKey(0), cfg,
                                        jnp.bfloat16))
        tok = _spec((SERVE_SLOTS, 1), jnp.int32, one_chip)
        step = jax.jit(make_serve_step(cfg), donate_argnums=(2,))
        compiled = step.lower(params, tok, caches).compile()
    else:
        dense = shaped(lambda: tf.init_caches(cfg, 1, 1024, jnp.bfloat16))
        row = _spec((SERVE_MAX_LEN // SERVE_PAGE,), jnp.int32, one_chip)
        n = _spec((), jnp.int32, one_chip)
        write = jax.jit(kv_cache.write_prompt_pages, donate_argnums=(0,))
        compiled = write.lower(caches["blocks"], dense["blocks"], row,
                               n, n, n).compile()
    text = compiled.as_text()
    pool = caches["blocks"][0]["k_pages"]
    assert pool.shape == (HKV, SERVE_POOL_PAGES, SERVE_PAGE, D)
    assert " scatter(" in text
    assert _pool_sized_copies(text, math.prod(pool.shape)) == []
