"""Foundational layers (pure-functional JAX).

Every module follows the same convention:

    params = <module>_init(key, cfg_or_dims, dtype=...)
    y      = <module>_apply(params, x, ...)

Params are plain dicts of ``jnp.ndarray`` so they compose into pytrees
that pjit / checkpointing / compression handle uniformly.  Compute-heavy
matmuls run in the params' dtype (bf16 in production) with f32 for
normalization statistics and softmax, per DESIGN.md §7.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.optim.quant import quant_int8


def default_dtype():
    return jnp.bfloat16


# ---------------------------------------------------------------------------
# attention implementation dispatch
# ---------------------------------------------------------------------------

# Which backend the `flash_attend` / `decode_attend` hot paths run on:
#   "auto"   — Pallas kernels on TPU, jnp reference elsewhere (default)
#   "pallas" — force the Pallas kernels (interpret mode off-TPU; this is
#              how the CPU equivalence tests and benchmarks drive them)
#   "jnp"    — force the pure-jnp reference paths
# Seeded from $REPRO_ATTN_IMPL; switchable at runtime (re-jit applies it).
_ATTN_IMPL = os.environ.get("REPRO_ATTN_IMPL", "auto")
_ATTN_IMPLS = ("auto", "pallas", "jnp")


def set_attention_impl(impl: str) -> str:
    """Select the attention backend; returns the previous setting."""
    global _ATTN_IMPL
    if impl not in _ATTN_IMPLS:
        raise ValueError(f"impl must be one of {_ATTN_IMPLS}, got {impl!r}")
    prev, _ATTN_IMPL = _ATTN_IMPL, impl
    return prev


def attention_impl() -> str:
    return _ATTN_IMPL


def _pallas_attention() -> bool:
    if _ATTN_IMPL == "pallas":
        return True
    return _ATTN_IMPL == "auto" and jax.default_backend() == "tpu"


def _pallas_interpret() -> bool:
    # off-TPU the kernels run in the Pallas interpreter (test/CI path)
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# quantized-GEMM implementation dispatch
# ---------------------------------------------------------------------------

# Which backend quantized dense layers (``quant_dense_apply``) run on —
# same contract as the attention dispatch above:
#   "auto"   — VTA Pallas GEMM (fused dequant epilogue) on TPU, jnp
#              int8 reference elsewhere
#   "pallas" — force the Pallas kernel (interpret mode off-TPU)
#   "jnp"    — force the jnp reference
# Seeded from $REPRO_GEMM_IMPL; switchable at runtime (re-jit applies it).
_GEMM_IMPL = os.environ.get("REPRO_GEMM_IMPL", "auto")


def set_gemm_impl(impl: str) -> str:
    """Select the quantized-GEMM backend; returns the previous setting."""
    global _GEMM_IMPL
    if impl not in _ATTN_IMPLS:
        raise ValueError(f"impl must be one of {_ATTN_IMPLS}, got {impl!r}")
    prev, _GEMM_IMPL = _GEMM_IMPL, impl
    return prev


def gemm_impl() -> str:
    return _GEMM_IMPL


def _pallas_gemm() -> bool:
    if _GEMM_IMPL == "pallas":
        return True
    return _GEMM_IMPL == "auto" and jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# measured-cost tuning dispatch
# ---------------------------------------------------------------------------

# A ``core.autotune.TuningTable`` (``tune_runtime``'s output) consulted
# by the hot-path dispatchers below and by ``serve.engine.ServingEngine``
# for knobs the caller left unset: flash ``block_q``/``block_k``, decode
# split-KV ``block_k``, GEMM block overrides, serving ``page_size`` /
# ``prefill_chunk``.  Same contract as the impl dispatchers above:
# seeded from $REPRO_TUNING (a table file path, loaded lazily and
# ignored if its device signature doesn't match this process), and
# switchable at runtime via ``set_tuning`` (re-jit applies it).
# Explicit call-site arguments always win over the table.
_TUNING = None
_TUNING_LOADED = False


def set_tuning(table) -> object:
    """Install a ``TuningTable`` (or None to untune); returns the
    previous table so callers can restore it."""
    global _TUNING, _TUNING_LOADED
    prev, _TUNING, _TUNING_LOADED = _TUNING, table, True
    return prev


def tuning_table():
    """The active ``TuningTable`` (None = defaults).  First call loads
    $REPRO_TUNING if set; a table measured on a different
    backend/device/impl signature is ignored."""
    global _TUNING, _TUNING_LOADED
    if not _TUNING_LOADED:
        _TUNING_LOADED = True
        path = os.environ.get("REPRO_TUNING")
        if path:
            from repro.core.autotune import TuningTable
            from repro.core.measure import device_signature

            table = TuningTable.load(path)
            if table.device in ("any", device_signature()):
                _TUNING = table
    return _TUNING


def tuned(kind: str) -> dict:
    """Tuned knobs for one cost kind ({} when untuned)."""
    t = tuning_table()
    return t.get(kind) if t is not None else {}


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: float | None = None):
    if scale is None:
        scale = 1.0 / (d_in ** 0.5)
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x):
    if "qw" in p:
        return quant_dense_apply(p, x)
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def quant_dense_apply(p, x, act: str | None = None):
    """QuantizedLinear forward: int8 weights (per-output-channel scales,
    ``optim.quant.quantize_dense``) against dynamically int8-quantized
    activations, int32 accumulation, fused dequant->bias->``act``.

    Dispatcher twin of ``flash_attend``: on the Pallas path this is ONE
    ``vta_gemm`` call with the dequant epilogue — the f32 pre-activation
    never exists in HBM; the jnp reference quantizes the activations the
    SAME way and accumulates through the same exact int32 lattice, so
    the two backends agree to float rounding.
    """
    lead, k = x.shape[:-1], x.shape[-1]
    qx, sx = quant_int8(x.reshape(-1, k))
    # the dynamic per-tensor activation scale folds into the epilogue's
    # per-channel weight scales — one multiplier per output column
    scale = p["qscale"].astype(jnp.float32) * sx
    bias = p["b"].astype(jnp.float32) if "b" in p else None
    if _pallas_gemm():
        from repro.kernels.ops import dense_int8

        blocks = {k: int(v) for k, v in tuned("gemm_int8").items()
                  if k in ("block_m", "block_n", "block_k")}
        y = dense_int8(qx, p["qw"], scale, bias=bias, act=act,
                       interpret=_pallas_interpret(), **blocks)
    else:
        acc = jnp.dot(qx.astype(jnp.int32), p["qw"].astype(jnp.int32))
        y = acc.astype(jnp.float32) * scale[None, :]
        if bias is not None:
            y = y + bias
        y = _epilogue_act(y, act)
    return y.reshape(*lead, -1).astype(x.dtype)


def _epilogue_act(y, act):
    from repro.kernels.vta_gemm import _apply_act

    return _apply_act(y, act)


def embedding_init(key, vocab: int, d: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embedding_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def embedding_logits(p, x):
    """Tied-softmax readout."""
    return x @ p["table"].T


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def gated_mlp_init(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, d_ff, dtype),
        "w_up": dense_init(k2, d, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d, dtype),
    }


def gated_mlp_apply(p, x):
    if "qw" in p["w_gate"]:
        # quantized path: silu fuses into the gate GEMM's epilogue —
        # dequant -> silu is one kernel, no f32 intermediate in HBM
        g = quant_dense_apply(p["w_gate"], x, act="silu")
        u = quant_dense_apply(p["w_up"], x)
        return quant_dense_apply(p["w_down"], g * u)
    g = jax.nn.silu(dense_apply(p["w_gate"], x).astype(jnp.float32)).astype(x.dtype)
    u = dense_apply(p["w_up"], x)
    return dense_apply(p["w_down"], g * u)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def causal_mask(q_len: int, kv_len: int, *, window: int = 0,
                q_offset: int = 0) -> jnp.ndarray:
    """Boolean mask (q_len, kv_len): True = attend.

    ``q_offset`` is the absolute position of query 0 (decode: cache_len).
    ``window`` > 0 enables sliding-window attention (mixtral SWA).
    """
    q_pos = jnp.arange(q_len) + q_offset
    kv_pos = jnp.arange(kv_len)
    mask = kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= kv_pos[None, :] > (q_pos[:, None] - window)
    return mask


def flash_attend(
    q,
    k,
    v,
    *,
    q_offset=0,
    window: int = 0,
    bidirectional: bool = False,
    scale: float | None = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    kv_len=None,
    block_q: int | None = None,
    block_k: int | None = None,
):
    """Tiled online-softmax attention — never materializes (S, T) logits.

    Dispatcher: on TPU (or when forced via ``set_attention_impl`` /
    $REPRO_ATTN_IMPL) this lowers to the Pallas flash kernel, whose
    block-level causal/window masking *skips* fully-masked KV tiles
    (~2x prefill FLOPs saved, EXPERIMENTS.md §Perf); elsewhere it runs
    ``flash_attend_ref``, the two-level jnp scan, identical interface.

    q: (B,S,H,D); k/v: (B,T,Hkv,Dv); GQA grouping handled internally.
    ``q_offset``: absolute position of query 0 (decode/prefill resume).
    ``kv_len``: dynamic count of valid kv positions (padded caches).
    ``block_q``/``block_k`` override the tile sizes on BOTH impls
    (Pallas grid blocks / reference chunk sizes); left None they resolve
    through the tuning table (``set_tuning``), else the legacy defaults
    (Pallas ``min(chunk, 128)``, reference ``q_chunk``/``kv_chunk``).
    """
    if block_q is None or block_k is None:
        t = tuned("flash_prefill")
        block_q = block_q if block_q is not None else t.get("block_q")
        block_k = block_k if block_k is not None else t.get("block_k")
    if _pallas_attention():
        from repro.dist.sharding import per_shard
        from repro.kernels.flash_attention import flash_attention

        def kernel(q, k, v, q_offset, kv_len):
            return flash_attention(
                q, k, v, q_offset=q_offset, window=window,
                bidirectional=bidirectional, scale=scale, kv_len=kv_len,
                block_q=int(block_q) if block_q else min(q_chunk, 128),
                block_k=int(block_k) if block_k else min(kv_chunk, 128),
                interpret=_pallas_interpret(),
            )

        return per_shard(kernel, (q, k, v),
                         (q_offset, k.shape[1] if kv_len is None else kv_len))
    return flash_attend_ref(
        q, k, v, q_offset=q_offset, window=window,
        bidirectional=bidirectional, scale=scale,
        q_chunk=int(block_q) if block_q else q_chunk,
        kv_chunk=int(block_k) if block_k else kv_chunk, kv_len=kv_len,
    )


def flash_attend_ref(
    q,
    k,
    v,
    *,
    q_offset=0,
    window: int = 0,
    bidirectional: bool = False,
    scale: float | None = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    kv_len=None,
):
    """jnp reference: two-level scan with online softmax.

    The tile working set is (q_chunk x kv_chunk) — what makes train_4k
    and prefill_32k lowerable at pod scale on any backend.  Same FLOPs
    as direct attention (untaken causal tiles are still computed — the
    rectangular-scan trade the Pallas kernel removes).  Also serves as
    the Pallas kernel's backward-pass recompute target.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5

    def pick_chunk(n, target):
        c = min(target, n)
        while n % c:
            c -= 1
        return c

    qc = pick_chunk(s, q_chunk)  # largest divisor <= target (4352 -> 272)
    kc = pick_chunk(t, kv_chunk)
    nq, nk = s // qc, t // kc

    qf = (q.astype(jnp.float32) * scale).reshape(b, nq, qc, hkv, g, d)
    kf = k.astype(jnp.float32).reshape(b, nk, kc, hkv, d)
    vf = v.astype(jnp.float32).reshape(b, nk, kc, hkv, dv)

    q_pos_base = jnp.arange(qc)
    kv_pos_base = jnp.arange(kc)

    def q_block(qi, q_tile):
        q_pos = q_offset + qi * qc + q_pos_base  # (qc,)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, inp):
            m, l, acc = carry
            kj, k_tile, v_tile = inp
            kv_pos = kj * kc + kv_pos_base  # (kc,)
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_tile, k_tile)
            mask = jnp.ones((qc, kc), bool)
            if not bidirectional:
                mask &= kv_pos[None, :] <= q_pos[:, None]
                if window:
                    mask &= kv_pos[None, :] > (q_pos[:, None] - window)
            if kv_len is not None:
                mask &= (kv_pos < kv_len)[None, :]
            logits = jnp.where(mask[None, None, None, :, :], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_tile
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qc, dv), jnp.float32)
        ks = jnp.moveaxis(kf, 1, 0)  # (nk, b, kc, hkv, d)
        vs = jnp.moveaxis(vf, 1, 0)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (b,hkv,g,qc,dv)
        return jnp.moveaxis(out, 3, 1)  # (b,qc,hkv,g,dv)

    outs = jax.lax.map(
        lambda args: q_block(*args),
        (jnp.arange(nq), jnp.moveaxis(qf, 1, 0)),
    )  # (nq, b, qc, hkv, g, dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dv)
    return out.astype(q.dtype)


def softmax_attend(q, k, v, mask=None, *, scale: float | None = None):
    """q: (B,S,H,D)  k/v: (B,T,Hkv,D[v]) with H % Hkv == 0 (GQA).

    ``mask``: (S, T) boolean, True = attend; None = full attention
    (no (S, T) allocation).  f32 softmax; returns (B,S,H,Dv).
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, s, hkv, group, d)
    scale = scale if scale is not None else d ** -0.5
    logits = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def decode_attend(q, k, v, *, kv_len, window: int = 0,
                  scale: float | None = None,
                  block_k: int | None = None):
    """Single-token decode attention over a padded KV cache.

    q: (B,1,H,D); k/v: (B,T,Hkv,D[v]) with the new token's K/V already
    written, so the query's absolute position is ``kv_len - 1`` (traced).
    Dispatcher twin of ``flash_attend``: the Pallas split-KV kernel costs
    O(kv_len) per step; the jnp fallback masks the full O(T) buffer.
    ``block_k`` sets the kernel's split-KV partition size (None resolves
    through the tuning table, else the kernel default; the jnp fallback
    has no partitioning so the knob is a no-op there).
    """
    if block_k is None:
        block_k = tuned("decode").get("block_k")
    if _pallas_attention():
        from repro.kernels.decode_attention import (
            DEFAULT_BLOCK_K, decode_attention)

        return decode_attention(
            q, k, v, kv_len=kv_len, window=window, scale=scale,
            block_k=int(block_k) if block_k else DEFAULT_BLOCK_K,
            interpret=_pallas_interpret(),
        )
    # q_pos = kv_len - 1, so "<= q_pos" doubles as the kv_len clamp
    mask = causal_mask(1, k.shape[1], window=window, q_offset=kv_len - 1)
    return softmax_attend(q, k, v, mask, scale=scale)


def paged_decode_attend(q, k_pages, v_pages, block_tables, kv_lens, *,
                        window: int = 0, scale: float | None = None,
                        dv: int | None = None, k_scales=None, v_scales=None):
    """Decode attention over a paged KV pool (S=1 decode; S>1 verifies
    S consecutive positions per sequence, the speculative-decoding
    verify step).

    q: (B,S,H,D) — position of query s is ``kv_lens[b] - S + s``;
    k_pages/v_pages: (Hkv, num_pages, page_size, W) shared
    pools; block_tables: (B, pages_per_seq) int32 page indices (-1 past
    a sequence's live pages / for inactive slots); kv_lens: (B,)
    per-sequence live token counts INCLUDING the just-written token(s)
    (0 = inactive slot, output exactly zero).  ``dv`` restricts values
    to the leading columns of ``v_pages`` (the MLA shared-pool trick).
    int8 pools pass their (Hkv, num_pages) per-page-per-head
    ``k_scales``/``v_scales`` — dequantization happens inside the
    kernel, right after the page DMA.
    Dispatcher triplet of ``decode_attend``: the Pallas kernel DMAs
    pages straight through the block table; the jnp fallback gathers
    the pages dense and masks per sequence.
    """
    if _pallas_attention():
        from repro.kernels.decode_attention import paged_decode_attention

        return paged_decode_attention(
            q, k_pages, v_pages, block_tables, kv_lens, window=window,
            scale=scale, dv=dv, k_scales=k_scales, v_scales=v_scales,
            interpret=_pallas_interpret(),
        )
    return paged_decode_attend_ref(q, k_pages, v_pages, block_tables,
                                   kv_lens, window=window, scale=scale,
                                   dv=dv, k_scales=k_scales,
                                   v_scales=v_scales)


def paged_decode_attend_ref(q, k_pages, v_pages, block_tables, kv_lens, *,
                            window: int = 0, scale: float | None = None,
                            dv: int | None = None, k_scales=None,
                            v_scales=None):
    """jnp reference: gather each sequence's pages into a dense
    (B, T, Hkv, W) view (T = pages_per_seq * page_size, position order
    preserved, int8 pages dequantized by their page scale) and attend
    with a per-sequence length/window mask."""
    b, s, h, d = q.shape
    hkv, num_pages, pg, _ = k_pages.shape
    g = h // hkv
    dv = v_pages.shape[-1] if dv is None else dv
    scale = scale if scale is not None else d ** -0.5
    bt = jnp.clip(block_tables, 0, num_pages - 1)
    t = bt.shape[1] * pg

    def gather(pages, w, scales):
        dense = pages[:, bt]  # (Hkv, B, pages_per_seq, pg, W)
        if scales is not None:
            dense = dense.astype(jnp.float32) * scales[:, bt][..., None, None]
        return dense.transpose(1, 2, 3, 0, 4).reshape(b, t, hkv, -1)[..., :w]

    kd = gather(k_pages, d, k_scales).astype(jnp.float32)
    vd = gather(v_pages, dv, v_scales).astype(jnp.float32)
    lens = jnp.asarray(kv_lens, jnp.int32)
    kv_pos = jnp.arange(t)
    # query s of sequence b sits at absolute position lens[b] - S + s;
    # each attends its own causal (and window) range
    q_pos = lens[:, None] - s + jnp.arange(s)[None, :]  # (B, S)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]  # (B, S, T)
    if window > 0:
        mask &= kv_pos[None, None, :] > (q_pos[:, :, None] - window)

    qg = (q.astype(jnp.float32) * scale).reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bshgd,bthd->bshgt", qg, kd)
    logits = jnp.where(mask[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bshgt,bthd->bshgd", probs, vd)
    # fully-masked rows (inactive slots) must be exactly zero, like the
    # kernel's all-dead combine
    out = out * (lens > 0)[:, None, None, None, None]
    return out.reshape(b, s, h, dv).astype(q.dtype)
