"""Plain reference of the Qwen3 dense decoder, in float32 jax.numpy at
the highest matmul precision: no kernels, no cache, no batching.

Follows the published architecture (hf Qwen/Qwen3-0.6B, modeling_qwen3):
pre-norm RMSNorm blocks, GQA attention with RMSNorm on each query and
key head before rotary embedding (rotate-half, theta from the config),
causal softmax, SwiGLU MLP, final RMSNorm, tied or separate head.
``attention_bias`` adds the Qwen2 bias on q, k and v.

``mode`` sets the arithmetic of every matmul:
  "f32"  float32 operands at Precision.HIGHEST (the reference),
  "bf16" bfloat16 operands and results (the training control),
  "fp8"  float8 e4m3 operands, each scaled to the format's largest
         value: weights per output channel and activations per row in
         the forward pass; in the backward pass the cotangents per row
         against the weights per input channel, and the activations
         against the cotangents per column (the controls).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def _f8_dot(x, w):
    """x (..., K) @ w (K, N) in float8: x scaled per row, w per column."""
    qx, sx = _f8(x, -1)
    qw, sw = _f8(w, 0)
    return jnp.dot(qx, qw, precision=HI) * sx * sw


@jax.custom_vjp
def _mm_f8(x, w):
    return _f8_dot(x, w)


def _mm_f8_fwd(x, w):
    return _f8_dot(x, w), (x, w)


def _mm_f8_bwd(res, g):
    x, w = res
    dx = _f8_dot(g, w.T)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dw = _f8_dot(x2.T, g2)
    return dx, dw


_mm_f8.defvjp(_mm_f8_fwd, _mm_f8_bwd)


def mm(x, w, mode: str):
    if mode == "f32":
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       precision=HI)
    if mode == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    if mode == "fp8":
        return _mm_f8(x.astype(jnp.float32), w.astype(jnp.float32))
    raise ValueError(f"unknown mode {mode!r}")


def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def attention(q, k, v, mode):
    """Causal GQA attention of one sequence: q (S, H, hd), k/v (S, Hkv, hd)."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    prec = HI if mode != "bf16" else None
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    logits = jnp.einsum("shd,thd->hst", q.astype(dt), k.astype(dt),
                        precision=prec).astype(jnp.float32) * hd ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hst,thd->shd", p.astype(dt), v.astype(dt),
                     precision=prec)
    return out.astype(q.dtype)


def layer(x, p, config, positions, mode):
    eps = config["rms_norm_eps"]
    hd = (config.get("head_dim")
          or config["hidden_size"] // config["num_attention_heads"])
    h = rmsnorm(x, p["norm1"], eps)
    q = mm(h, p["wq"], mode)
    k = mm(h, p["wk"], mode)
    v = mm(h, p["wv"], mode)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    s = x.shape[0]
    q = q.reshape(s, -1, hd).astype(x.dtype)
    k = k.reshape(s, -1, hd).astype(x.dtype)
    v = v.reshape(s, -1, hd).astype(x.dtype)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], eps)
        k = rmsnorm(k, p["k_norm"], eps)
    q = rope(q, positions, config["rope_theta"])
    k = rope(k, positions, config["rope_theta"])
    o = attention(q, k, v, mode).reshape(s, -1)
    x = x + mm(o, p["wo"], mode).astype(x.dtype)
    h = rmsnorm(x, p["norm2"], eps)
    g = jax.nn.silu(mm(h, p["w_gate"], mode).astype(jnp.float32))
    u = mm(h, p["w_up"], mode).astype(jnp.float32)
    return x + mm((g * u).astype(x.dtype), p["w_down"], mode).astype(x.dtype)


def hidden(w, config, tokens, mode: str = "f32", remat: bool = False):
    """Final-normed hidden states (S, D) of one token sequence (S,)."""
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = w["embed"][tokens].astype(dt)
    positions = jnp.arange(tokens.shape[0])

    def body(x, p):
        return layer(x, p, config, positions, mode), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    return rmsnorm(x, w["final_norm"], config["rms_norm_eps"])


def head(w, h, mode: str = "f32"):
    """Logits (rows, vocab) of final-normed hidden rows."""
    table = w["embed"].T if "lm_head" not in w else w["lm_head"]
    return mm(h, table, mode).astype(jnp.float32)


def loss(w, config, rows, mode: str = "f32", head_rows: int = 512):
    """Mean next-token cross-entropy over a batch of rows of S + 1
    tokens.  Rows run one after another and the head in blocks of
    ``head_rows`` positions, each recomputed in the backward pass, so
    that the gradient of a whole batch fits beside its optimizer state."""

    @jax.checkpoint
    def block(h, t):
        logp = jax.nn.log_softmax(head(w, h, mode), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, t[:, None], axis=-1))

    def one(total, row):
        h = hidden(w, config, row[:-1], mode, remat=True)
        s = h.shape[0]
        c = min(head_rows, s)
        hb = h.reshape(s // c, c, -1)
        tb = row[1:].reshape(s // c, c)
        ll = jax.lax.map(lambda a: block(*a), (hb, tb))
        return total + jnp.sum(ll), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), rows)
    return -total / (rows.shape[0] * (rows.shape[1] - 1))
