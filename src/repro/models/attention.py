"""Attention variants: GQA (+bias/qk_norm/SWA), MLA, cross-attention.

All variants share one calling convention:

    params = attn_init(key, cfg, dtype)
    y, cache = attn_apply(params, cfg, x, positions, cache=None|KVCache)

* ``cache=None``        — training / encoder forward (full causal or
                          bidirectional attention, no state).
* ``cache`` w/ len==0   — prefill: keys/values written into the cache.
* ``cache`` w/ len==T   — decode: x is (B, 1, D), one new token.

Caches are plain dicts so they shard/checkpoint like any pytree:
GQA:  {"k": (B, T, Hkv, D), "v": (B, T, Hkv, Dv), "len": i32}
SWA:  same but T == window and writes wrap (rolling buffer, O(window))
MLA:  {"ckv": (B, T, R), "k_rope": (B, T, Dr), "len": i32} — the
      compressed cache that makes deepseek-v2 long-context serving cheap.

Paged decode (serve/kv_cache.py layout; S=1 decode, S>1 speculative
verify): the cache dict instead carries a shared page pool plus
per-sequence routing —
GQA:  {"k_pages"/"v_pages": (Hkv, P, page, D),
       "block_tables": (B, pages), "len": (B,) i32}
MLA:  {"kv_pages": (1, P, page, r+dr), ...} — and ``len`` is the
per-sequence PRE-write fill (the engine owns its updates), so one
batched step serves sequences at different fill levels.  Inactive
slots (block_tables row -1) drop their write and emit zeros.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import DP, MDL, hint
from repro.models.layers import (
    apply_rope,
    causal_mask,
    decode_attend,
    dense_apply,
    dense_init,
    flash_attend,
    paged_decode_attend,
    rmsnorm_apply,
    rmsnorm_init,
    softmax_attend,
)

# sequences at or above this length attend via the chunked online-softmax
# path (never materializes S x T logits); shorter ones go direct
FLASH_MIN_SEQ = 512


# ---------------------------------------------------------------------------
# paged-cache plumbing (shared by GQA and MLA decode)
# ---------------------------------------------------------------------------


def _w(p):
    """Weight of a dense dict for einsum-shaped uses (MLA weight
    absorption): quantized params materialize the f32 dequant on the
    fly — the stored leaf stays int8; f32 params pass through as-is."""
    if "qw" in p:
        from repro.optim.quant import dequant_int8

        return dequant_int8(p["qw"], p["qscale"])
    return p["w"]


def _paged_token_coords(cache, pool_key, s: int = 1):
    """Where this step's ``s`` tokens land in the pool, per slot.

    Returns (page, slot, new_len): page (B, S) is the pool index at
    each sequence's write positions ``len .. len+s-1`` — inactive slots
    (block table row -1) get ``num_pages``, i.e. out of bounds, so a
    ``mode="drop"`` scatter discards them; new_len is the post-write
    per-sequence fill (0 stays 0 for inactive slots, which zeroes
    their attention output too).
    """
    bt, lens = cache["block_tables"], cache["len"]
    num_pages, pg = cache[pool_key].shape[1], cache[pool_key].shape[2]
    pos = lens[:, None] + jnp.arange(s)[None, :]  # (B, S)
    idx = jnp.clip(pos // pg, 0, bt.shape[1] - 1)
    page = jnp.take_along_axis(bt, idx, axis=1)
    # positions past the block table (a speculative tail poking beyond a
    # request's last page) must DROP, never clip onto a live page
    page = jnp.where((page < 0) | (pos // pg > bt.shape[1] - 1),
                     num_pages, page)
    active = bt[:, 0] >= 0
    new_len = jnp.where(active, lens + s, 0)
    return page, pos % pg, new_len


# ---------------------------------------------------------------------------
# GQA (covers MHA, GQA, SWA, qkv-bias, qk-norm)
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, hkv * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, hkv * hd, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def gqa_cache_init(cfg, batch: int, max_len: int, dtype):
    t = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, t, cfg.kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, t, cfg.kv_heads, cfg.head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = dense_apply(p["wk"], x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
    v = dense_apply(p["wv"], x).reshape(b, s, cfg.kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_apply(p, cfg, x, positions, cache=None, *, bidirectional=False):
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)

    if cache is None:
        if s >= FLASH_MIN_SEQ:
            out = flash_attend(q, k, v, window=cfg.sliding_window,
                               bidirectional=bidirectional)
        else:
            mask = (
                jnp.ones((s, s), bool)
                if bidirectional
                else causal_mask(s, s, window=cfg.sliding_window)
            )
            out = softmax_attend(q, k, v, mask)
        new_cache = None
    elif "k_pages" in cache:
        # paged decode (S=1) / speculative verify (S>1): write the S
        # tokens into their pool pages, attend through the block table
        # (O(own kv_len) per sequence)
        page, slot, new_len = _paged_token_coords(cache, "k_pages", s)
        if cache["k_pages"].dtype == jnp.int8:
            from repro.serve.kv_cache import quant_page_update

            kp, ksc = cache["k_pages"], cache["k_scales"]
            vp, vsc = cache["v_pages"], cache["v_scales"]
            # sequential inserts: token j's requant sees tokens < j of
            # the same page live, rows past its own slot zeroed
            for j in range(s):
                kp, ksc = quant_page_update(
                    kp, ksc, page[:, j], slot[:, j],
                    k[:, j].transpose(1, 0, 2))
                vp, vsc = quant_page_update(
                    vp, vsc, page[:, j], slot[:, j],
                    v[:, j].transpose(1, 0, 2))
            out = paged_decode_attend(
                q, kp, vp, cache["block_tables"], new_len,
                window=cfg.sliding_window, k_scales=ksc, v_scales=vsc)
            new_cache = {"k_pages": kp, "v_pages": vp,
                         "k_scales": ksc, "v_scales": vsc}
        else:
            from repro.serve.kv_cache import pool_write_rows

            kp = pool_write_rows(cache["k_pages"], k.transpose(2, 0, 1, 3),
                                 page, slot)
            vp = pool_write_rows(cache["v_pages"], v.transpose(2, 0, 1, 3),
                                 page, slot)
            out = paged_decode_attend(q, kp, vp, cache["block_tables"],
                                      new_len, window=cfg.sliding_window)
            new_cache = {"k_pages": kp, "v_pages": vp}
    else:
        t = cache["k"].shape[1]
        cur = cache["len"]
        rolling = bool(cfg.sliding_window) and t <= cfg.sliding_window
        if rolling:
            # SWA rolling buffer, ordered-snapshot invariant: after every
            # call, slot j holds the key for absolute position
            # len - t + j (negative => slot not yet written, masked out).
            # Works for chunked prefill AND decode: attend over
            # [buffer | new keys], then keep the trailing `t` entries.
            full_k = jnp.concatenate([cache["k"], k], axis=1)  # (b, t+s, ...)
            full_v = jnp.concatenate([cache["v"], v], axis=1)
            kv_pos = cur - t + jnp.arange(t + s)
            q_pos = cur + jnp.arange(s)
            mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos >= 0)[None, :]
            mask &= kv_pos[None, :] > (q_pos[:, None] - cfg.sliding_window)
            out = softmax_attend(q, full_k, full_v, mask)
            ck, cv = full_k[:, s:], full_v[:, s:]
            new_len = cur + s
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, cur, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, cur, 0, 0))
            new_len = cur + s
            if s == 1:
                # decode: split-KV kernel, O(kv_len) not O(max_len)
                out = decode_attend(q, ck, cv, kv_len=new_len,
                                    window=cfg.sliding_window)
            elif s >= FLASH_MIN_SEQ:
                out = flash_attend(q, ck, cv, q_offset=cur,
                                   window=cfg.sliding_window, kv_len=new_len)
            else:
                kv_pos = jnp.arange(t)
                q_pos = jnp.arange(s) + cur
                mask = kv_pos[None, :] <= q_pos[:, None]
                mask &= (kv_pos < new_len)[None, :]
                if cfg.sliding_window:
                    mask &= kv_pos[None, :] > (q_pos[:, None] - cfg.sliding_window)
                out = softmax_attend(q, ck, cv, mask)
        new_cache = {"k": ck, "v": cv, "len": new_len}

    y = dense_apply(p["wo"], out.reshape(b, s, -1))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v2)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype):
    d, h = cfg.d_model, cfg.num_heads
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dv = cfg.mla_head_dim, cfg.mla_v_head_dim
    ks = jax.random.split(key, 6)
    p = {
        # queries (nope + rope parts); q-lora omitted when rank == 0
        "wq": dense_init(ks[0], d, h * (dn + dr), dtype),
        # joint KV down-projection -> [c_kv (r) | k_rope (dr)]
        "wdkv": dense_init(ks[1], d, r + dr, dtype),
        "ckv_norm": rmsnorm_init(r, dtype),
        # up-projections from the latent
        "wuk": dense_init(ks[2], r, h * dn, dtype),
        "wuv": dense_init(ks[3], r, h * dv, dtype),
        "wo": dense_init(ks[4], h * dv, d, dtype),
    }
    if cfg.q_lora_rank:
        p["wdq"] = dense_init(ks[5], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq"] = dense_init(ks[0], cfg.q_lora_rank, h * (dn + dr), dtype)
    return p


def mla_cache_init(cfg, batch: int, max_len: int, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _mla_qkv_latent(p, cfg, x, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.num_heads, cfg.mla_head_dim, cfg.rope_head_dim
    xq = x
    if cfg.q_lora_rank:
        xq = rmsnorm_apply(p["q_norm"], dense_apply(p["wdq"], x), cfg.norm_eps)
    q = dense_apply(p["wq"], xq).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = dense_apply(p["wdkv"], x)
    ckv = rmsnorm_apply(p["ckv_norm"], dkv[..., : cfg.kv_lora_rank], cfg.norm_eps)
    k_rope = dkv[..., cfg.kv_lora_rank :][:, :, None, :]  # 1 shared head
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def _mla_attend(p, cfg, q_nope, q_rope, ckv, k_rope, mask=None, *,
                q_offset=0, kv_len=None):
    """MLA attention: latent is up-projected per head; the rope part is a
    single shared head concatenated onto the nope part so the chunked
    flash path applies unchanged for long sequences."""
    b, s, h, dn = q_nope.shape
    t = ckv.shape[1]
    dr = cfg.rope_head_dim
    dv = cfg.mla_v_head_dim
    k_nope = dense_apply(p["wuk"], ckv).reshape(b, t, h, dn)
    v = dense_apply(p["wuv"], ckv).reshape(b, t, h, dv)
    scale = (dn + dr) ** -0.5

    if s >= FLASH_MIN_SEQ:
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # the shared rope head broadcasts across h: without a hint the
        # concat (sharded h ++ replicated h) de-shards the whole key
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, dr))], axis=-1
        )
        q = hint(q, DP, None, MDL, None)
        k = hint(k, DP, None, MDL, None)
        out = flash_attend(q, k, v, q_offset=q_offset, kv_len=kv_len,
                           scale=scale)
        return out.reshape(b, s, h * dv)

    logits = jnp.einsum("bshd,bthd->bhst", q_nope.astype(jnp.float32),
                        k_nope.astype(jnp.float32))
    logits += jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32))
    logits = logits * scale
    logits = jnp.where(mask[None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h * dv).astype(q_nope.dtype)


def _mla_absorbed_q(p, cfg, q_nope, q_rope):
    """Fold ``Wuk`` into the query: latent-space queries (B,1,H,r+dr)."""
    h, dn = q_nope.shape[2], q_nope.shape[3]
    r = cfg.kv_lora_rank
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope,
                       _w(p["wuk"]).reshape(r, h, dn))
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    return hint(q, DP, None, MDL, None)


def _mla_up_project(p, cfg, out_lat):
    """Up-project the single attended latent through ``Wuv``."""
    b, s, h, r = out_lat.shape
    dv = cfg.mla_v_head_dim
    out = jnp.einsum("bshr,rhd->bshd", out_lat,
                     _w(p["wuv"]).reshape(r, h, dv))
    return out.reshape(b, s, h * dv)


def _mla_attend_absorbed(p, cfg, q_nope, q_rope, ckv, k_rope, *, kv_len):
    """Decode (S=1) MLA via weight absorption: because
    ``k_nope[t,h] = Wuk[:,h]^T c_kv[t]``, the nope logits equal
    ``(Wuk q_nope) . c_kv`` — so the step attends directly in the
    compressed latent space (keys ``[c_kv | k_rope]``, values ``c_kv``,
    one shared KV head) and only the single attended latent goes through
    ``Wuv``.  The padded cache is never up-projected: per-step cost is
    the split-KV kernel's O(kv_len) plus O(h·r·(dn+dv)) for one token."""
    dn, dr = cfg.mla_head_dim, cfg.rope_head_dim
    q = _mla_absorbed_q(p, cfg, q_nope, q_rope)
    k = jnp.concatenate([ckv, k_rope], axis=-1)[:, :, None, :]  # 1 kv head
    out_lat = decode_attend(q, k, ckv[:, :, None, :], kv_len=kv_len,
                            scale=(dn + dr) ** -0.5)  # (B, 1, H, r)
    return _mla_up_project(p, cfg, out_lat)


def _mla_attend_absorbed_paged(p, cfg, q_nope, q_rope, pool, block_tables,
                               kv_lens, scales=None):
    """Paged twin of ``_mla_attend_absorbed``: pool rows are
    ``[c_kv | k_rope]``, so the pool serves as BOTH key and value pages
    — ``dv=r`` reads the value c_kv as each row's leading columns (an
    int8 pool's per-page ``scales`` serve both sides the same way)."""
    dn, dr = cfg.mla_head_dim, cfg.rope_head_dim
    q = _mla_absorbed_q(p, cfg, q_nope, q_rope)
    out_lat = paged_decode_attend(q, pool, pool, block_tables, kv_lens,
                                  scale=(dn + dr) ** -0.5,
                                  dv=cfg.kv_lora_rank,
                                  k_scales=scales, v_scales=scales)
    return _mla_up_project(p, cfg, out_lat)


def mla_apply(p, cfg, x, positions, cache=None):
    b, s, _ = x.shape
    q_nope, q_rope, ckv, k_rope, = _mla_qkv_latent(p, cfg, x, positions)
    if cache is None:
        mask = causal_mask(s, s) if s < FLASH_MIN_SEQ else None
        out = _mla_attend(p, cfg, q_nope, q_rope, ckv, k_rope, mask)
        new_cache = None
    elif "kv_pages" in cache:
        # paged decode (S=1) / speculative verify (S>1): one
        # [c_kv | k_rope] row per token in the pool
        page, slot, new_len = _paged_token_coords(cache, "kv_pages", s)
        row = jnp.concatenate([ckv, k_rope], axis=-1)  # (B, S, r+dr)
        if cache["kv_pages"].dtype == jnp.int8:
            from repro.serve.kv_cache import quant_page_update

            pool, ksc = cache["kv_pages"], cache["kv_scales"]
            for j in range(s):
                pool, ksc = quant_page_update(
                    pool, ksc, page[:, j], slot[:, j], row[None, :, j])
            out = _mla_attend_absorbed_paged(p, cfg, q_nope, q_rope, pool,
                                             cache["block_tables"], new_len,
                                             scales=ksc)
            new_cache = {"kv_pages": pool, "kv_scales": ksc}
        else:
            pool = cache["kv_pages"].at[0, page, slot].set(row, mode="drop")
            out = _mla_attend_absorbed_paged(p, cfg, q_nope, q_rope, pool,
                                             cache["block_tables"], new_len)
            new_cache = {"kv_pages": pool}
    else:
        cur = cache["len"]
        t = cache["ckv"].shape[1]
        cc = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, cur, 0))
        cr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope, (0, cur, 0))
        new_len = cur + s
        if s == 1:
            # decode: weight-absorbed split-KV over the compressed cache
            out = _mla_attend_absorbed(p, cfg, q_nope, q_rope, cc, cr,
                                       kv_len=new_len)
        elif s >= FLASH_MIN_SEQ:
            out = _mla_attend(p, cfg, q_nope, q_rope, cc, cr,
                              q_offset=cur, kv_len=new_len)
        else:
            kv_pos = jnp.arange(t)
            q_pos = jnp.arange(s) + cur
            mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos < new_len)[None, :]
            out = _mla_attend(p, cfg, q_nope, q_rope, cc, cr, mask)
        new_cache = {"ckv": cc, "k_rope": cr, "len": new_len}
    return dense_apply(p["wo"], out), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec decoder blocks)
# ---------------------------------------------------------------------------


def cross_attn_init(key, cfg, dtype):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, h * hd, dtype),
        "wv": dense_init(ks[2], d, h * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }


def cross_attn_kv(p, cfg, enc_out):
    """Precompute encoder K/V once per request (the enc-dec 'cache')."""
    b, t, _ = enc_out.shape
    k = dense_apply(p["wk"], enc_out).reshape(b, t, cfg.num_heads, cfg.head_dim)
    v = dense_apply(p["wv"], enc_out).reshape(b, t, cfg.num_heads, cfg.head_dim)
    return {"k": k, "v": v}


def cross_attn_apply(p, cfg, x, kv):
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(b, s, cfg.num_heads, cfg.head_dim)
    t = kv["k"].shape[1]
    # bidirectional: no (S, T) mask to build in either branch
    if s >= FLASH_MIN_SEQ or t >= FLASH_MIN_SEQ:
        out = flash_attend(q, kv["k"], kv["v"], bidirectional=True)
    else:
        out = softmax_attend(q, kv["k"], kv["v"])
    return dense_apply(p["wo"], out.reshape(b, s, -1))
