"""Flash-decoding style split-KV attention for single-token decode.

``serve_step`` decodes ONE token per sequence against a padded KV cache
of ``max_len`` slots, of which only ``kv_len`` are live.  The jnp path
(`softmax_attend` over the full buffer) therefore pays O(max_len) per
step: a decode_32k cell with 100 generated tokens still attends 32k
padded slots.  This kernel makes the step cost track the cache fill:

* the padded cache is **partitioned along KV** into ``block_k`` slices
  (one grid step each) — the flash-decoding split that turns a skinny
  (G, T) attention into P independent (G, block_k) panels;
* partitions at/after ``kv_len`` are skipped under ``pl.when`` and their
  DMA is clamped onto the last live partition by the scalar-prefetched
  index map, so a fresh cache costs ~1 partition, a full one costs P —
  O(kv_len), not O(max_len);
* each live partition emits an unnormalized partial output plus its
  online-softmax statistics (m, l); the cross-partition **max /
  logsumexp combine** runs as cheap jnp on (B, Hkv, P, G) arrays.

Layout mirrors ``flash_attention``: q folds the GQA group into rows,
(B, Hkv, G, D) against (B, Hkv, Tp, D) K/V panels, f32 statistics.
A per-partition execution counter backs the accounting tests and the
``attn_bench`` achieved-vs-skipped report.

``paged_decode_attention`` is the **paged** variant the continuous-
batching engine serves from (serve/engine.py): K/V live in fixed-size
pages of a shared pool and each sequence owns a per-request **block
table** of page indices.  The grid partition IS the page — the scalar-
prefetched block table feeds the index map, so partition ``ip`` of
sequence ``b`` DMAs pool page ``block_tables[b, ip]`` directly from
wherever the allocator put it (no gather/copy of the cache before the
kernel).  ``kv_lens`` is per-sequence, so one batched call serves
sequences at wildly different fill levels, each at O(its own kv_len);
dead partitions clamp onto the sequence's last live page exactly like
the dense kernel clamps onto the last live tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import MASK_VALUE, _pad_axis

DEFAULT_BLOCK_K = 512


def _split_kv_partition(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, cnt_ref, *,
    kvlen, k_lo, kc, window, scale, k_scale=None, v_scale=None,
    qs=1, group=None,
):
    """One KV partition of a split-KV decode step: emit the unnormalized
    partial output plus (m, l) online-softmax statistics, or neutral
    statistics when the partition lies at/after ``kvlen`` (or fully
    outside the sliding window).  Shared by the dense and paged kernels —
    they differ only in where ``kvlen`` and the K/V panel come from.

    ``k_scale``/``v_scale`` (traced scalars) dequantize an int8 page
    right after its DMA: because the scale is per PAGE (== partition),
    it folds into the logits as one scalar multiplier after the QK dot
    and into the partial output after the PV dot — the dequantized f32
    panel never exists outside this partition's registers.

    ``qs`` > 1 is the MULTI-TOKEN (speculative verify) form: the q panel
    carries ``qs`` consecutive positions ``[kvlen - qs, kvlen)``
    position-major (row ``r`` is position ``kvlen - qs + r // group``),
    each causally masked at its own position.  A row whose positions all
    fall before this partition masks fully — its (m = MASK_VALUE, l = kc)
    statistics are then annihilated by the cross-partition combine
    (``alpha ~ exp(MASK_VALUE - m_glob) = 0``), the same mechanism that
    kills dead partitions."""
    group = group if group is not None else q_ref.shape[-2]

    executed = k_lo < kvlen
    if window > 0:
        # live iff inside the OLDEST row's window (kvlen - qs, ...]
        executed &= (k_lo + kc - 1) > (kvlen - qs - window)
    if cnt_ref is not None:
        cnt_ref[...] = jnp.broadcast_to(
            executed.astype(jnp.int32), cnt_ref.shape)

    @pl.when(executed)
    def _partition():
        q = q_ref[...].reshape(q_ref.shape[-2], q_ref.shape[-1])  # (qs*G, D)
        k = k_ref[...].reshape(kc, k_ref.shape[-1])
        if k_scale is not None:
            k = k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (qs*G, kc)
        if k_scale is not None:
            s = s * k_scale

        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row_pos = kvlen - qs + (
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group)
        mask = cols <= row_pos  # == cols < kvlen when qs == 1
        if window > 0:
            mask &= cols > row_pos - window
        s = jnp.where(mask, s, MASK_VALUE)

        m = jnp.max(s, axis=1, keepdims=True)  # (G, 1)
        p = jnp.exp(s - m)
        v = v_ref[...].reshape(kc, v_ref.shape[-1])
        if v_scale is not None:
            pv = jax.lax.dot(
                p, v.astype(jnp.float32), preferred_element_type=jnp.float32,
            ) * v_scale
        else:
            pv = jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        o_ref[...] = pv.reshape(o_ref.shape)
        m_ref[...] = m.reshape(m_ref.shape)
        l_ref[...] = jnp.sum(p, axis=1, keepdims=True).reshape(l_ref.shape)

    @pl.when(jnp.logical_not(executed))
    def _dead():
        # neutral statistics: alpha = exp(-inf - m_glob) = 0 in the combine
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)


def _partition_outputs(b, hkv, np_, rows, dv, with_counts):
    """Out specs/shapes of a split-KV kernel with grid (b, hkv, np_):
    per partition, the (rows, dv) partial output, its (rows, 1) m and l
    statistics and, with counts, a (1, 1) execution flag.  Every block
    spans the full extent of the array's last two dims, the TPU tiling
    rule that a block such as (1, rows) on a (np_, rows) tail breaks."""
    def spec(*tail):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda ib, ih, ip, *_: (ib, ih, ip, 0, 0))

    out_specs = [spec(rows, dv), spec(rows, 1), spec(rows, 1)]
    out_shape = [
        jax.ShapeDtypeStruct((b, hkv, np_, rows, dv), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, np_, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, np_, rows, 1), jnp.float32),
    ]
    if with_counts:
        out_specs.append(spec(1, 1))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, np_, 1, 1), jnp.int32))
    return out_specs, out_shape


def _combine_partitions(o_part, m_part, l_part):
    """Cross-partition max / logsumexp merge: o_part (B, Hkv, P, G, Dv),
    m_part / l_part (B, Hkv, P, G, 1)."""
    m_part, l_part = m_part[..., 0], l_part[..., 0]
    m_glob = jnp.max(m_part, axis=2, keepdims=True)
    # dead partitions carry m = -inf; exp(-inf - finite) = 0 kills them
    alpha = jnp.exp(m_part - jnp.maximum(m_glob, MASK_VALUE))
    den = jnp.sum(alpha * l_part, axis=2)  # (B, Hkv, G)
    num = jnp.sum(alpha[..., None] * o_part, axis=2)  # (B, Hkv, G, Dv)
    return num / jnp.maximum(den, 1e-30)[..., None]


def _decode_kernel(
    sref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *refs,
    kc, window, scale, with_counts,
):
    cnt_ref = refs[0] if with_counts else None
    ip = pl.program_id(2)
    _split_kv_partition(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, cnt_ref,
        kvlen=sref[0], k_lo=ip * kc, kc=kc, window=window, scale=scale)


def decode_attention(
    q, k, v, *,
    kv_len,
    window: int = 0,
    scale: float | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    return_counts: bool = False,
):
    """Split-KV decode attention.

    q: (B, 1, H, D) — the single new token's queries;
    k/v: (B, T, Hkv, D[v]) — the padded cache AFTER the new K/V were
    written, so the query's absolute position is ``kv_len - 1``.
    ``kv_len`` may be a traced scalar.  Returns (B, 1, H, Dv)
    [+ (B, Hkv, P) partition execution map when ``return_counts``].
    """
    b, s, h, d = q.shape
    assert s == 1, f"decode_attention is an S=1 kernel, got S={s}"
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    kc = min(block_k, t)

    q3 = q.reshape(b, hkv, g, d)
    k4 = _pad_axis(k.transpose(0, 2, 1, 3), 2, kc)
    v4 = _pad_axis(v.transpose(0, 2, 1, 3), 2, kc)
    tp = k4.shape[2]
    np_ = tp // kc

    kvlen = jnp.minimum(jnp.asarray(kv_len, jnp.int32), t)
    scalars = kvlen[None] if kvlen.ndim == 0 else kvlen.reshape(1)

    def kv_index(ib, ih, ip, sref):
        # dead partitions re-present the last live tile: no wasted DMA
        live_last = jnp.maximum((sref[0] - 1) // kc, 0)
        return ib, ih, jnp.clip(jnp.minimum(ip, live_last), 0, np_ - 1), 0

    out_specs, out_shape = _partition_outputs(b, hkv, np_, g, dv,
                                              return_counts)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, np_),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ip, s: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, kc, d), kv_index),
            pl.BlockSpec((1, 1, kc, dv), kv_index),
        ],
        out_specs=out_specs,
    )
    res = pl.pallas_call(
        functools.partial(_decode_kernel, kc=kc, window=window, scale=scale,
                          with_counts=return_counts),
        grid_spec=grid_spec,
        name="split_kv_decode_attention",
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(scalars, q3, k4, v4)
    # max / logsumexp combine across partitions (cheap: (B,Hkv,P,G))
    out = _combine_partitions(*res[:3]).reshape(b, 1, h, dv).astype(q.dtype)
    if return_counts:
        return out, res[3].reshape(b, hkv, np_)
    return out


def decode_partition_counts(t: int, kv_len: int, *,
                            block_k: int = DEFAULT_BLOCK_K,
                            window: int = 0):
    """Analytic (executed, total) partition counts for one (batch,
    kv-head) decode step — the split-KV analogue of
    ``flash_tile_counts``."""
    kc = min(block_k, t)
    np_ = -(-t // kc)
    kvlen = min(kv_len, t)
    executed = 0
    for ip in range(np_):
        k_lo = ip * kc
        live = k_lo < kvlen
        if window > 0:
            live = live and (k_lo + kc - 1) > (kvlen - 1 - window)
        executed += int(live)
    return executed, np_


# ---------------------------------------------------------------------------
# paged variant: KV gathered through per-sequence block tables
# ---------------------------------------------------------------------------


def _paged_kernel(
    *refs, pg, window, scale, with_counts, quantized, num_pages, max_pp, qs,
    group,
):
    if quantized:
        btref, lref, ksref, vsref = refs[:4]
        refs = refs[4:]
    else:
        btref, lref = refs[:2]
        refs = refs[2:]
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs[:6]
    cnt_ref = refs[6] if with_counts else None
    ib, ih, ip = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kvlen = lref[ib]
    k_scale = v_scale = None
    if quantized:
        # the page this partition's DMA presented (same clamp as the
        # index map) picks its scale off the scalar-prefetch channel
        first, last = _live_page_range(kvlen, pg=pg, window=window, qs=qs)
        page = btref[ib * max_pp + jnp.clip(ip, first, last)]
        page = jnp.clip(page, 0, num_pages - 1)
        k_scale = ksref[ih * num_pages + page]
        v_scale = vsref[ih * num_pages + page]
    _split_kv_partition(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, cnt_ref,
        kvlen=kvlen, k_lo=ip * pg, kc=pg, window=window, scale=scale,
        k_scale=k_scale, v_scale=v_scale, qs=qs, group=group)


def _live_page_range(kvlen, *, pg, window, qs=1):
    """[first, last] live partition indices for a sequence of ``kvlen``
    tokens (partition == page).  Mirrors the ``executed`` predicate in
    ``_split_kv_partition`` (``qs`` query rows end at ``kvlen - 1``);
    empty caches collapse to [0, 0]."""
    last = jnp.maximum((kvlen - 1) // pg, 0)
    if window > 0:
        # page ip is live iff ip*pg + pg - 1 > (kvlen - qs) - window,
        # the OLDEST query row's window edge
        c = (kvlen - qs) - window + 2 - pg
        first = jnp.maximum(jnp.int32(0), -((-c) // pg))
    else:
        first = jnp.int32(0)
    return first, jnp.maximum(last, first)


def paged_decode_attention(
    q, k_pages, v_pages, block_tables, kv_lens, *,
    window: int = 0,
    scale: float | None = None,
    dv: int | None = None,
    k_scales=None,
    v_scales=None,
    interpret: bool = False,
    return_counts: bool = False,
):
    """Split-KV decode attention over a paged KV pool.

    q: (B, S, H, D) — the new tokens' queries (S = 1 decode, S > 1
    speculative verify), K/V for them already written into the pool (so
    sequence b's last query sits at absolute position
    ``kv_lens[b] - 1``);
    k_pages / v_pages: (Hkv, num_pages, page_size, W) shared pools;
    block_tables: (B, pages_per_seq) int32 pool-page indices — entries
    past a sequence's live pages (and whole rows of inactive slots) may
    be -1;
    kv_lens: (B,) int32 live token counts, 0 for inactive slots (their
    output is exactly zero).

    ``dv`` reads only the leading ``dv`` columns of ``v_pages`` — this
    lets MLA serve keys ``[c_kv | k_rope]`` and values ``c_kv`` out of
    ONE pool without materializing a sliced copy.  One partition == one
    page; partitions outside a sequence's [window, kv_len) range are
    skipped under ``pl.when`` with their DMA clamped onto the last live
    page.

    **int8 pools**: pass ``k_scales``/``v_scales`` (Hkv, num_pages) f32
    per-page-per-head scales (kv_cache.py writes them) — they ride the
    scalar-prefetch channel next to the block table, and each partition
    dequantizes its page right after the DMA.  MLA's shared pool passes
    the SAME array for both.  Returns (B, S, H, dv)
    [+ (B, Hkv, P) execution map].

    **S > 1** is the speculative-verify form: q carries S consecutive
    positions per sequence ending at ``kv_lens[b] - 1`` (their K/V
    already written), folded into the kernel's row axis position-major
    — row ``r`` of a panel is position ``kv_lens[b] - S + r // group``,
    masked causally at its own position.  One batched call verifies
    every slot's whole draft against the same paged pool the S=1
    decode serves from.
    """
    b, s, h, d = q.shape
    hkv, num_pages, pg, wk = k_pages.shape
    assert wk >= d, (wk, d)
    g = h // hkv
    dv = v_pages.shape[-1] if dv is None else dv
    scale = scale if scale is not None else d ** -0.5
    max_pp = block_tables.shape[1]
    quantized = k_pages.dtype == jnp.int8
    assert quantized == (k_scales is not None) == (v_scales is not None), \
        "int8 pools need k_scales AND v_scales; float pools must not pass them"

    # position-major row fold: row r = position s_idx * g + group g_idx
    q3 = (q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, s * g, d))
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    lens = jnp.asarray(kv_lens, jnp.int32)
    scalars = [bt_flat, lens]
    if quantized:
        scalars += [k_scales.reshape(-1).astype(jnp.float32),
                    v_scales.reshape(-1).astype(jnp.float32)]

    def kv_index(ib, ih, ip, btref, lref, *_):
        # dead partitions re-present the sequence's last live page: the
        # block table is the DMA descriptor, -1 tails never dereference
        first, last = _live_page_range(lref[ib], pg=pg, window=window, qs=s)
        page = btref[ib * max_pp + jnp.clip(ip, first, last)]
        return ih, jnp.clip(page, 0, num_pages - 1), 0, 0

    rows = s * g
    out_specs, out_shape = _partition_outputs(b, hkv, max_pp, rows, dv,
                                              return_counts)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hkv, max_pp),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda ib, ih, ip, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, pg, d), kv_index),
            pl.BlockSpec((1, 1, pg, dv), kv_index),
        ],
        out_specs=out_specs,
    )
    res = pl.pallas_call(
        functools.partial(_paged_kernel, pg=pg, window=window, scale=scale,
                          with_counts=return_counts, quantized=quantized,
                          num_pages=num_pages, max_pp=max_pp, qs=s, group=g),
        grid_spec=grid_spec,
        name="paged_decode_attention",
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*scalars, q3, k_pages, v_pages)
    out = (_combine_partitions(*res[:3]).reshape(b, hkv, s, g, dv)
           .transpose(0, 2, 1, 3, 4).reshape(b, s, h, dv).astype(q.dtype))
    if return_counts:
        return out, res[3].reshape(b, hkv, max_pp)
    return out


def paged_partition_counts(pages_per_seq: int, kv_lens, *,
                           page_size: int, window: int = 0):
    """Per-sequence analytic (executed, total) page counts for one
    batched paged decode step — ``decode_partition_counts`` evaluated
    at each sequence's own fill level.  Returns (list[int], total)."""
    t = pages_per_seq * page_size
    executed = [
        decode_partition_counts(t, int(n), block_k=page_size,
                                window=window)[0]
        for n in kv_lens
    ]
    return executed, pages_per_seq
