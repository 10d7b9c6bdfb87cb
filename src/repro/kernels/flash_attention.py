"""Causal flash attention as a Pallas TPU grid kernel.

The jnp reference (``repro.models.layers.flash_attend_ref``) is a
two-level scan that computes every KV tile — including tiles that the
causal / sliding-window mask fully discards — because lax.scan needs a
rectangular iteration space.  For causal prefill that is ~2x the useful
FLOPs.  This kernel keeps the rectangular Pallas grid but makes the
untaken tiles free twice over:

* the KV **index map** clamps skipped grid steps onto the nearest live
  tile, so no new HBM->VMEM DMA is issued for a tile whose mask is all
  False (scalar-prefetched ``q_offset`` / ``kv_len`` feed the clamp), and
* the kernel body runs under ``pl.when(executed)``, so the MXU never sees
  the dead tile.

Structure follows the canonical TPU flash kernel: VMEM scratch carries
the online-softmax state (running max ``m``, normalizer ``l``, f32
output accumulator) across the innermost KV grid dimension; state is
initialized on the first *live* KV tile of each Q tile and the
normalized output is stored on the last.

GQA is handled by folding the query-head group into the Q tile: q is
laid out (B, Hkv, G, S, D) and each grid cell attends a (G*block_q, D)
query panel against one (block_k, D) panel of its KV head — the MXU
reduction over the group comes for free, no K/V replication.

``q_offset`` (absolute position of query row 0 — chunked prefill resume,
decode) and ``kv_len`` (live prefix of a padded cache) are dynamic
scalars; everything else is static.  A per-tile execution counter is
written unconditionally so tests and benchmarks can assert the skip
actually happened (``flash_tile_counts`` gives the analytic expectation).

The kernel is wrapped in ``jax.custom_vjp``: backward recomputes through
the jnp reference, keeping the Pallas path differentiable for the train
graphs that share ``flash_attend``.

Interpret mode (``interpret=True``) runs the same grid on CPU and is the
validation path (tests/test_attn_kernels.py) per DESIGN.md §2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf on masked logits: exp(mask - m) underflows to
# exactly 0 without the exp(-inf - (-inf)) = nan hazard (guide §Numerics).
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _tile_bounds(q_lo, kvlen, *, qc, kc, window, bidirectional, nk):
    """First/last live KV tile index for the Q tile starting at absolute
    position ``q_lo``.  Live set is the contiguous [first, last]; empty
    when last < first.  All inputs may be traced scalars."""
    if bidirectional:
        first = jnp.int32(0)
        last = (kvlen - 1) // kc
    else:
        q_hi = q_lo + qc - 1
        last = jnp.minimum(q_hi, kvlen - 1) // kc
        if window > 0:
            # tile [k_lo, k_lo+kc-1] is visible from below iff its last
            # key is inside the widest window of the tile's query rows:
            # k_lo + kc - 1 > q_lo - window
            c = q_lo - window + 2 - kc
            first = jnp.maximum(jnp.int32(0), -((-c) // kc))
        else:
            first = jnp.int32(0)
    return first.astype(jnp.int32), last.astype(jnp.int32)


def _kv_block_index(ib, ih, iq, ik, sref, *, qc, kc, window, bidirectional, nk):
    """Index map for K/V: clamp skipped grid steps onto the live range so
    Pallas re-presents an already-resident tile instead of DMA-ing a dead
    one."""
    q_lo = sref[0] + iq * qc
    first, last = _tile_bounds(q_lo, sref[1], qc=qc, kc=kc, window=window,
                               bidirectional=bidirectional, nk=nk)
    clamped = jnp.clip(ik, first, jnp.maximum(last, first))
    return ib, ih, jnp.clip(clamped, 0, nk - 1), 0


def _flash_kernel(
    sref, q_ref, k_ref, v_ref, o_ref, *refs,
    qc, kc, g, nk, window, bidirectional, scale, with_counts,
):
    cnt_ref = refs[0] if with_counts else None
    m_scr, l_scr, acc_scr = refs[-3:]
    iq, ik = pl.program_id(2), pl.program_id(3)
    q_off, kvlen = sref[0], sref[1]
    q_lo = q_off + iq * qc
    k_lo = ik * kc

    first, last = _tile_bounds(q_lo, kvlen, qc=qc, kc=kc, window=window,
                               bidirectional=bidirectional, nk=nk)
    executed = (ik >= first) & (ik <= last)
    if with_counts:
        cnt_ref[...] = jnp.broadcast_to(
            executed.astype(jnp.int32), cnt_ref.shape)

    @pl.when(executed & (ik == first))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(executed)
    def _tile():
        q = q_ref[...].reshape(g * qc, q_ref.shape[-1])
        k = k_ref[...].reshape(kc, k_ref.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (g*qc, kc)

        # element-level mask; rows are (group, q) flattened g-major so a
        # row's absolute position depends only on row % qc
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % qc
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < kvlen
        if not bidirectional:
            mask &= cols <= rows
            if window > 0:
                mask &= cols > rows - window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[...].reshape(kc, v_ref.shape[-1]),
            preferred_element_type=jnp.float32,
        )

    @pl.when(executed & (ik == last))
    def _store():
        out = acc_scr[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _flash_call(q, k, v, scalars, *, window, bidirectional, scale,
                block_q, block_k, interpret, with_counts):
    """q: (B, Hkv, G, Sp, D); k/v: (B, Hkv, Tp, D[v]); scalars: (2,) i32
    [q_offset, kv_len].  Returns out (B,Hkv,G,Sp,Dv) [+ tile counts]."""
    b, hkv, g, sp, d = q.shape
    tp = k.shape[2]
    dv = v.shape[-1]
    qc, kc = min(block_q, sp), min(block_k, tp)
    assert sp % qc == 0 and tp % kc == 0, (sp, tp, qc, kc)
    nq, nk = sp // qc, tp // kc

    kv_index = functools.partial(
        _kv_block_index, qc=qc, kc=kc, window=window,
        bidirectional=bidirectional, nk=nk)
    out_specs = [
        pl.BlockSpec((1, 1, g, qc, dv), lambda ib, ih, iq, ik, s: (ib, ih, 0, iq, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((b, hkv, g, sp, dv), q.dtype)]
    if with_counts:
        out_specs.append(
            pl.BlockSpec((1, 1, 1, 1), lambda ib, ih, iq, ik, s: (ib, ih, iq, ik)))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, nq, nk), jnp.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, qc, d), lambda ib, ih, iq, ik, s: (ib, ih, 0, iq, 0)),
            pl.BlockSpec((1, 1, kc, d), kv_index),
            pl.BlockSpec((1, 1, kc, dv), kv_index),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g * qc, 128), jnp.float32),  # running max m
            pltpu.VMEM((g * qc, 128), jnp.float32),  # running normalizer l
            pltpu.VMEM((g * qc, dv), jnp.float32),   # output accumulator
        ],
    )
    kernel = functools.partial(
        _flash_kernel, qc=qc, kc=kc, g=g, nk=nk, window=window,
        bidirectional=bidirectional, scale=scale, with_counts=with_counts)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="flash_attention",
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(scalars, q, k, v)
    return out if with_counts else (out[0], None)


def _pad_axis(x, axis, mult):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _flash_impl(q, k, v, q_offset, kv_len, statics):
    (window, bidirectional, scale, block_q, block_k, interpret,
     return_counts) = statics
    b, s, h, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    dv = v.shape[-1]
    scale = scale if scale is not None else d ** -0.5

    # every key at/after kv_len is masked; padding extends that region
    kvlen = jnp.minimum(jnp.asarray(kv_len, jnp.int32), t)
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32), kvlen])

    qc = min(block_q, s)
    kc = min(block_k, t)
    q5 = _pad_axis(q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4), 3, qc)
    k4 = _pad_axis(k.transpose(0, 2, 1, 3), 2, kc)
    v4 = _pad_axis(v.transpose(0, 2, 1, 3), 2, kc)

    out5, counts = _flash_call(
        q5, k4, v4, scalars, window=window, bidirectional=bidirectional,
        scale=scale, block_q=qc, block_k=kc, interpret=interpret,
        with_counts=return_counts)
    out = out5.transpose(0, 3, 1, 2, 4).reshape(b, -1, h, dv)[:, :s]
    if return_counts:
        return out, counts
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_diff(q, k, v, q_offset, kv_len, statics):
    """Differentiable wrapper; q_offset/kv_len ride as i32 arrays whose
    cotangents are zero."""
    return _flash_impl(q, k, v, q_offset, kv_len, statics)


def _flash_diff_fwd(q, k, v, q_offset, kv_len, statics):
    return _flash_impl(q, k, v, q_offset, kv_len, statics), (q, k, v, q_offset, kv_len)


def _flash_diff_bwd(statics, res, grad):
    from repro.models.layers import flash_attend_ref

    q, k, v, q_offset, kv_len = res
    window, bidirectional, scale, *_ = statics

    def ref(q, k, v):
        return flash_attend_ref(
            q, k, v, q_offset=q_offset.astype(jnp.int32), window=window,
            bidirectional=bidirectional, scale=scale,
            kv_len=kv_len.astype(jnp.int32))

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(grad)
    # the dynamic scalars ride as f32 arrays precisely so their zero
    # cotangents are representable
    return dq, dk, dv, jnp.zeros_like(q_offset), jnp.zeros_like(kv_len)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(
    q, k, v, *,
    q_offset=0,
    window: int = 0,
    bidirectional: bool = False,
    scale: float | None = None,
    kv_len=None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    return_counts: bool = False,
):
    """Pallas flash attention.  Same contract as
    ``repro.models.layers.flash_attend``:

    q: (B, S, H, D); k/v: (B, T, Hkv, D[v]) with H % Hkv == 0 (GQA).
    ``q_offset``: absolute position of query row 0 (chunked prefill /
    decode resume); ``kv_len``: live prefix of a padded KV buffer.  Both
    may be traced scalars.  Shapes need not be block multiples (padded
    keys are masked through ``kv_len``; padded query rows are dropped).

    ``return_counts=True`` additionally returns the (B, Hkv, nq, nk)
    per-tile execution map — 1 where the MXU ran, 0 where the causal /
    window / kv_len block-skip fired (not differentiable).
    """
    statics = (window, bidirectional, scale, block_q, block_k, interpret,
               return_counts)
    # dynamic scalars travel as f32 arrays so custom_vjp can hand back
    # well-typed zero cotangents (cast to i32 at the kernel boundary)
    q_offset = jnp.asarray(q_offset, jnp.float32)
    kv_len = jnp.asarray(k.shape[1] if kv_len is None else kv_len, jnp.float32)
    if return_counts:
        return _flash_impl(q, k, v, q_offset, kv_len, statics)
    return _flash_diff(q, k, v, q_offset, kv_len, statics)


def flash_tile_counts(
    s: int, t: int, *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_offset: int = 0,
    window: int = 0,
    bidirectional: bool = False,
    kv_len: int | None = None,
):
    """Analytic (executed, total) KV-tile counts for one (batch, kv-head)
    slice of the grid — the oracle for the block-skip accounting test and
    the benchmark's achieved-vs-skipped report."""
    qc, kc = min(block_q, s), min(block_k, t)
    sp, tp = -(-s // qc) * qc, -(-t // kc) * kc
    nq, nk = sp // qc, tp // kc
    kvlen = min(t if kv_len is None else int(kv_len), t)
    executed = 0
    for iq in range(nq):
        first, last = _tile_bounds(
            jnp.int32(q_offset + iq * qc), jnp.int32(kvlen), qc=qc, kc=kc,
            window=window, bidirectional=bidirectional, nk=nk)
        first, last = int(first), min(int(last), nk - 1)
        executed += max(0, last - first + 1)
    return executed, nq * nk
