"""Pipeline schedules over the mesh 'model' axis via shard_map.

The paper's pipeline strategy cuts the NN graph into contiguous
segments, one node per segment, and streams inputs through the pipe —
and its headline knob is that the cuts need NOT be even: the cluster
"manually allocates greater resources to the most computationally
intensive layers".  This module executes exactly that:

**Uneven contiguous cuts.**  ``boundaries`` (from
:func:`repro.core.partition.partition_layers`, surfaced through
``Placement.layer_boundaries``) assign stage *k* the layer slice
``[boundaries[k], boundaries[k+1])``.  The ``shard_map`` body must stay
homogeneous across stages, so every stage's slice is padded to the
deepest stage's layer count (:func:`pad_pipeline_params` — padding rows
repeat the stage's last real layer) and masked out with per-stage depth
counters: a padded layer is an identity no-op whose params receive zero
gradient.  Stored params keep the padded ``(stages * max_depth, ...)``
layout sharded ``P('model')`` on the layer axis, so they feed the
pipeline's in_specs with zero resharding.

**Schedules.**  The forward pipe is fill-and-drain (``m + S - 1``
rounds).  The pipelined train loop (:func:`make_pipeline_loss_and_grad`)
runs ONE fused round body for both schedules; they differ only in the
``lag`` between the forward stream and the backward stream:

  gpipe  lag = m + S - 1   backward fills only after the forward fully
                           drains — 2(m + S - 1) rounds total
  1f1b   lag = S - 1       the backward of microbatch i starts the
                           round its forward finishes at the last
                           stage — m + 2(S - 1) rounds total

Because the two schedules share the round body bit-for-bit (the lag is
a python int), their losses and gradients are bitwise identical; 1F1B
just overlaps the forward drain with the backward fill.
:func:`pipeline_bubble_counts` is the analytic oracle (mirroring
``flash_tile_counts`` in the kernel suite): per-(stages, microbatches)
total rounds and busy/idle stage-rounds, asserted against both
schedules in tests/test_dist.py.

**Hybrid stacks** (``attn_every``, zamba2-style) pipeline at the *group*
boundary: a cut unit is ``attn_every`` Mamba layers plus the shared
attention block, whose params are replicated to every stage.

Embedding and the LM head run *outside* the shard_map for the forward
pipe; the train pipe folds final-norm + head + CE into the last stage
(1F1B needs the loss gradient mid-loop), which is why
``param_specs(..., 'pipeline')`` keeps head/embed off the 'model' axis.

MoE capacity caveat, resolved: router capacity buffers are sized from
the **global** batch token count (not the microbatch), so a pipelined
MoE run matches the full-batch forward exactly whenever the full-batch
run is below capacity.  Over capacity, which tokens drop still differs
(cumsum order restarts per microbatch) — a warning is emitted once at
build time for MoE configs.
"""

from __future__ import annotations

import warnings

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.partition import (  # noqa: F401  (bubble oracle re-export)
    even_boundaries,
    pipeline_bubble_counts,
    stage_depths,
)
from repro.dist.sharding import (
    MDL,
    _axis_size,
    _dp,
    dp_axes,
    fix_spec,
    manual_mode,
)
from repro.models import attention as attn
from repro.models import transformer as tf
from repro.models.layers import (
    dense_apply,
    embedding_logits,
    gated_mlp_apply,
    rmsnorm_apply,
)


def num_stages(mesh: Mesh) -> int:
    return mesh.shape.get(MDL, 1)


def pipeline_units(cfg) -> int:
    """Number of cut units in the stack: layers for homogeneous decoder
    stacks, shared-attention *groups* for hybrids (cuts between a group's
    Mamba layers would strand its shared block mid-stage)."""
    if cfg.is_enc_dec:
        raise NotImplementedError(
            "pipeline runtime covers decoder stacks; "
            f"{cfg.name} is encoder-decoder"
        )
    if cfg.attn_every:
        if cfg.num_layers % cfg.attn_every:
            raise ValueError("num_layers % attn_every != 0")
        return cfg.num_layers // cfg.attn_every
    return cfg.num_layers


def _resolve_boundaries(cfg, stages: int, boundaries) -> tuple[int, ...]:
    units = pipeline_units(cfg)
    if boundaries is None:
        boundaries = even_boundaries(units, stages)
    boundaries = tuple(int(b) for b in boundaries)
    if len(boundaries) != stages + 1:
        raise ValueError(
            f"{len(boundaries)} boundaries for {stages} stages "
            f"(want stages + 1)"
        )
    if boundaries[-1] != units:
        raise ValueError(
            f"boundaries end at {boundaries[-1]}, stack has {units} units"
        )
    stage_depths(boundaries)  # validates monotonicity from 0
    return boundaries


def pad_pipeline_params(params, cfg, boundaries):
    """Pad ``params['blocks']`` to the homogeneous per-stage layout the
    pipeline shard_map expects: ``(stages * max_depth, ...)`` on the
    leading layer axis, stage *k*'s slice holding its real layers
    followed by copies of its last real layer (masked no-ops at run
    time, zero gradient at train time).  Identity when the cuts are
    already even.  Works on arrays or (via ``jax.eval_shape``)
    ShapeDtypeStructs.
    """
    boundaries = tuple(int(b) for b in boundaries)
    depths = stage_depths(boundaries)
    max_d = max(depths)
    if all(d == max_d for d in depths):
        return params
    per = cfg.attn_every or 1
    rows: list[int] = []
    for s, d in enumerate(depths):
        for j in range(max_d):
            unit = boundaries[s] + min(j, d - 1)
            rows.extend(unit * per + r for r in range(per))
    gather = np.asarray(rows, np.int32)
    out = dict(params)
    out["blocks"] = jax.tree.map(lambda a: a[gather], params["blocks"])
    return out


def unpad_pipeline_params(params, cfg, boundaries):
    """Inverse of :func:`pad_pipeline_params`: recover the canonical
    ``(num_layers, ...)`` blocks layout from the padded per-stage one.

    Stage *k*'s slice holds its real layers first (rows ``j < depth_k``
    of ``k * max_depth + j``); the trailing rows are masked copies, so
    dropping them is exact.  The canonical layout is what checkpoints
    store (topology-independent restore) and what a live re-cut re-pads
    from — the unpad -> re-pad pair is how the supervisor moves running
    state between boundary vectors without touching values.
    """
    boundaries = tuple(int(b) for b in boundaries)
    depths = stage_depths(boundaries)
    max_d = max(depths)
    if all(d == max_d for d in depths):
        return params
    per = cfg.attn_every or 1
    rows: list[int] = []
    for s, d in enumerate(depths):
        for j in range(d):
            rows.extend((s * max_d + j) * per + r for r in range(per))
    gather = np.asarray(rows, np.int32)
    out = dict(params)
    out["blocks"] = jax.tree.map(lambda a: a[gather], params["blocks"])
    return out


def _check_padded(blocks, stages: int, max_d: int, per: int) -> None:
    lead = {int(l.shape[0]) for l in jax.tree.leaves(blocks)}
    want = stages * max_d * per
    if lead != {want}:
        raise ValueError(
            f"params['blocks'] leading dim {sorted(lead)} != {want} "
            f"(= stages {stages} x max stage depth {max_d} x {per}); "
            "pad uneven cuts with pad_pipeline_params(params, cfg, "
            "boundaries) before sharding"
        )


def _masked_set(q, val, i, valid):
    """q[i] = valid ? val : q[i]  (single clamped dynamic index)."""
    cur = jax.lax.dynamic_index_in_dim(q, i, 0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        q, jnp.where(valid, val, cur), i, 0
    )


def _moe_global_capacity(cfg, global_tokens: int) -> int | None:
    """Capacity per expert sized from the GLOBAL batch token count —
    the same formula ``moe_apply`` derives for the full-batch forward,
    so pipelined microbatches can never overflow unless the full-batch
    run would.  ``_ffn_apply`` clamps it to each call's own token count,
    so the dispatch buffers stay O(microbatch) — a per-expert load never
    exceeds the call's tokens, so the clamp cannot introduce drops."""
    if not cfg.moe_experts:
        return None
    return int(
        max(
            1,
            round(
                cfg.moe_capacity_factor
                * global_tokens
                * cfg.moe_top_k
                / cfg.moe_experts
            ),
        )
    )


def _warn_moe_over_capacity(cfg) -> None:
    if cfg.moe_experts:
        warnings.warn(
            f"pipelined MoE ({cfg.name}): router capacity buffers are "
            "sized from the global batch, so results match the "
            "full-batch forward below capacity; an over-capacity router "
            "still drops different tokens than the full-batch forward "
            "(per-microbatch cumsum order)",
            stacklevel=3,
        )


def _make_run_local(cfg, max_d: int, keep, positions, moe_cap, shared,
                    remat: bool = False):
    """Stage-local layer runner: scan over the (padded) slice, masking
    padded units into identity no-ops.  Returns ``(y, aux_sum)``.

    ``keep``: (max_depth,) bool — unit j is a real layer/group of this
    stage.  ``shared``: hybrid shared-attention params or None.
    ``remat``: per-layer checkpoint so the backward unit's vjp stores
    one activation per layer, not every within-layer intermediate.
    """

    if not cfg.attn_every:

        def run_local(blocks, x):
            def body(carry, inp):
                xc, aux = carry
                p, kp = inp
                y, _, a = tf.block_apply(
                    p, cfg, xc, positions, None, moe_cap=moe_cap
                )
                return (
                    jnp.where(kp, y, xc),
                    aux + jnp.where(kp, a, 0.0),
                ), None

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (y, aux), _ = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), (blocks, keep)
            )
            return y, aux

        return run_local

    per = cfg.attn_every

    def run_local(blocks, x):
        grouped = jax.tree.map(
            lambda a: a.reshape(max_d, per, *a.shape[1:]), blocks
        )

        def group_body(carry, inp):
            xc, aux = carry
            gp, kp = inp  # gp: one group's (per, ...) layer slice

            def layer_body(c, p):
                y, _, a = tf.block_apply(
                    p, cfg, c[0], positions, None, moe_cap=moe_cap
                )
                return (y, c[1] + a), None

            (y, ga), _ = jax.lax.scan(
                layer_body, (xc, jnp.zeros((), jnp.float32)), gp
            )
            h, _ = attn.gqa_apply(
                shared["attn"], cfg,
                rmsnorm_apply(shared["norm"], y, cfg.norm_eps),
                positions, None,
            )
            y = y + h
            y = y + gated_mlp_apply(
                shared["mlp"], rmsnorm_apply(shared["mlp_norm"], y, cfg.norm_eps)
            )
            return (jnp.where(kp, y, xc), aux + jnp.where(kp, ga, 0.0)), None

        if remat:
            group_body = jax.checkpoint(group_body, prevent_cse=False)
        (y, aux), _ = jax.lax.scan(
            group_body, (x, jnp.zeros((), jnp.float32)), (grouped, keep)
        )
        return y, aux

    return run_local


# ---------------------------------------------------------------------------
# forward (inference / equivalence) pipeline — fill-and-drain
# ---------------------------------------------------------------------------


def make_pipeline_forward(cfg, mesh: Mesh, num_microbatches: int = 8,
                          boundaries=None):
    """Build ``fwd(params, tokens) -> logits`` running the layer stack as
    a ``mesh.shape['model']``-stage fill-and-drain pipeline.

    ``boundaries`` are contiguous layer (group, for hybrids) cut points
    from the planner; None cuts by layer count.  Uneven cuts require
    params padded with :func:`pad_pipeline_params`.  Needs
    ``batch % num_microbatches == 0``; enc-dec stacks are not supported.
    """
    stages = num_stages(mesh)
    bounds = _resolve_boundaries(cfg, stages, boundaries)
    depths = stage_depths(bounds)
    max_d = max(depths)
    per = cfg.attn_every or 1
    if num_microbatches < 1:
        raise ValueError("need at least one microbatch")
    _warn_moe_over_capacity(cfg)
    depths_arr = np.asarray(depths, np.int32)

    def fwd(params, tokens, embeds=None):
        x = tf._embed(params, cfg, tokens, embeds)
        b, s, d = x.shape
        m = num_microbatches
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        _check_padded(params["blocks"], stages, max_d, per)
        moe_cap = _moe_global_capacity(cfg, b * s)
        x_mb = x.reshape(m, b // m, s, d)
        shared = params.get("shared_attn")

        def stage_fn(blocks, shared_p, x_mb):
            """One pipeline stage.  blocks: this stage's padded layer
            slice (max_depth * per leading); x_mb: (M, mb, S, D)
            microbatch queue, replicated over 'model', batch-split over
            the data axes."""
            with manual_mode():
                idx = jax.lax.axis_index(MDL)
                keep = jnp.arange(max_d) < jnp.asarray(depths_arr)[idx]
                positions = jnp.broadcast_to(
                    jnp.arange(x_mb.shape[2]), x_mb.shape[1:3]
                )
                run_local = _make_run_local(
                    cfg, max_d, keep, positions, moe_cap, shared_p
                )
                ring = [(i, (i + 1) % stages) for i in range(stages)]

                def round_body(t, carry):
                    buf, outs = carry
                    # stage 0 injects microbatch t while the queue lasts
                    # (single clamped read + one mask; once drained it
                    # recycles the ring buffer, whose values can no
                    # longer reach the last stage within the loop)
                    fresh = jax.lax.dynamic_index_in_dim(
                        x_mb, jnp.minimum(t, m - 1), 0, keepdims=False
                    )
                    x_in = jnp.where((idx == 0) & (t < m), fresh, buf)
                    y, _ = run_local(blocks, x_in)
                    # pipe full after stages-1 warmup rounds: last stage
                    # drains one finished microbatch per round
                    mb = jnp.maximum(t - (stages - 1), 0)
                    keep_out = (t >= stages - 1) & (idx == stages - 1)
                    outs = _masked_set(outs, y, mb, keep_out)
                    return jax.lax.ppermute(y, MDL, ring), outs

                # fori_loop (not a python loop) so the jaxpr holds ONE
                # copy of the per-stage layer scan, not m + stages - 1
                _, outs = jax.lax.fori_loop(
                    0, m + stages - 1, round_body,
                    (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb)),
                )
                # only the last stage holds real outputs — broadcast
                # them back so the result is replicated along 'model'
                outs = jnp.where(idx == stages - 1, outs, 0.0)
                return jax.lax.psum(outs, MDL)

        io_spec = P(*fix_spec((None, _dp(mesh)), x_mb.shape, mesh))
        piped = jax.shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(P(MDL), P(), io_spec),
            out_specs=io_spec,
            check_vma=False,
        )
        x = piped(params["blocks"], shared, x_mb).reshape(b, s, d)
        return tf._head(params, cfg, x)

    return fwd


# ---------------------------------------------------------------------------
# pipelined train loss/grad — gpipe vs 1f1b fused round loop
# ---------------------------------------------------------------------------


def make_pipeline_loss_and_grad(cfg, mesh: Mesh, num_microbatches: int = 8,
                                boundaries=None, schedule: str = "1f1b",
                                aux_weight: float = 0.01,
                                remat: bool = True):
    """Build ``loss_and_grad(params, batch) -> ((loss, metrics), grads)``
    with microbatch gradient accumulation *through* the pipe.

    One fused round loop serves both schedules.  Per round every stage
    executes one forward unit and one backward unit (masked when not
    scheduled — the SPMD lockstep price); the backward unit recomputes
    its stage forward from the stashed stage input (per-stage remat) and
    accumulates layer grads locally, so ``grads['blocks']`` comes out
    stage-sharded exactly like the padded params.  Final-norm + LM head
    + token-mean CE run inside the LAST stage (1F1B needs the loss
    gradient mid-loop); the embedding runs outside with a standard vjp
    fed by the dX stream exiting stage 0.

    ``schedule``: ``'gpipe'`` (backward starts after the forward drains)
    or ``'1f1b'`` (backward lags the forward by ``stages - 1`` rounds) —
    bitwise-identical results, fewer idle stage-rounds for 1f1b per
    :func:`pipeline_bubble_counts`.  Homogeneous decoder stacks only.
    """
    stages = num_stages(mesh)
    if cfg.attn_every or cfg.is_enc_dec:
        raise NotImplementedError(
            "pipelined train covers homogeneous decoder stacks; "
            f"{cfg.name} interleaves shared/cross blocks"
        )
    if cfg.frontend:
        raise NotImplementedError(
            "pipelined train is token-only; "
            f"{cfg.name} takes {cfg.frontend} embeddings"
        )
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}")
    bounds = _resolve_boundaries(cfg, stages, boundaries)
    depths = stage_depths(bounds)
    max_d = max(depths)
    m = num_microbatches
    if m < 1:
        raise ValueError("need at least one microbatch")
    _warn_moe_over_capacity(cfg)
    depths_arr = np.asarray(depths, np.int32)
    lag = (stages - 1) if schedule == "1f1b" else (m + stages - 1)
    rounds = lag + m + stages - 1
    dpn = dp_axes(mesh)
    tied = cfg.tie_embeddings

    def loss_and_grad(params, batch):
        tokens = batch["tokens"]
        inp_tok, tgt = tokens[:, :-1], tokens[:, 1:]

        def embed_fn(embed_p):
            return tf._embed({"embed": embed_p}, cfg, inp_tok, None)

        x, embed_vjp = jax.vjp(embed_fn, params["embed"])
        b, s, d = x.shape
        if b % m:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        _check_padded(params["blocks"], stages, max_d, 1)
        moe_cap = _moe_global_capacity(cfg, b * s)
        x_mb = x.reshape(m, b // m, s, d)
        t_mb = tgt.reshape(m, b // m, s)
        io_fixed = fix_spec((None, _dp(mesh)), x_mb.shape, mesh)
        # the dp factor that actually survived spec repair: when the
        # microbatch dim is not divisible by the data axes, fix_spec
        # drops them and x_mb replicates, so the dX normalizer must be
        # the EFFECTIVE shard count, not the mesh's
        ndp = _axis_size(mesh, io_fixed[1])
        head_tree = {"final_norm": params["final_norm"]}
        if tied:
            head_tree["embed"] = params["embed"]
        else:
            head_tree["lm_head"] = params["lm_head"]

        def stage_fn(blocks, head_p, x_mb, t_mb):
            with manual_mode():
                idx = jax.lax.axis_index(MDL)
                is_last = idx == stages - 1
                keep = jnp.arange(max_d) < jnp.asarray(depths_arr)[idx]
                positions = jnp.broadcast_to(
                    jnp.arange(x_mb.shape[2]), x_mb.shape[1:3]
                )
                run_local = _make_run_local(
                    cfg, max_d, keep, positions, moe_cap, None, remat=remat
                )

                def head_loss(hp, y, tg):
                    # chunked fused CE (same as the unpipelined loss):
                    # the (mb, chunk, vocab) f32 logits exist one chunk
                    # at a time, in the vjp too
                    from repro.train.step import chunked_ce

                    h = rmsnorm_apply(hp["final_norm"], y, cfg.norm_eps)
                    head_fn = (
                        (lambda hh: embedding_logits(hp["embed"], hh))
                        if tied else (lambda hh: dense_apply(hp["lm_head"], hh))
                    )
                    return chunked_ce(head_fn, h, tg)

                ring_f = [(i, (i + 1) % stages) for i in range(stages)]
                ring_b = [(i, (i - 1) % stages) for i in range(stages)]
                f32 = jnp.float32
                gblocks0 = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, f32), blocks
                )
                ghead0 = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, f32), head_p
                )

                def round_body(t, carry):
                    (buf, dbuf, stash, dhq, dxq,
                     gblocks, ghead, ce_acc, aux_acc) = carry

                    # ---- forward unit: this stage forwards microbatch
                    # t - idx (stage 0 injects it fresh off the queue)
                    fw_i = t - idx
                    fw_valid = (fw_i >= 0) & (fw_i < m)
                    fw_ic = jnp.clip(fw_i, 0, m - 1)
                    fresh = jax.lax.dynamic_index_in_dim(
                        x_mb, jnp.minimum(t, m - 1), 0, keepdims=False
                    )
                    x_in = jnp.where((idx == 0) & (t < m), fresh, buf)
                    stash = _masked_set(stash, x_in, fw_ic, fw_valid)
                    y, aux_fw = run_local(blocks, x_in)
                    aux_acc = aux_acc + jnp.where(fw_valid, aux_fw, 0.0)

                    # ---- loss seed (last stage): token-mean CE of the
                    # just-finished microbatch + its dY, queued for the
                    # backward stream.  Branched on is_last (a concrete
                    # per-device scalar, and head_loss has no
                    # collectives), so the other S-1 stages skip the
                    # vocab-sized head forward+vjp instead of masking it
                    tg_i = jax.lax.dynamic_index_in_dim(
                        t_mb, fw_ic, 0, keepdims=False
                    )

                    def seed_unit(args):
                        hp, yy, tg = args
                        ce, head_vjp = jax.vjp(
                            lambda h_, y_: head_loss(h_, y_, tg), hp, yy
                        )
                        dhp, dy = head_vjp(f32(1.0 / m))
                        return ce, dhp, dy

                    def no_seed(args):
                        hp, yy, _ = args
                        return (
                            jnp.zeros((), f32),
                            jax.tree.map(
                                lambda a: jnp.zeros(a.shape, a.dtype), hp
                            ),
                            jnp.zeros_like(yy),
                        )

                    ce_i, dhead_i, dy_i = jax.lax.cond(
                        is_last, seed_unit, no_seed, (head_p, y, tg_i)
                    )
                    seed = fw_valid & is_last
                    ce_acc = ce_acc + jnp.where(seed, ce_i / m, 0.0)
                    ghead = jax.tree.map(
                        lambda g, dg: g + jnp.where(seed, dg, 0.0).astype(f32),
                        ghead, dhead_i,
                    )
                    dhq = _masked_set(dhq, dy_i.astype(x_mb.dtype), fw_ic, seed)

                    # ---- backward unit: microbatch t - lag - (S-1-idx),
                    # recomputed from the stashed stage input (remat)
                    bw_i = t - lag - (stages - 1 - idx)
                    bw_valid = (bw_i >= 0) & (bw_i < m)
                    bw_ic = jnp.clip(bw_i, 0, m - 1)
                    x_j = jax.lax.dynamic_index_in_dim(
                        stash, bw_ic, 0, keepdims=False
                    )
                    dy_in = jnp.where(
                        is_last,
                        jax.lax.dynamic_index_in_dim(
                            dhq, bw_ic, 0, keepdims=False
                        ),
                        dbuf,
                    )
                    _, pull = jax.vjp(run_local, blocks, x_j)
                    dbl_j, dx_j = pull((dy_in, f32(aux_weight / m)))
                    gblocks = jax.tree.map(
                        lambda g, dg: g
                        + jnp.where(bw_valid, dg, 0.0).astype(f32),
                        gblocks, dbl_j,
                    )
                    dxq = _masked_set(
                        dxq, dx_j, bw_ic, bw_valid & (idx == 0)
                    )

                    return (
                        jax.lax.ppermute(y, MDL, ring_f),
                        jax.lax.ppermute(dx_j, MDL, ring_b),
                        stash, dhq, dxq, gblocks, ghead, ce_acc, aux_acc,
                    )

                zero_mb = jnp.zeros_like(x_mb[0])
                (_, _, _, _, dxq, gblocks, ghead, ce_acc, aux_acc) = (
                    jax.lax.fori_loop(
                        0, rounds, round_body,
                        (zero_mb, zero_mb, jnp.zeros_like(x_mb),
                         jnp.zeros_like(x_mb), jnp.zeros_like(x_mb),
                         gblocks0, ghead0, jnp.zeros((), f32),
                         jnp.zeros((), f32)),
                    )
                )

                # reductions: per-shard grads are d(local-mean loss);
                # the global loss is the mean over data shards, so
                # replicated-param grads pmean over the data axes.  The
                # head/loss ran only on the last stage -> psum over
                # 'model' broadcasts it; dX exits stage 0 the same way.
                def pmean_dp(v):
                    return jax.lax.pmean(v, dpn) if dpn else v

                gblocks = jax.tree.map(pmean_dp, gblocks)
                ghead = jax.tree.map(
                    lambda g: pmean_dp(jax.lax.psum(g, MDL)), ghead
                )
                dxq = jax.lax.psum(dxq, MDL) / ndp
                ce = pmean_dp(jax.lax.psum(ce_acc, MDL))
                aux = pmean_dp(jax.lax.psum(aux_acc, MDL)) / m
                return gblocks, ghead, dxq, ce, aux

        io_spec = P(*io_fixed)
        tgt_spec = P(*fix_spec((None, _dp(mesh)), t_mb.shape, mesh))
        piped = jax.shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(P(MDL), P(), io_spec, tgt_spec),
            out_specs=(P(MDL), P(), io_spec, P(), P()),
            check_vma=False,
        )
        gblocks, ghead, dxq, ce, aux = piped(
            params["blocks"], head_tree, x_mb, t_mb
        )
        (d_embed,) = embed_vjp(dxq.reshape(b, s, d).astype(x.dtype))
        d_embed = jax.tree.map(lambda a: a.astype(jnp.float32), d_embed)
        if tied:  # table grad: lookup (outside) + tied logits (in-pipe)
            d_embed = jax.tree.map(jnp.add, d_embed, ghead["embed"])
        grads = {
            "blocks": gblocks,
            "final_norm": ghead["final_norm"],
            "embed": d_embed,
        }
        if not tied:
            grads["lm_head"] = ghead["lm_head"]
        loss = ce + aux_weight * aux
        return (loss, {"ce": ce, "aux": aux}), grads

    return loss_and_grad
