"""Sharding-spec engine: the paper's cluster plans as PartitionSpecs.

This is the runtime half of the planner/runtime split.  The planner
(``repro.core.strategies`` -> ``repro.core.placement``) picks one of the
paper's strategies; this module lowers that choice onto an actual
``jax.sharding.Mesh``:

  scatter_gather      -> params fully replicated, batch split over the
                         data axes (the paper's frame round-robin)
  ai_core_assignment  -> tensor/expert parallelism: the bottleneck
                         matmuls (QKV/MLP/expert FFN — the highest-MAC
                         operators) get the ``model`` axis
  fused               -> FSDP x TP 2D: the AI-core TP split plus the
                         data axes sharding the complementary weight dim
  pipeline            -> the 'model' axis shards the *leading layer
                         axis* of stacked blocks (stage k physically
                         holds its — possibly padded, uneven-cut —
                         contiguous layer slice, matching
                         :mod:`repro.dist.pipeline`'s shard_map
                         in_specs); non-stacked params (embed / head /
                         final norm) stay off 'model' and FSDP over the
                         data axes only, since the pipelined train step
                         replicates them into the last stage's loss head

Everything here is *mesh-safe by construction*: every emitted spec runs
through :func:`fix_spec`, which drops any sharding whose dimension does
not divide the mesh axis, so the same code path works on a 1-CPU smoke
mesh, the 4-fake-device pipeline test, and the 16x16 / 2x16x16 dry-run
meshes.

Activation hints (:func:`hint` / :func:`hint_dp`) are
``with_sharding_constraint`` wrappers that no-op when no mesh is active
(plain CPU tests) and inside :func:`manual_mode` (shard_map bodies,
where the axes are already manual and a named-sharding constraint would
be ill-typed).
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: mesh axis names.  ``DP`` is the canonical data axis; a multi-pod mesh
#: adds a leading "pod" axis which :func:`dp_axes` folds into the
#: data-parallel group.  ``MDL`` carries TP/EP/pipeline-stage sharding.
DP = "data"
MDL = "model"

#: weight matrices split column-wise (output-dim) under TP — each shard
#: computes a slice of the output features
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "w_gate", "w_up", "wuk", "wuv", "wdkv", "wdq",
    "in_proj", "lm_head",
})
#: weight matrices split row-wise (input-dim) under TP — they consume
#: the column-parallel outputs, so the contraction dim is sharded and
#: the result is psum-reduced
_ROW_PARALLEL = frozenset({"wo", "w_down", "out_proj"})

#: param subtrees whose leaves carry a leading stacked-layer axis (the
#: ``lax.scan`` convention in repro.models) — FSDP avoids that axis
_STACKED_SUBTREES = frozenset({"blocks", "encoder", "decoder"})

SHARDING_STRATEGIES = ("scatter_gather", "ai_core_assignment", "fused",
                      "pipeline")


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Every mesh axis that carries data parallelism (all but 'model')."""
    return tuple(a for a in mesh.axis_names if a != MDL)


def _dp(mesh: Mesh):
    """dp_axes as a PartitionSpec entry: name, tuple of names, or None."""
    axes = dp_axes(mesh)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _axis_size(mesh: Mesh, axis) -> int:
    """Size of a spec entry: an axis name or a tuple of axis names."""
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def fix_spec(spec, shape, mesh: Mesh) -> tuple:
    """Repair ``spec`` against ``shape``: any entry whose mesh-axis size
    does not divide its dimension is trimmed (tuple entries drop axes
    from the right) or dropped entirely.  Unknown axis names are dropped.
    The result always satisfies ``dim % _axis_size(mesh, entry) == 0``
    and is padded with None to ``len(shape)``.
    """
    fixed = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            fixed.append(None)
            continue
        axes = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        axes = tuple(a for a in axes if a in mesh.shape)
        while axes and dim % _axis_size(mesh, axes) != 0:
            axes = axes[:-1]
        if not axes:
            fixed.append(None)
        elif len(axes) == 1:
            fixed.append(axes[0])
        else:
            fixed.append(axes)
    return tuple(fixed)


# ---------------------------------------------------------------------------
# activation hints
# ---------------------------------------------------------------------------

_MANUAL = contextvars.ContextVar("repro_dist_manual", default=False)


@contextlib.contextmanager
def manual_mode():
    """Disable activation hints while tracing a shard_map body, where
    mesh axes are manual and with_sharding_constraint is ill-typed."""
    token = _MANUAL.set(True)
    try:
        yield
    finally:
        _MANUAL.reset(token)


def _current_mesh() -> Mesh | None:
    from jax.interpreters import pxla

    mesh = pxla.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def hint(x, *axes):
    """``with_sharding_constraint(x, P(*axes))`` against the active mesh.

    Entries may be None, explicit axis names, or the DP/MDL sentinels;
    DP expands to *all* data axes of the mesh (so the same model code
    serves single-pod and multi-pod meshes).  Shorter specs are padded
    with None; illegal entries are repaired by :func:`fix_spec`.  No-op
    when no mesh is active or inside :func:`manual_mode`.
    """
    if _MANUAL.get():
        return x
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = []
    for a in axes[: x.ndim]:
        if a == DP:
            spec.append(_dp(mesh))
        elif a == MDL:
            spec.append(MDL if MDL in mesh.shape else None)
        else:
            spec.append(a)
    fixed = fix_spec(tuple(spec), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


def hint_dp(x):
    """Keep the leading (batch) dim split across the data axes."""
    return hint(x, DP)


def per_shard(fn, arrays, scalars=()):
    """``fn(*arrays, *scalars)`` run once per shard of the active mesh.

    Mosaic (Pallas TPU) kernels cannot be partitioned automatically, so
    under a mesh of more than one device a kernel call goes through
    ``shard_map``: dim 0 (batch) of every array and of the result splits
    over the data axes, dim 2 (heads) over 'model', each only where every
    array divides; ``scalars`` replicate.  With no such mesh, or inside
    a shard_map body (:func:`manual_mode`), ``fn`` is called directly.
    """
    mesh = _current_mesh()
    if _MANUAL.get() or mesh is None or mesh.size == 1:
        return fn(*arrays, *scalars)
    dp = _dp(mesh)
    batch = dp if all(a.shape[0] % _axis_size(mesh, dp) == 0
                      for a in arrays) else None
    heads = MDL if MDL in mesh.shape and all(
        a.shape[2] % mesh.shape[MDL] == 0 for a in arrays) else None
    spec = P(batch, None, heads)
    scalars = tuple(jax.numpy.asarray(x) for x in scalars)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec,) * len(arrays) + (P(),) * len(scalars),
        out_specs=spec, check_vma=False,
    )(*arrays, *scalars)


# ---------------------------------------------------------------------------
# input / cache specs
# ---------------------------------------------------------------------------


def batch_spec(mesh: Mesh, ndim: int = 2) -> P:
    """Batch-leading array: dim 0 over the data axes, rest replicated."""
    return P(_dp(mesh), *([None] * (ndim - 1)))


def data_specs(batch, mesh: Mesh):
    """Specs for a pytree of input arrays (tokens/embeds/frames): the
    leading batch dim is split over the data axes."""

    def leaf(x):
        if x.ndim == 0:
            return P()
        return P(*fix_spec((_dp(mesh),), x.shape, mesh))

    return jax.tree.map(leaf, batch)


def cache_specs(caches, mesh: Mesh):
    """Specs for stacked KV/SSM cache trees (leading layer axis, batch at
    dim 1).  Attention k/v additionally put their heads dim on 'model'
    (TP serving keeps each shard's heads local); 'len' counters and conv
    states replicate.
    """

    def leaf(path, x):
        name = _key_names(path)[-1] if path else ""
        if x.ndim < 2 or name == "len":
            return P()
        spec = [None] * x.ndim
        spec[1] = _dp(mesh)
        if name in ("k", "v") and x.ndim >= 4:
            spec[x.ndim - 2] = MDL  # heads dim of (L, B, T, H, D)
        elif name == "ssm" and x.ndim >= 4:
            spec[2] = MDL  # heads dim of (L, B, H, N, P)
        return P(*fix_spec(tuple(spec), x.shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf, caches)


# ---------------------------------------------------------------------------
# param specs — the strategy engine
# ---------------------------------------------------------------------------


def _key_names(path) -> list[str]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:
            names.append(str(k))
    return names


def _tp_dim(names: list[str], ndim: int) -> int | None:
    """Which dim the 'model' axis shards under AI-core assignment (TP/EP).

    Mirrors the paper's rule — the highest-MAC operators get the
    accelerator axis: QKV/MLP matmuls split column-wise, their consumers
    row-wise, MoE experts split across the expert axis, the embedding
    across d_model.  Norm scales, biases of row-parallel layers, routers
    and the small SSM vectors stay replicated.
    """
    if ndim < 2 or not names:
        return None
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if "experts" in names or "shared" in names:
        # (L, E, d_in, d_out) stacked / (E, d_in, d_out) unstacked: EP
        # over the expert axis
        return ndim - 3 if leaf == "w" else None
    if leaf == "table":
        # embedding (V, D): vocab-parallel (Megatron convention).  The
        # lookup lowers to a masked gather + all-reduce and the tied
        # logits keep vocab sharded; splitting D instead makes XLA's
        # partitioner emit an illegal dynamic-slice under grad-accum.
        return ndim - 2
    if leaf == "w":
        if parent in _ROW_PARALLEL:
            return ndim - 2
        if parent in _COL_PARALLEL:
            return ndim - 1
        return None  # router & friends replicate
    if leaf == "b" and parent in _COL_PARALLEL:
        return ndim - 1  # bias follows its column-split output dim
    return None


def _fsdp_dim(names: list[str], shape, tp: int | None) -> int | None:
    """Which dim the data axes shard under 'fused' (FSDP x TP): the
    largest weight dim not already taken by TP, skipping the stacked
    layer axis (scan would gather a layer slice per step anyway, and the
    per-layer all-gather of a layer-sharded stack serializes)."""
    if len(shape) < 2 or not names:
        return None
    if names[-1] not in ("w", "table", "conv_w"):
        return None  # scales/biases/vectors are too small to matter
    start = 1 if names[0] in _STACKED_SUBTREES else 0
    candidates = [d for d in range(start, len(shape)) if d != tp]
    if not candidates:
        return None
    return max(candidates, key=lambda d: shape[d])


def param_specs(params, mesh: Mesh, strategy: str = "fused"):
    """PartitionSpec tree for a param (shape) tree under ``strategy``.

    Accepts real arrays or ShapeDtypeStructs; returns one spec per leaf
    with the tree structure preserved.  Under 'pipeline' the stacked
    block subtrees put 'model' on the leading layer axis — the same
    layout :func:`repro.dist.pipeline.make_pipeline_forward` demands in
    its shard_map in_specs, so the stored params feed the pipeline with
    no per-step resharding — while non-stacked params (embed, head,
    final norm) keep the 'fused' layout.  Every spec is repaired with
    :func:`fix_spec`, so the result is legal on any mesh.
    """
    if strategy not in SHARDING_STRATEGIES:
        raise ValueError(
            f"unknown sharding strategy {strategy!r}; "
            f"choose from {SHARDING_STRATEGIES}"
        )
    dp_entry = _dp(mesh)

    def leaf(path, x):
        shape = tuple(x.shape)
        if strategy == "scatter_gather" or not shape:
            return P()
        names = _key_names(path)
        spec = [None] * len(shape)
        if strategy == "pipeline":
            if names and names[0] in _STACKED_SUBTREES:
                # layer axis only: the pipeline shard_map's in_specs is
                # P('model') on the (possibly padded, stages*max_depth)
                # layer axis, so any extra dp sharding here would be
                # all-gathered on every forward call
                spec[0] = MDL if MDL in mesh.shape else None
                return P(*fix_spec(tuple(spec), shape, mesh))
            # non-stacked params (embed / head / final norm) stay OFF the
            # 'model' axis: the train pipe folds the loss head into the
            # last stage with replicated in_specs, so a model-axis shard
            # here would be re-gathered along the stage axis every step.
            # FSDP over the data axes still bounds their memory.
            fs = _fsdp_dim(names, shape, None)
            if fs is not None:
                spec[fs] = dp_entry
            return P(*fix_spec(tuple(spec), shape, mesh))
        tp = _tp_dim(names, len(shape))
        if tp is not None and MDL in mesh.shape:
            spec[tp] = MDL
        if strategy == "fused":
            fs = _fsdp_dim(names, shape, tp)
            if fs is not None:
                spec[fs] = dp_entry
        return P(*fix_spec(tuple(spec), shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf, params)
