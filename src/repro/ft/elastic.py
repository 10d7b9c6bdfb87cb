"""Elastic rescale + failure recovery.

The recovery contract at pod scale:

  1. a node fails / the pod is resized,
  2. the launcher reforms the mesh from the devices that remain
     (``make_mesh_for(devices)``),
  3. ``rescale(ckpt_dir, like, new_mesh)`` restores the latest
     checkpoint re-sharded onto the new mesh (checkpoints store FULL
     arrays, so any old-topology -> new-topology move is a device_put),
  4. training resumes; the batch schedule recomputes from the restored
     step, so sample order is preserved modulo the resize.

The same path handles *scale-up* (new nodes join) — reconfigurability
is the paper's whole point, applied to fault tolerance.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding

from repro.dist.sharding import param_specs
from repro.ft import checkpoint as ckpt
from repro.launch.mesh import make_mesh
from repro.optim.adamw import OptState


def make_mesh_for(devices=None, model_axis: int | None = None) -> Mesh:
    """Form a (data, model) mesh from whatever devices survive."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_axis is None:
        # largest power-of-two model axis <= sqrt(n)
        model_axis = 1
        while model_axis * 2 <= int(n ** 0.5):
            model_axis *= 2
    data_axis = n // model_axis
    return make_mesh((data_axis, model_axis), ("data", "model"),
                     devices=devices[: data_axis * model_axis])


def state_shardings(state_like, mesh: Mesh, strategy: str = "fused"):
    pspecs = param_specs(state_like["params"], mesh, strategy)
    specs = {
        "params": pspecs,
        "opt": OptState(mu=pspecs, nu=pspecs,
                        step=jax.sharding.PartitionSpec()),
        "step": jax.sharding.PartitionSpec(),
    }
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


def rescale(ckpt_dir: str, state_like, new_mesh: Mesh, strategy: str = "fused"):
    """Restore a checkpoint re-sharded for ``new_mesh``."""
    shardings = state_shardings(state_like, new_mesh, strategy)
    return ckpt.restore(ckpt_dir, state_like, shardings)
