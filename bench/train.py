"""A training cell: the program's fused train step with its AdamW, on
packed documents from the seed, timed over the window; its first three
steps checked against the plain reference.

Set-up builds one compiled step and its state and drives them through
steps 1-3 with the window's own call; the window then continues from
step 4 on the same objects.  Compared with the reference (float32 at the
highest precision, with the job's AdamW):
  loss_gap   relative gap of the first step's loss (the losses of steps
             2 and 3 swing with the noise of the updates before them:
             they are printed, not compared),
  grad_gap   worst leaf's gap of the gradient norm of step 1, as the
             optimizer got it (its first moment over 1 - beta1),
  delta_gap  worst leaf's gap of the norm of the change after step 3,
  grad_err   worst leaf's norm of the difference between the program's
             step-1 gradient and the reference's, element by element
             (a gap of norms averages out rounding that is spread over
             the elements; this does not),
each leaf's gap over the larger of its reference norm and the median
leaf's.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone and are left out of delta_gap.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import refopt, traffic, weights
from bench.peaks import peak_bytes

clock = time.monotonic
CHECK_STEPS = 3
#: the control's matmul arithmetic: the job states float32 parameters at
#: the default matmul precision, which on a TPU rounds matmul operands to
#: bfloat16, so the precision below the one that runs is float8
CONTROL = "fp8"
#: a leaf whose reference gradient is below this share of the median
#: leaf's moves under Adam by round-off alone
STILL_LEAF = 1e-3


def _per_layer(tree: dict):
    """(key, leaf, stacked) of a reference-layout tree: every dict-valued
    entry is a stacked group whose leaves carry a leading layer axis,
    keyed ``<group>.<i>.<name>`` per layer."""
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            for k, v in leaf.items():
                yield f"{name}.{{}}.{k}", v, True
        else:
            yield name, leaf, False


def _norms_into(out: dict, key: str, v, stacked: bool) -> None:
    import jax.numpy as jnp

    sq = jnp.square(v.astype(jnp.float32))
    if stacked:
        per = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, v.ndim))))
        for i, x in enumerate(np.asarray(per)):
            out[key.format(i)] = float(x)
    else:
        out[key] = float(jnp.sqrt(jnp.sum(sq)))


def leaf_norms(tree: dict) -> dict:
    """Norm of each tensor of a reference-layout tree, the stacked layer
    tensors split per layer."""
    out: dict = {}
    for key, v, stacked in _per_layer(tree):
        _norms_into(out, key, v, stacked)
    return out


def change_norms(new: dict, old: dict) -> dict:
    """:func:`leaf_norms` of ``new - old`` in float32, one leaf at a
    time, each leaf of ``old`` first placed as ``new``'s is."""
    import jax

    before = {key: v for key, v, _ in _per_layer(old)}
    out: dict = {}
    for key, v, stacked in _per_layer(new):
        was = jax.device_put(before[key], v.sharding)
        _norms_into(out, key, v.astype("float32") - was.astype("float32"),
                    stacked)
    return out


def host_leaves(tree: dict) -> dict:
    """A reference-layout tree as float32 host arrays, keyed and split
    per layer as :func:`leaf_norms` keys them."""
    out = {}
    for key, v, stacked in _per_layer(tree):
        a = np.asarray(v, np.float32)
        if stacked:
            for i in range(a.shape[0]):
                out[key.format(i)] = a[i]
        else:
            out[key] = a
    return out


def worst_err(got: dict, want: dict) -> float:
    """max over leaves of |got - want| / max(|want|, median |want|)."""
    norms = {k: float(np.linalg.norm(v.ravel())) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm((got[k] - want[k]).ravel()))
               / max(norms[k], med) for k in want)


def worst_gap(got: dict, want: dict, keep=None) -> float:
    """max over leaves of |got - want| / max(want, median of want)."""
    names = [k for k in want if keep is None or keep(k)]
    med = float(np.median([want[k] for k in names]))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in names)


def reference_mesh(chips: int):
    """A one-axis mesh over the cell's chips, which the reference's
    weights and optimizer state are spread over when one chip cannot
    hold them (None for a one-chip cell)."""
    import jax

    from jax.sharding import Mesh

    if chips <= 1:
        return None
    return Mesh(np.asarray(jax.devices()[:chips]), ("chips",))


def reference(model, config: dict, job: dict, seed: int, data, steps: int,
              mode: str = "f32", rows: int | None = None, mesh=None):
    """Losses, first clipped gradient norms, change norms and first
    clipped gradient (host arrays) of the plain reference over
    ``steps`` steps (matmuls in ``mode``; with ``rows``, on the first
    ``rows`` rows of each batch only).  Parameters are held in the job's
    ``params_dtype`` and moments in its ``moments_dtype``; the loss,
    its gradient and the update are computed in float32.  With ``mesh``
    every weight and moment is spread over its devices and the jitted
    steps are partitioned by XLA from that placement."""
    import jax
    import jax.numpy as jnp

    from bench.spec import reference_module

    ref = reference_module(config)
    opt = job["optimizer"]
    f32 = jnp.float32

    def value_and_grad(w, rows):
        w32 = jax.tree.map(lambda a: a.astype(f32), w)
        return jax.value_and_grad(
            lambda p: ref.loss(p, config, rows, mode))(w32)

    vg = jax.jit(value_and_grad)
    upd = jax.jit(lambda w, g, s, k: refopt.update(opt, w, g, s, k),
                  static_argnums=3, donate_argnums=(0, 2))
    w = weights.make(model, config, seed, job["params_dtype"], mesh)
    state = refopt.init(w, job.get("moments_dtype", "float32"))
    losses, gnorm, grad = [], None, None
    for i in range(steps):
        loss, g = vg(w, jnp.asarray(data.batch(i)[:rows]))
        losses.append(float(loss))
        w, state, gc_ = upd(w, g, state, i + 1)
        if i == 0:
            gnorm = leaf_norms(gc_)
            grad = host_leaves(gc_)
        del g, gc_
    del state
    w0 = weights.make(model, config, seed, job["params_dtype"], mesh)
    dnorm = change_norms(w, w0)
    return losses, gnorm, dnorm, grad


def compare(got, want) -> dict:
    """The numbers of the module docstring, for (losses, gradient norms,
    change norms, gradient) against the reference's."""
    losses, gnorm, dnorm, grad = got
    r_losses, r_gnorm, r_dnorm, r_grad = want
    med = float(np.median(list(r_gnorm.values())))
    moving = {n for n, v in r_gnorm.items() if v >= STILL_LEAF * med}
    return {
        "loss_gap": abs(losses[0] - r_losses[0]) / abs(r_losses[0]),
        "grad_gap": worst_gap(gnorm, r_gnorm),
        "delta_gap": worst_gap(dnorm, r_dnorm, keep=moving.__contains__),
        "grad_err": worst_err(grad, r_grad),
    }


def run_cell(cell, seed: int, seconds: float, tracer, started: float,
             compiles, check: str = "program") -> dict:
    """One run of the training cell.  ``check`` "control" adds the
    readings of the float8 control and of the half-batch fault, both
    computed by the reference in the program's place."""
    import jax
    import jax.numpy as jnp

    from bench import program

    job, config, model = cell.traffic, cell.config, cell.model
    cfg = program.model_config(model, config)
    data = traffic.PackedDocs(job, seed, int(config["vocab_size"]),
                              int(config["eos_token_id"]))
    w = weights.make(model, config, seed, job["params_dtype"])
    program.check_layout(model, w, cfg)
    run, state = program.trainer(model, cfg, job,
                                 program.to_program(model, w))
    del w

    def feed(k):
        return run.feed(data.batch(k))

    with run.mesh:
        compiled = run.step.lower(state, feed(0)).compile()
        losses = []
        b1 = job["optimizer"]["b1"]
        for k in range(CHECK_STEPS):
            state, m = compiled(state, feed(k))
            losses.append(float(m["loss"]))
            if k == 0:
                mu = run.moments(state)
                gnorm = {n: v / (1 - b1) for n, v in leaf_norms(mu).items()}
                grad = {n: v / (1 - b1) for n, v in host_leaves(mu).items()}
                del mu
        w0 = weights.make(model, config, seed, job["params_dtype"])
        dnorm = change_norms(run.params(state), w0)
        del w0
        jax.block_until_ready(state)
        setup_s = clock() - started
        c0 = compiles.count
        rows = int(job["batch"]) * int(job["seq_len"])
        if tracer is not None:
            tracer.start()
        t0 = clock()
        k, pending, seen = CHECK_STEPS, None, [t0]
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.generate"):
                    batch = feed(k)
                with jax.profiler.TraceAnnotation("bench.step"):
                    state, m = compiled(state, batch)
                k += 1
                if pending is not None:
                    float(pending)
                    seen.append(clock())
                pending = m["loss"]
                if clock() >= t0 + seconds:
                    break
            float(pending)
            t1 = clock()
            seen.append(t1)
        if tracer is not None:
            tracer.stop()
        in_window = compiles.count - c0
    steps = k - CHECK_STEPS
    peak = peak_bytes()
    mem = compiled.memory_analysis()
    del state, compiled, m, pending, run
    gc.collect()
    t_ref = clock()
    mesh = reference_mesh(cell.chips)
    want = reference(model, config, job, seed, data, CHECK_STEPS, mesh=mesh)
    r_losses, r_gnorm = want[0], want[1]
    med = float(np.median(list(r_gnorm.values())))
    checks = compare((losses, gnorm, dnorm, grad), want)
    del grad
    control = None
    if check == "control":
        control = {
            CONTROL: compare(reference(model, config, job, seed, data,
                                       CHECK_STEPS, mode=CONTROL, mesh=mesh),
                             want),
            "half_batch": compare(reference(
                model, config, job, seed, data, CHECK_STEPS,
                rows=int(job["batch"]) // 2, mesh=mesh), want),
        }
    return {
        "control": control,
        "setup_s": setup_s,
        "e2e": {"train_tokens_per_s": steps * rows / (t1 - t0)},
        "compiles_in_window": in_window, "attempted": steps, "failed": 0,
        "peak": peak, "checks": checks,
        "notes": {
            "steps": steps, "window_s": t1 - t0, "losses": losses,
            "loss_seen_s": np.diff(seen).round(4).tolist(),
            "reference_losses": r_losses, "reference_s": clock() - t_ref,
            "still_leaves": sorted(n for n, v in r_gnorm.items()
                                   if v < STILL_LEAF * med),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        },
        "tokens_per_step": rows, "window": (t0, t1), "steps_run": steps,
    }
