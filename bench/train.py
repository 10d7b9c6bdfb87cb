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


def leaf_norms(tree: dict) -> dict:
    """Norm of each tensor of a reference-layout tree, the stacked layer
    tensors split per layer (``layers.<i>.<name>``)."""
    import jax.numpy as jnp

    out = {}
    for name, leaf in tree.items():
        if name == "layers":
            for k, v in leaf.items():
                per = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                                       axis=tuple(range(1, v.ndim))))
                for i, x in enumerate(np.asarray(per)):
                    out[f"layers.{i}.{k}"] = float(x)
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32)))))
    return out


def host_leaves(tree: dict) -> dict:
    """A reference-layout tree as float32 host arrays, keyed and split
    per layer as :func:`leaf_norms` keys them."""
    out = {}
    for name, leaf in tree.items():
        if name == "layers":
            for k, v in leaf.items():
                a = np.asarray(v, np.float32)
                for i in range(a.shape[0]):
                    out[f"layers.{i}.{k}"] = a[i]
        else:
            out[name] = np.asarray(leaf, np.float32)
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


def _diff(a: dict, b: dict) -> dict:
    import jax

    return jax.tree.map(lambda x, y: x.astype("float32") - y.astype("float32"),
                        a, b)


def reference(config: dict, job: dict, seed: int, data, steps: int,
              mode: str = "f32", rows: int | None = None):
    """Losses, first clipped gradient norms, change norms and first
    clipped gradient (host arrays) of the plain reference over
    ``steps`` steps (matmuls in ``mode``; with ``rows``, on the first
    ``rows`` rows of each batch only)."""
    import jax

    from bench.spec import reference_module

    ref = reference_module(config)
    opt = job["optimizer"]
    vg = jax.jit(jax.value_and_grad(
        lambda w, rows: ref.loss(w, config, rows, mode)))
    upd = jax.jit(lambda w, g, s, k: refopt.update(opt, w, g, s, k),
                  static_argnums=3, donate_argnums=(0, 2))
    w = weights.make(config, seed, job["params_dtype"])
    state = refopt.init(w)
    losses, gnorm, grad = [], None, None
    for i in range(steps):
        loss, g = vg(w, jax.numpy.asarray(data.batch(i)[:rows]))
        losses.append(float(loss))
        w, state, gc_ = upd(w, g, state, i + 1)
        if i == 0:
            gnorm = leaf_norms(gc_)
            grad = host_leaves(gc_)
        del g, gc_
    del state
    w0 = weights.make(config, seed, job["params_dtype"])
    dnorm = leaf_norms(_diff(w, w0))
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

    job, config = cell.traffic, cell.config
    cfg = program.model_config(config)
    place, mesh = program.train_step(cfg, job["optimizer"])
    data = traffic.PackedDocs(job, seed, int(config["vocab_size"]),
                              int(config["eos_token_id"]))
    w = weights.make(config, seed, job["params_dtype"])
    program.check_layout(w, cfg)
    state, step, _ = place(program.to_program(w))
    del w

    def feed(k):
        return {"tokens": jnp.asarray(data.batch(k))}

    with mesh:
        compiled = step.lower(state, feed(0)).compile()
        losses = []
        b1 = job["optimizer"]["b1"]
        for k in range(CHECK_STEPS):
            state, m = compiled(state, feed(k))
            losses.append(float(m["loss"]))
            if k == 0:
                mu = program.adam_moments(state)
                gnorm = {n: v / (1 - b1) for n, v in leaf_norms(mu).items()}
                grad = {n: v / (1 - b1) for n, v in host_leaves(mu).items()}
                del mu
        w0 = weights.make(config, seed, job["params_dtype"])
        dnorm = leaf_norms(_diff(program.from_program(state["params"]), w0))
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
    del state, compiled, m, pending
    gc.collect()
    t_ref = clock()
    want = reference(config, job, seed, data, CHECK_STEPS)
    r_losses, r_gnorm = want[0], want[1]
    med = float(np.median(list(r_gnorm.values())))
    checks = compare((losses, gnorm, dnorm, grad), want)
    del grad
    control = None
    if check == "control":
        control = {
            CONTROL: compare(reference(config, job, seed, data, CHECK_STEPS,
                                       mode=CONTROL), want),
            "half_batch": compare(reference(
                config, job, seed, data, CHECK_STEPS,
                rows=int(job["batch"]) // 2), want),
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
