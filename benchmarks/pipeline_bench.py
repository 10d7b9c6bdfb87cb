"""Pipeline schedule benchmarks: bubble fractions and cut balance.

Three sections, all archived per-PR in ``BENCH_pipeline.json``:

1. **Bubble accounting** — ``pipeline_bubble_counts`` idle fractions for
   fill-and-drain GPipe vs 1F1B across (stages, microbatches).  1F1B
   overlaps the forward drain with the backward fill, halving the idle
   stage-rounds at m >= S.
2. **Cut balance** — max-stage/mean-stage cost imbalance of even
   (layer-count) cuts vs the cost-driven ``partition_layers`` DP, on a
   uniform stack and on skewed per-layer cost profiles.  This is the
   paper's "more resources to the most intensive layers" knob in
   numbers: even cuts on a skewed stack bottleneck the pipe on the
   heaviest stage.
3. **Execution smoke** (needs >= 2 devices, e.g. CI's
   ``--xla_force_host_platform_device_count=4``) — wall-clock of the
   shard_map pipeline forward under even vs uneven cuts.  On fake CPU
   devices every layer really costs the same, so this row tracks the
   *padding overhead* of uneven cuts (each stage scans max-depth
   rounds, masked or not) rather than the balance win — the balance win
   only exists when per-layer costs actually differ, which is what
   section 2 quantifies against the cost model.
"""

from __future__ import annotations

import time


def _bubble_rows():
    from repro.dist.pipeline import pipeline_bubble_counts

    rows = []
    for s, m in [(2, 4), (4, 4), (4, 8), (4, 16), (8, 32)]:
        cells = {}
        for sched in ("forward", "gpipe", "1f1b"):
            rounds, busy, idle = pipeline_bubble_counts(s, m, sched)
            cells[sched] = (rounds, idle, idle / (busy + idle))
        print(
            f"bubble S={s} m={m}: "
            f"gpipe {cells['gpipe'][0]} rounds / {cells['gpipe'][1]} idle "
            f"({cells['gpipe'][2]:.2f}), "
            f"1f1b {cells['1f1b'][0]} rounds / {cells['1f1b'][1]} idle "
            f"({cells['1f1b'][2]:.2f})"
        )
        rows.append((
            f"pipeline_bubble_s{s}_m{m}", "",
            f"gpipe_rounds={cells['gpipe'][0]};gpipe_idle={cells['gpipe'][1]};"
            f"f1b_rounds={cells['1f1b'][0]};f1b_idle={cells['1f1b'][1]};"
            f"fwd_idle={cells['forward'][1]}",
        ))
    return rows


# per-layer cost profiles: uniform (a dense LM), front_heavy (early
# layers carry long-context attention), moe_every_3 (a dense/MoE
# interleave whose period does NOT divide the stage width, so even cuts
# land mid-pattern — the zamba2/deepseek-style skew)
_PROFILES = {
    "uniform": [1.0] * 16,
    "front_heavy": [4.0] * 4 + [1.0] * 12,
    "moe_every_3": [4.0 if i % 3 == 0 else 1.0 for i in range(16)],
}


def _imbalance_rows(stages: int = 4):
    from repro.core.partition import (
        even_boundaries,
        partition_layers,
        stage_costs,
    )

    rows = []
    for name, costs in _PROFILES.items():
        mean = sum(costs) / stages

        def imb(bounds):
            return max(stage_costs(costs, bounds)) / mean

        even = even_boundaries(len(costs), stages)
        bal = partition_layers(costs, stages)
        print(f"imbalance[{name}] S={stages}: even {imb(even):.3f} "
              f"(cuts {even}) vs balanced {imb(bal):.3f} (cuts {bal})")
        rows.append((
            f"pipeline_imbalance_{name}", "",
            f"even={imb(even):.3f};balanced={imb(bal):.3f};"
            f"cuts={'/'.join(map(str, bal))}",
        ))
    return rows


def _execution_rows():
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 2:
        print("execution smoke skipped: needs >= 2 devices "
              "(set --xla_force_host_platform_device_count)")
        return []
    from repro.configs.base import get_config
    from repro.core.partition import even_boundaries, partition_layers
    from repro.dist.pipeline import make_pipeline_forward, pad_pipeline_params
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as tf

    stages = min(4, len(jax.devices()))
    mesh = make_mesh((len(jax.devices()) // stages, stages),
                     ("data", "model"))
    cfg = get_config("qwen3_0p6b").scaled_down(
        num_layers=8, d_model=128, vocab=512
    )
    # a front-heavy cost-model profile: the DP gives stage 0 one layer
    costs = [4.0] * 2 + [1.0] * 6
    params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)

    rows = []
    for label, bounds in [
        ("even", even_boundaries(cfg.num_layers, stages)),
        ("uneven", partition_layers(costs, stages)),
    ]:
        padded = pad_pipeline_params(params, cfg, bounds)
        with mesh:
            fwd = jax.jit(make_pipeline_forward(cfg, mesh, 4, boundaries=bounds))
            fwd(padded, tokens).block_until_ready()  # compile+warm
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                out = fwd(padded, tokens)
            out.block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        print(f"exec[{label}] cuts {bounds}: {dt * 1e3:.1f} ms/call "
              f"({stages} stages, 4 microbatches, CPU shard_map; uneven "
              f"tracks padding overhead — see module docstring)")
        rows.append((f"pipeline_exec_{label}", dt * 1e6,
                     f"cuts={'/'.join(map(str, bounds))};stages={stages}"))
    return rows


def main():
    results = _bubble_rows() + _imbalance_rows() + _execution_rows()
    print("\nname,us_per_call,derived")
    for name, us, der in results:
        us_s = f"{us:.1f}" if isinstance(us, float) else us
        print(f"{name},{us_s},{der}")
    return results


if __name__ == "__main__":
    main()
