"""Distribution layer: sharding specs, pipeline runtime, placement,
autotune, launchers."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.core.autotune import tune
from repro.core.cost_model import ULTRASCALE
from repro.core.graph import resnet18_graph
from repro.core.placement import to_placement
from repro.core.strategies import make_plan
from repro.dist.sharding import fix_spec, param_specs
from repro.ft.elastic import make_mesh_for
from repro.launch import specs as sm


class TestSpecs:
    def test_param_specs_cover_all_leaves(self):
        cfg = get_config("deepseek_v2_236b")
        mesh = make_mesh_for(jax.devices())
        shapes = sm.param_shapes(cfg)
        specs = param_specs(shapes, mesh)
        n_leaves = len(jax.tree.leaves(shapes))
        n_specs = len(jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves

    def test_scatter_gather_replicates_params(self):
        cfg = get_config("qwen3_0p6b")
        mesh = make_mesh_for(jax.devices())
        shapes = sm.param_shapes(cfg)
        specs = param_specs(shapes, mesh, "scatter_gather")
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            assert all(ax is None for ax in s), s

    def test_fix_spec_always_legal(self):
        """Property: after fix_spec, every sharded dim divides exactly.

        fix_spec only consults mesh.shape / axis_names, so a duck-typed
        mesh with *non-trivial* axis sizes makes the property
        falsifiable (on a real 1-device mesh every axis has size 1 and
        any implementation passes)."""
        pytest.importorskip("hypothesis")
        from types import SimpleNamespace

        import numpy as np
        from hypothesis import given, settings, strategies as st

        from repro.dist.sharding import _axis_size

        @settings(max_examples=50, deadline=None)
        @given(
            dims=st.lists(st.integers(1, 64), min_size=1, max_size=4),
            data=st.sampled_from([1, 2, 3, 4, 8]),
            model=st.sampled_from([1, 2, 4, 5, 16]),
            seed=st.integers(0, 100),
        )
        def check(dims, data, model, seed):
            mesh = SimpleNamespace(shape={"data": data, "model": model},
                                   axis_names=("data", "model"))
            rng = np.random.default_rng(seed)
            entries = [None, "data", "model", ("data", "model")]
            spec = tuple(
                entries[rng.integers(len(entries))] for _ in dims
            )
            # de-dup axes (a PartitionSpec can use each axis once)
            seen = set()
            deduped = []
            for s in spec:
                axes = s if isinstance(s, tuple) else (s,)
                if s is None or not seen.isdisjoint(axes):
                    deduped.append(None)
                else:
                    seen.update(axes)
                    deduped.append(s)
            fixed = fix_spec(tuple(deduped), tuple(dims), mesh)
            assert len(fixed) == len(dims)
            for d, s in zip(dims, fixed):
                assert d % _axis_size(mesh, s) == 0

        check()


def _run_pipeline_subprocess(code: str, marker: str, timeout: int = 560):
    """Run a 4-fake-CPU-device pipeline check in a subprocess (the
    dry-run-only device override must not leak into this process)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": os.path.join(repo, "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/tmp"),
             "JAX_PLATFORMS": "cpu"},
        cwd=repo, timeout=timeout,
    )
    assert marker in r.stdout, r.stdout + r.stderr


class TestBubbleOracle:
    def test_forward_closed_form(self):
        from repro.dist.pipeline import pipeline_bubble_counts

        for s, m in [(1, 4), (2, 4), (4, 8), (8, 3)]:
            rounds, busy, idle = pipeline_bubble_counts(s, m, "forward")
            assert rounds == m + s - 1
            assert busy == s * m
            assert idle == s * (s - 1)

    def test_gpipe_closed_form(self):
        from repro.dist.pipeline import pipeline_bubble_counts

        for s, m in [(2, 4), (4, 8), (4, 2)]:
            rounds, busy, idle = pipeline_bubble_counts(s, m, "gpipe")
            assert rounds == 2 * (m + s - 1)
            assert busy == 2 * s * m  # fw and bw phases never overlap
            assert idle == 2 * s * (s - 1)

    def test_1f1b_fewer_idle_rounds_than_gpipe(self):
        from repro.dist.pipeline import pipeline_bubble_counts

        for s, m in [(2, 2), (2, 8), (4, 4), (4, 16), (8, 32)]:
            g_rounds, g_busy, g_idle = pipeline_bubble_counts(s, m, "gpipe")
            f_rounds, f_busy, f_idle = pipeline_bubble_counts(s, m, "1f1b")
            # gpipe's fw and bw phases never share a round; 1f1b fuses
            # them in steady state, so it spans strictly fewer rounds
            assert g_busy == 2 * s * m
            assert g_idle + g_busy == s * g_rounds
            assert f_idle + f_busy == s * f_rounds
            if s > 1:
                assert f_idle < g_idle
                assert f_rounds < g_rounds
            if m >= 2 * (s - 1):  # steady state: drain/fill overlap
                assert f_idle == s * (s - 1) == g_idle // 2

    def test_1f1b_rounds_match_lag_formula(self):
        from repro.dist.pipeline import pipeline_bubble_counts

        rounds, _, _ = pipeline_bubble_counts(4, 8, "1f1b")
        assert rounds == 8 + 2 * (4 - 1)


class TestPipeline:
    def test_pipeline_matches_scan(self):
        """GPipe shard_map pipeline == plain stacked scan, bitwise-ish.
        Runs in a subprocess with 4 fake CPU devices (the dry-run-only
        device override must not leak into this test process)."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.dist.pipeline import make_pipeline_forward
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
cfg = get_config("qwen3_0p6b").scaled_down(num_layers=4, d_model=64, vocab=256)
mesh = make_mesh((2, 2), ("data", "model"))
params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
want, _ = tf.forward(params, cfg, tokens)
with mesh:
    fwd = make_pipeline_forward(cfg, mesh, num_microbatches=2)
    got = jax.jit(fwd)(params, tokens)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-3)
print("PIPELINE_OK")
"""
        _run_pipeline_subprocess(code, "PIPELINE_OK")

    def test_uneven_plan_executes_and_matches_scan(self):
        """The acceptance loop: a skewed cost vector (straggling node)
        -> rebalance re-cuts the plan -> to_placement surfaces uneven
        layer boundaries -> pad_pipeline_params + make_pipeline_forward
        execute them -> output matches the stacked scan.  Also covers
        the num_microbatches < stages drained-queue regression (m=2 on
        a 4-stage pipe)."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.graph import config_graph
from repro.core.placement import to_placement
from repro.core.scheduler import rebalance
from repro.core.strategies import make_plan
from repro.dist.pipeline import make_pipeline_forward, pad_pipeline_params
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf

cfg = get_config("qwen3_0p6b").scaled_down(num_layers=8, d_model=64, vocab=256)
g = config_graph(cfg, seq_len=16)
plan = rebalance(g, make_plan(g, "pipeline", 4),
                 {0: 0.25, 1: 1.0, 2: 1.0, 3: 1.0})  # stage 0 straggles
mesh = make_mesh((1, 4), ("data", "model"))
placement = to_placement(plan, mesh, num_microbatches=4, graph=g)
depths = np.diff(placement.layer_boundaries)
assert depths[0] < depths.max(), placement.layer_boundaries  # uneven cut
params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
want, _ = tf.forward(params, cfg, tokens)
padded = pad_pipeline_params(params, cfg, placement.layer_boundaries)
with mesh:
    fwd = make_pipeline_forward(cfg, mesh, placement.num_microbatches,
                                boundaries=placement.layer_boundaries)
    got = jax.jit(fwd)(padded, tokens)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-3)
print("UNEVEN_OK")
# regression: fewer microbatches than stages (m=2 < S=4) must still
# drain every microbatch exactly once
with mesh:
    fwd2 = make_pipeline_forward(cfg, mesh, 2,
                                 boundaries=placement.layer_boundaries)
    got2 = jax.jit(fwd2)(padded, tokens)
np.testing.assert_allclose(np.asarray(got2), np.asarray(want), atol=2e-4, rtol=1e-3)
print("M_LT_S_OK")
"""
        _run_pipeline_subprocess(code, "M_LT_S_OK")

    def test_pipelined_train_schedules(self):
        """1F1B and GPipe produce bitwise-identical loss AND grads (one
        fused round body, different lag), and both match the plain
        value_and_grad loss to float tolerance — on a 2x2 mesh so the
        data-axis pmean reductions are exercised too."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.dist.pipeline import make_pipeline_loss_and_grad, pad_pipeline_params
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.train.step import make_loss_fn

cfg = get_config("qwen3_0p6b").scaled_down(num_layers=4, d_model=64, vocab=256)
mesh = make_mesh((2, 2), ("data", "model"))
bounds = (0, 1, 4)  # uneven: stage 0 one layer, stage 1 three
params = tf.init(jax.random.PRNGKey(0), cfg, jnp.float32)
padded = pad_pipeline_params(params, cfg, bounds)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (8, 17), 0, cfg.vocab)}
outs = {}
with mesh:
    for sched in ("gpipe", "1f1b"):
        lg = make_pipeline_loss_and_grad(cfg, mesh, num_microbatches=4,
                                         boundaries=bounds, schedule=sched)
        outs[sched] = jax.jit(lg)(padded, batch)
(lg_loss, _), lg_grads = outs["gpipe"]
(f_loss, _), f_grads = outs["1f1b"]
assert np.array_equal(np.asarray(lg_loss), np.asarray(f_loss))
for a, b in zip(jax.tree.leaves(lg_grads), jax.tree.leaves(f_grads)):
    assert np.array_equal(np.asarray(a), np.asarray(b))
print("BITWISE_OK")
# reference: plain (unpipelined) loss + autodiff grads on raw params
(ref_loss, _), ref_grads = jax.value_and_grad(
    make_loss_fn(cfg, remat=False), has_aux=True)(params, batch)
np.testing.assert_allclose(float(f_loss), float(ref_loss), atol=2e-4, rtol=1e-4)
rows = [0, 3, 4, 5]  # unpad: depths (1,3), max depth 3 -> stage0 row 0
                     # (rows 1-2 padding), stage1 rows 3..5
for key in ("embed", "final_norm"):
    for a, b in zip(jax.tree.leaves(f_grads[key]), jax.tree.leaves(ref_grads[key])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b, np.float32),
                                   atol=2e-3, rtol=1e-2)
gb = jax.tree.map(lambda a: np.asarray(a)[rows], f_grads["blocks"])
for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(ref_grads["blocks"])):
    np.testing.assert_allclose(a, np.asarray(b, np.float32), atol=2e-3, rtol=1e-2)
print("TRAIN_MATCH_OK")
# regression: microbatch dim NOT divisible by the data axes (fix_spec
# drops them, x_mb replicates) — the dX normalizer must follow the
# EFFECTIVE shard count or embedding grads come out scaled by 1/ndp
mesh4 = make_mesh((4, 1), ("data", "model"))
b4 = {"tokens": batch["tokens"][:4]}
with mesh4:
    lg4 = make_pipeline_loss_and_grad(cfg, mesh4, num_microbatches=4)
    (l4, _), g4 = jax.jit(lg4)(params, b4)
(rl4, _), rg4 = jax.value_and_grad(
    make_loss_fn(cfg, remat=False), has_aux=True)(params, b4)
np.testing.assert_allclose(float(l4), float(rl4), atol=2e-4, rtol=1e-4)
np.testing.assert_allclose(np.asarray(g4["embed"]["table"]),
                           np.asarray(rg4["embed"]["table"], np.float32),
                           atol=2e-3, rtol=1e-2)
print("NONDIV_DP_OK")
"""
        _run_pipeline_subprocess(code, "NONDIV_DP_OK")

    def test_moe_capacity_and_hybrid_groups(self):
        """Satellites: pipelined MoE sizes router capacity from the
        GLOBAL batch (exact match to the full forward below capacity,
        with the build-time divergence warning), and hybrid attn_every
        stacks pipeline at group boundaries — including uneven group
        cuts."""
        code = r"""
import os, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.dist.pipeline import make_pipeline_forward, pad_pipeline_params
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf

# capacity_factor = experts/top_k makes the global cap provably
# dropless, so the full-batch run is below capacity by construction
mcfg = get_config("mixtral_8x22b").scaled_down(
    num_layers=4, d_model=64, vocab=256, moe_capacity_factor=2.0)
mesh = make_mesh((1, 4), ("data", "model"))
params = tf.init(jax.random.PRNGKey(0), mcfg, jnp.float32)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, mcfg.vocab)
want, _ = tf.forward(params, mcfg, tokens)
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    with mesh:
        fwd = make_pipeline_forward(mcfg, mesh, 4)
    assert any("capacity" in str(x.message) for x in w), "missing MoE warning"
with mesh:
    got = jax.jit(fwd)(params, tokens)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-3)
print("MOE_CAP_OK")

hcfg = get_config("zamba2_2p7b").scaled_down(num_layers=8, attn_every=2,
                                             d_model=64, vocab=256)
hparams = tf.init(jax.random.PRNGKey(0), hcfg, jnp.float32)
htok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, hcfg.vocab)
hwant, _ = tf.forward(hparams, hcfg, htok)
mesh2 = make_mesh((2, 2), ("data", "model"))
hb = (0, 1, 4)  # uneven GROUP cuts: 1 group vs 3 groups
hp = pad_pipeline_params(hparams, hcfg, hb)
with mesh2:
    hfwd = make_pipeline_forward(hcfg, mesh2, 2, boundaries=hb)
    hgot = jax.jit(hfwd)(hp, htok)
np.testing.assert_allclose(np.asarray(hgot), np.asarray(hwant), atol=2e-4, rtol=1e-3)
print("HYBRID_OK")
"""
        _run_pipeline_subprocess(code, "HYBRID_OK")


    def test_flash_kernel_per_shard_matches_jnp(self):
        """Under a 2x2 mesh the Pallas flash kernel runs per shard
        (shard_map over batch and heads), forward and backward, and
        matches the jnp reference."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import layers
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (4, 64, 4, 32))
k = jax.random.normal(ks[1], (4, 64, 2, 32))
v = jax.random.normal(ks[2], (4, 64, 2, 32))

def loss(q, k, v):
    return jnp.sum(layers.flash_attend(q, k, v, block_q=32, block_k=32) ** 2)

want = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
layers.set_attention_impl("pallas")
with make_mesh((2, 2), ("data", "model")):
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert "shard_map" in str(jax.make_jaxpr(loss)(q, k, v))
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
print("PER_SHARD_OK")
"""
        _run_pipeline_subprocess(code, "PER_SHARD_OK")


class TestPlacement:
    @pytest.mark.parametrize("strategy", ["scatter_gather", "ai_core_assignment", "fused", "pipeline"])
    def test_placement_roundtrip(self, strategy):
        g = resnet18_graph()
        plan = make_plan(g, strategy, 4)
        mesh = make_mesh_for(jax.devices())
        p = to_placement(plan, mesh)
        assert p.strategy == strategy
        if strategy == "pipeline":
            assert p.pipeline_stages == mesh.shape["model"]

    def test_pipeline_plan_boundaries_without_graph(self):
        """A bare to_placement(plan, mesh) call must not silently drop a
        rebalanced plan's uneven cuts: the layer count is recovered from
        the plan's own op names."""
        from types import SimpleNamespace

        from repro.core.graph import transformer_graph
        from repro.core.scheduler import rebalance

        tg = transformer_graph(
            "t", num_layers=8, d_model=64, num_heads=4, kv_heads=2,
            d_ff=128, vocab=1000, seq_len=128,
        )
        plan = rebalance(tg, make_plan(tg, "pipeline", 4),
                         {0: 0.25, 1: 1.0, 2: 1.0, 3: 1.0})
        mesh = SimpleNamespace(shape={"data": 1, "model": 4})
        p = to_placement(plan, mesh)
        assert p.layer_boundaries is not None
        assert p.layer_boundaries[0] == 0 and p.layer_boundaries[-1] == 8
        depths = np.diff(p.layer_boundaries)
        assert depths[0] < depths.max()  # straggler cut survived

    @pytest.mark.parametrize("mesh_kind", ["real_1dev", "fake_2x4"])
    @pytest.mark.parametrize("strategy", ["scatter_gather", "ai_core_assignment", "fused", "pipeline"])
    def test_placement_param_specs_legal(self, strategy, mesh_kind):
        """Planner -> runtime bridge: Placement.param_specs emits one
        spec per param leaf, and every spec is a fix_spec fixpoint (all
        sharded dims divide their mesh axes).  The fake 2x4 mesh (the
        spec engine only reads shape/axis_names) makes divisibility
        non-trivial; the real 1-device mesh checks the live path."""
        from types import SimpleNamespace

        from repro.dist.sharding import _axis_size

        g = resnet18_graph()
        plan = make_plan(g, strategy, 4)
        mesh = make_mesh_for(jax.devices())
        placement = to_placement(plan, mesh)
        if mesh_kind == "fake_2x4":
            mesh = SimpleNamespace(shape={"data": 2, "model": 4},
                                   axis_names=("data", "model"))

        cfg = get_config("qwen3_0p6b").scaled_down()
        shapes = sm.param_shapes(cfg)
        specs = placement.param_specs(shapes, mesh)

        is_p = lambda x: isinstance(x, P)
        shape_leaves = jax.tree.leaves(shapes)
        spec_leaves = jax.tree.leaves(specs, is_leaf=is_p)
        assert len(spec_leaves) == len(shape_leaves)
        for shape_leaf, spec in zip(shape_leaves, spec_leaves):
            shp = shape_leaf.shape
            padded = tuple(spec) + (None,) * (len(shp) - len(spec))
            for dim, entry in zip(shp, padded):
                assert dim % _axis_size(mesh, entry) == 0, (shp, spec)
            # fix_spec is idempotent on what param_specs emits
            assert fix_spec(padded, shp, mesh) == padded


class TestAutotune:
    def test_reproduces_paper_reconfig_direction(self):
        """The tuner independently rediscovers §IV: a bigger block with
        bigger buffers beats the Table-I baseline despite a lower clock."""
        g = resnet18_graph()
        res = tune(g, ULTRASCALE)
        assert res.speedup > 1.2
        assert res.best.block >= 32

    def test_baseline_in_table(self):
        g = resnet18_graph()
        res = tune(g, ULTRASCALE)
        assert len(res.table) == 16


def test_train_launcher_smoke():
    from repro.launch.train import main

    main(["--arch", "qwen3_0p6b", "--smoke", "--steps", "4",
          "--seq", "32", "--batch", "2"])
