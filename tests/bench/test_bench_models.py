"""Model types are files: the Qwen module gives the harness exactly what
it had before model types moved into ``bench/models`` (numbers copied
from the commit before), and a model type the harness has never seen
is loaded, drawn, mapped and counted from files under a benchmark root
alone."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import flops, program, spec, weights

FIX = Path(__file__).resolve().parent / "fixtures"
REPO = Path(__file__).resolve().parents[2]

GOLDEN = json.loads((FIX / "golden-parent.json").read_text())
SEED = 2**33 + 77
DECODE = ([1], [7, 300], [512] * 64, [4096, 17, 33])
PREFILL = [(0, 512), (512, 76), (1024, 512), (0, 1)]
PAGED = ([1], [7, 300], [512] * 64)
TRAIN_SEQ = (64, 2048, 4096)


def _shapes(tree):
    return {k: ({a: list(b) for a, b in v.items()} if isinstance(v, dict)
                else list(v)) for k, v in tree.items()}


@pytest.mark.parametrize("path", ["bench/configs/qwen3-0.6b.json",
                                  "tests/bench/fixtures/tiny.json"])
def test_qwen_module_gives_the_parents_layout_and_counts(path):
    config = json.loads((REPO / path).read_text())
    want = GOLDEN[config["name"]]
    model = spec.model_module(config)
    assert _shapes(model.shapes(config)) == want["shapes"]
    w = jax.eval_shape(lambda: weights._make(weights.key_for(0), model,
                                             config, jnp.float32))
    tree = jax.tree.map(lambda a: list(a.shape), program.to_program(model, w))
    assert tree == want["program_tree"]
    d = model.dims(config)
    for lens in DECODE:
        assert list(flops.decode_step(d, lens, 2, 2)) == \
            want["decode_step"][str(lens)]
    for o, n in PREFILL:
        assert list(flops.prefill_chunk(d, o, n, 2, 2)) == \
            want["prefill_chunk"][f"{o},{n}"]
    for lens in PAGED:
        assert list(flops.paged_decode_call(d, lens, 2)) == \
            want["paged_decode_call"][str(lens)]
    for s in TRAIN_SEQ:
        assert flops.train_flops_per_token(d, s) == \
            want["train_flops_per_token"][str(s)]
    assert d.weight_bytes(2) == want["weight_bytes"]


def test_qwen_module_draws_the_parents_weights():
    config = json.loads((FIX / "tiny.json").read_text())
    w = weights.make(spec.model_module(config), config, SEED, jnp.float32)

    def flat(tree):
        return {"/".join(str(k.key) for k in path): float(v)
                for path, v in jax.tree_util.tree_leaves_with_path(tree)}

    sums = flat(jax.tree.map(lambda a: jnp.sum(jnp.abs(a)), w))
    assert sums == pytest.approx(flat(GOLDEN["tiny"]["weight_sums"]),
                                 rel=1e-6)


def test_from_program_inverts_to_program():
    config = json.loads((FIX / "tiny.json").read_text())
    model = spec.model_module(config)
    w = weights.make(model, config, SEED, jnp.float32)
    back = program.from_program(model, program.to_program(model, w))
    assert jax.tree.structure(back) == jax.tree.structure(w)
    assert all(bool(jnp.all(a == b)) for a, b in
               zip(jax.tree.leaves(back), jax.tree.leaves(w)))


@pytest.fixture(scope="module")
def mla_root(tmp_path_factory):
    """A benchmark root that gains a model type by two files: its
    module and a configuration."""
    root = tmp_path_factory.mktemp("mlaroot")
    (root / "bench" / "models").mkdir(parents=True)
    (root / "bench" / "configs").mkdir()
    shutil.copy(FIX / "models" / "tiny_mla.py",
                root / "bench" / "models" / "tiny_mla.py")
    shutil.copy(FIX / "tiny-mla.json",
                root / "bench" / "configs" / "tiny-mla.json")
    return root


def test_new_model_type_by_files_alone(mla_root):
    config = json.loads(
        (mla_root / "bench" / "configs" / "tiny-mla.json").read_text())
    with pytest.raises(FileNotFoundError):
        spec.model_module(config)  # the repository's own root lacks it
    model = spec.model_module(config, mla_root)
    cfg = program.model_config(model, config)
    assert cfg.uses_mla and not cfg.moe_experts
    w = weights.make(model, config, SEED, jnp.float32)
    program.check_layout(model, w, cfg)
    params = program.to_program(model, w)
    assert "wdkv" in params["blocks"]["mixer"]

    # the program runs the mapped weights
    from repro.models import transformer as tf

    tokens = jnp.arange(12, dtype=jnp.int32)[None] % config["vocab_size"]
    logits, _ = tf.forward(params, cfg, tokens)
    assert logits.shape == (1, 12, config["vocab_size"])
    assert bool(jnp.all(jnp.isfinite(logits)))

    # its counts differ from a GQA decoder's where the layout does
    d = model.dims(config)
    r, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    assert d.kv_row_bytes(2) == (r + dr) * 2
    f, b = flops.paged_decode_call(d, [5, 9], 2)
    assert f == d.attn_per_key * 14 and b > 14 * d.kv_row_bytes(2)
    f, b = flops.decode_step(d, [5, 9], 2, 2)
    assert b == d.weight_bytes(2) + d.layers * 14 * d.kv_row_bytes(2)
    assert flops.train_flops_per_token(d, 64) > 6 * d.body_params
