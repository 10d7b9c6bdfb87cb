"""The dense GQA decoders of hf ``Qwen3ForCausalLM`` and
``Qwen2ForCausalLM`` as the harness sees them: the program's
configuration, the reference layout's weight shapes and how each weight
is drawn, the map from that layout to the program's parameter tree, and
the sizes the counts of ``bench/flops.py`` read.

Qwen3 puts an RMSNorm on every query and key head (``qk_norm``); Qwen2
adds a bias to q, k and v (``attention_bias``).  Neither config.json of
Qwen2 names ``head_dim``: it is ``hidden_size / num_attention_heads``.
"""

from __future__ import annotations

from bench.flops import Dims

#: reference-layout name ("group/leaf" inside a stacked group) -> path
#: in the program's parameter tree
LEAVES = {
    "embed": ("embed", "table"),
    "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    "layers/norm1": ("blocks", "norm1", "scale"),
    "layers/wq": ("blocks", "mixer", "wq", "w"),
    "layers/wk": ("blocks", "mixer", "wk", "w"),
    "layers/wv": ("blocks", "mixer", "wv", "w"),
    "layers/wo": ("blocks", "mixer", "wo", "w"),
    "layers/bq": ("blocks", "mixer", "wq", "b"),
    "layers/bk": ("blocks", "mixer", "wk", "b"),
    "layers/bv": ("blocks", "mixer", "wv", "b"),
    "layers/q_norm": ("blocks", "mixer", "q_norm", "scale"),
    "layers/k_norm": ("blocks", "mixer", "k_norm", "scale"),
    "layers/norm2": ("blocks", "norm2", "scale"),
    "layers/w_gate": ("blocks", "ffn", "w_gate", "w"),
    "layers/w_up": ("blocks", "ffn", "w_up", "w"),
    "layers/w_down": ("blocks", "ffn", "w_down", "w"),
}


def head_dim(config: dict) -> int:
    return int(config.get("head_dim")
               or config["hidden_size"] // config["num_attention_heads"])


def program_config(config: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head_dim=head_dim(config),
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        qkv_bias=bool(config.get("attention_bias", False)),
        qk_norm=bool(config.get("qk_norm", False)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        max_seq_len=int(config["max_position_embeddings"]),
    )


def shapes(config: dict) -> dict:
    """Weight shapes in the reference layout; ``layers`` is the stacked
    group of every decoder layer."""
    L, D, hd = (config["num_hidden_layers"], config["hidden_size"],
                head_dim(config))
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    ff = config["intermediate_size"]
    layers = {
        "norm1": (L, D), "wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv),
        "wo": (L, q, D), "norm2": (L, D),
        "w_gate": (L, D, ff), "w_up": (L, D, ff), "w_down": (L, ff, D),
    }
    if config.get("qk_norm"):
        layers.update(q_norm=(L, hd), k_norm=(L, hd))
    if config.get("attention_bias"):
        layers.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    out = {"embed": (config["vocab_size"], D), "final_norm": (D,),
           "layers": layers}
    if not config["tie_word_embeddings"]:
        out["lm_head"] = (D, config["vocab_size"])
    return out


def draw(name: str) -> str:
    """How ``bench/weights.py`` draws the weight ``name``."""
    base = name.split("/")[-1]
    if base.startswith("norm") or base.endswith("_norm"):
        return "scale"
    if base == "embed":
        return "embedding"
    if base in ("bq", "bk", "bv"):
        return "bias"
    return "matrix"


def dims(config: dict) -> Dims:
    """Every weight a token passes through is read once a step, so the
    matmul parameters that set FLOPs and bytes are the same; K and V of
    every KV head are cached; each head's query meets each key twice
    (QK and PV) in ``head_dim`` multiply-adds."""
    L, D, hd = (config["num_hidden_layers"], config["hidden_size"],
                head_dim(config))
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    body = L * (D * h * hd * 2 + D * kvh * hd * 2
                + 3 * D * config["intermediate_size"])
    return Dims(layers=L, flop_params=body, read_params=body,
                head_params=D * config["vocab_size"],
                kv_row_elems=2 * kvh * hd, attn_per_key=4 * h * hd,
                q_elems=h * hd)
