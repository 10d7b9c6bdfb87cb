"""A model type the harness's own files do not know: a decoder with
DeepSeek-V2's latent attention (MLA, no q-LoRA) and a dense SwiGLU FFN,
mapped onto the program's MLA attention.  Its layer and KV-row layout
differ from a GQA decoder's: one cached latent of ``kv_lora_rank`` plus
one shared rope key per token and layer."""

from __future__ import annotations

from bench.flops import Dims

LEAVES = {
    "embed": ("embed", "table"),
    "final_norm": ("final_norm", "scale"),
    "lm_head": ("lm_head", "w"),
    "layers/norm1": ("blocks", "norm1", "scale"),
    "layers/wq": ("blocks", "mixer", "wq", "w"),
    "layers/wdkv": ("blocks", "mixer", "wdkv", "w"),
    "layers/kv_norm": ("blocks", "mixer", "ckv_norm", "scale"),
    "layers/wuk": ("blocks", "mixer", "wuk", "w"),
    "layers/wuv": ("blocks", "mixer", "wuv", "w"),
    "layers/wo": ("blocks", "mixer", "wo", "w"),
    "layers/norm2": ("blocks", "norm2", "scale"),
    "layers/w_gate": ("blocks", "ffn", "w_gate", "w"),
    "layers/w_up": ("blocks", "ffn", "w_up", "w"),
    "layers/w_down": ("blocks", "ffn", "w_down", "w"),
}


def program_config(config: dict):
    from repro.configs.base import ModelConfig

    h = config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=h, kv_heads=h,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        kv_lora_rank=config["kv_lora_rank"],
        rope_head_dim=config["qk_rope_head_dim"],
        mla_head_dim=config["qk_nope_head_dim"],
        mla_v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        max_seq_len=int(config["max_position_embeddings"]))


def _sizes(config):
    return (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["kv_lora_rank"],
            config["qk_rope_head_dim"], config["qk_nope_head_dim"],
            config["v_head_dim"], config["intermediate_size"])


def shapes(config: dict) -> dict:
    L, D, h, r, dr, dn, dv, ff = _sizes(config)
    layers = {
        "norm1": (L, D), "wq": (L, D, h * (dn + dr)),
        "wdkv": (L, D, r + dr), "kv_norm": (L, r),
        "wuk": (L, r, h * dn), "wuv": (L, r, h * dv), "wo": (L, h * dv, D),
        "norm2": (L, D),
        "w_gate": (L, D, ff), "w_up": (L, D, ff), "w_down": (L, ff, D),
    }
    out = {"embed": (config["vocab_size"], D), "final_norm": (D,),
           "layers": layers}
    if not config["tie_word_embeddings"]:
        out["lm_head"] = (D, config["vocab_size"])
    return out


def draw(name: str) -> str:
    base = name.split("/")[-1]
    if base.startswith("norm") or base.endswith("_norm"):
        return "scale"
    return "embedding" if base == "embed" else "matrix"


def dims(config: dict) -> Dims:
    L, D, h, r, dr, dn, dv, ff = _sizes(config)
    layer = (D * h * (dn + dr) + D * (r + dr) + r * h * (dn + dv)
             + h * dv * D + 3 * D * ff)
    return Dims(layers=L, flop_params=L * layer, read_params=L * layer,
                head_params=D * config["vocab_size"],
                kv_row_elems=r + dr,
                attn_per_key=2 * h * (dn + dr) + 2 * h * dv,
                q_elems=h * (dn + dr))
