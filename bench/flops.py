"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of the work a call *needs*, not of what an implementation
happens to do: real tokens only (no pad rows), causal attention over
the keys each query sees, and each byte of weights or cache read once.
So a share of a roofline built on them cannot pass 100% unless the time
leaves out part of the work.  All counts are for the dense GQA decoder
(``model_type`` qwen2/qwen3), in the dtypes the cell runs.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        return cls(config["num_hidden_layers"], config["hidden_size"],
                   config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"],
                   config["intermediate_size"], config["vocab_size"],
                   bool(config["tie_word_embeddings"]))

    @property
    def body_params(self) -> int:
        """Matmul weights of all layers (norms and biases left out)."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.d_ff)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    def weight_bytes(self, itemsize: int) -> int:
        """Bytes a step reads of the weights: every layer and the head
        (the embedding table doubles as the head when tied)."""
        return (self.body_params + self.head_params) * itemsize

    def kv_row_bytes(self, itemsize: int) -> int:
        """Cache bytes of one token in one layer (K and V)."""
        return 2 * self.kv_heads * self.head_dim * itemsize

    def attn_flops(self, keys: int) -> int:
        """One query against ``keys`` keys, all heads, one layer."""
        return 4 * self.heads * self.head_dim * keys


def causal_keys(offset: int, n: int) -> int:
    """Sum over ``n`` queries at positions offset..offset+n-1 of the keys
    each sees (itself and everything before it)."""
    return n * offset + n * (n + 1) // 2


# -- one kernel call (one layer) --------------------------------------------


def paged_decode_call(dims: Dims, kv_lens, kv_itemsize: int,
                      q_itemsize: int = 2) -> tuple[int, int]:
    """The paged decode kernel for one layer: each live sequence's query
    against its ``kv_len`` cached rows (the new one included)."""
    flops = sum(dims.attn_flops(c) for c in kv_lens)
    rows = sum(kv_lens)
    qo = 2 * len(kv_lens) * dims.heads * dims.head_dim * q_itemsize
    return flops, rows * dims.kv_row_bytes(kv_itemsize) + qo


# -- one whole program call --------------------------------------------------


def decode_step(dims: Dims, kv_lens, w_itemsize: int,
                kv_itemsize: int) -> tuple[int, int]:
    """One batched decode step over the live sequences: every weight
    read once, each sequence's cache read once."""
    b = len(kv_lens)
    flops = 2 * b * (dims.body_params + dims.head_params)
    flops += dims.layers * sum(dims.attn_flops(c) for c in kv_lens)
    nbytes = dims.weight_bytes(w_itemsize)
    nbytes += dims.layers * sum(kv_lens) * dims.kv_row_bytes(kv_itemsize)
    return flops, nbytes


def prefill_chunk(dims: Dims, offset: int, n: int, w_itemsize: int,
                  kv_itemsize: int) -> tuple[int, int]:
    """One prefill call of ``n`` real tokens at ``offset``: the layers
    for every token, the head for the last one."""
    flops = 2 * n * dims.body_params + 2 * dims.head_params
    flops += dims.layers * dims.attn_flops(1) * causal_keys(offset, n)
    nbytes = dims.weight_bytes(w_itemsize)
    nbytes += dims.layers * (offset + n) * dims.kv_row_bytes(kv_itemsize)
    return flops, nbytes


def prefill_chunks(prompt_len: int, chunk: int):
    """(offset, real tokens) of each prefill call of one prompt."""
    return [(o, min(chunk, prompt_len - o))
            for o in range(0, prompt_len, chunk)]


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Model operations per trained token, forward and backward
    (3x forward), causal attention averaged over the sequence;
    recomputation does not count."""
    fwd = 2 * (dims.body_params + dims.head_params)
    fwd += dims.layers * dims.attn_flops(1) * (seq_len + 1) / 2
    return 3.0 * fwd
