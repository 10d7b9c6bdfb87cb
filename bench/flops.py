"""Operations and bytes the algorithm needs, computed from shapes.

Counts are of the work a call *needs*, not of what an implementation
happens to do: real tokens only (no pad rows), causal attention over
the keys each query sees, and each byte of weights or cache read once.
So a share of a roofline built on them cannot pass 100% unless the time
leaves out part of the work.  The sizes come from :class:`Dims`, which
each model type's module (``bench/models/<model_type>.py``) fills from
its configuration; the counts here name no model.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """What the counts read of a model.  ``flop_params`` and
    ``read_params`` differ where a token passes through fewer weights
    than a step reads, as with experts."""
    layers: int
    flop_params: int  # matmul weights of all layers one token passes through
    read_params: int  # weights of all layers a step reads (norms, biases out)
    head_params: int  # the output head (the embedding table when tied)
    kv_row_elems: int  # cache elements of one token in one layer
    attn_per_key: int  # operations of one query against one key, one layer
    q_elems: int  # elements of one token's query (and of its output)

    @property
    def body_params(self) -> int:
        return self.flop_params

    def weight_bytes(self, itemsize: int) -> int:
        """Bytes a step reads of the weights: every layer and the head
        (the embedding table doubles as the head when tied)."""
        return (self.read_params + self.head_params) * itemsize

    def kv_row_bytes(self, itemsize: int) -> int:
        """Cache bytes of one token in one layer."""
        return self.kv_row_elems * itemsize

    def attn_flops(self, keys: int) -> int:
        """One query against ``keys`` keys, all heads, one layer."""
        return self.attn_per_key * keys


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
    qo = 2 * len(kv_lens) * dims.q_elems * q_itemsize
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
