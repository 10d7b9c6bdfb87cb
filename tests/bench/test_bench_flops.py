"""Operation and byte counts against hand counts for one call."""

from bench import flops, peaks
from bench.models import qwen3

D = qwen3.dims({"num_hidden_layers": 2, "hidden_size": 8,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 4, "intermediate_size": 16, "vocab_size": 32,
                "tie_word_embeddings": True})


def test_param_counts():
    # per layer: q 8x16, k 8x8, v 8x8, o 16x8 = 128+64+64+128 = 384;
    # mlp 3 x 8x16 = 384
    assert D.body_params == 2 * (384 + 384)
    assert D.head_params == 8 * 32


def test_paged_decode_call_by_hand():
    f, b = flops.paged_decode_call(D, [3, 5], kv_itemsize=2)
    # QK and PV: 2 x 2 flops x heads x head_dim per key
    assert f == 4 * 4 * 4 * (3 + 5)
    # K and V rows: 8 keys x 2 kv heads x 4 x 2 (K,V) x 2 B; q and out:
    # 2 x 2 seqs x 4 heads x 4 x 2 B
    assert b == 8 * 2 * 4 * 2 * 2 + 2 * 2 * 4 * 4 * 2


def test_causal_keys_by_hand():
    # queries at 2, 3, 4 see 3 + 4 + 5 = 12 keys
    assert flops.causal_keys(2, 3) == 12
    assert flops.causal_keys(0, 1) == 1


def test_decode_step_and_prefill_chunk_by_hand():
    f, b = flops.decode_step(D, [3, 5], w_itemsize=2, kv_itemsize=2)
    assert f == 2 * 2 * (768 * 2 + 256) + 2 * 4 * 4 * 4 * 8
    assert b == (1536 + 256) * 2 + 2 * 8 * (2 * 2 * 4 * 2)
    f, b = flops.prefill_chunk(D, 0, 4, 2, 2)
    assert f == 2 * 4 * 1536 + 2 * 256 + 2 * 64 * flops.causal_keys(0, 4)
    assert flops.prefill_chunks(1100, 512) == [(0, 512), (512, 512),
                                              (1024, 76)]


def test_train_flops_per_token_by_hand():
    per = flops.train_flops_per_token(D, seq_len=7)
    assert per == 3 * (2 * (1536 + 256) + 2 * 64 * 4)


def test_roofline_takes_the_larger_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.roofline_s(1000, 50, p) == 10.0
    assert peaks.roofline_s(100, 50, p) == 5.0


def test_unknown_device_is_an_error():
    import pytest

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9000")
