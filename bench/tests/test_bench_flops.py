"""Operation and byte counts against hand counts at a smoke size, and the
peaks table."""
import pytest

from bench.harness.flops import (
    DecoderShape, decode_step_bytes, decode_step_flops, prefill_flops,
    roofline_seconds)
from bench.harness.peaks import PEAKS, UnknownDevice, peaks_for

# 2 layers, d=48, 3 heads of 16 over 1 KV head, d_ff=128, vocab 128, tied
S = DecoderShape(layers=2, d_model=48, heads=3, kv_heads=1, head_dim=16,
                 d_ff=128, vocab=128, tied_embeddings=True, elem_bytes=2)


def test_param_count_by_hand():
    per_layer = 48 * 48 + 48 * 16 * 2 + 48 * 48 + 3 * 48 * 128 + 2 * 48
    assert S.params() == 128 * 48 + 2 * per_layer + 48


# one layer, one token: q 48x48, k and v 48x16 each, o 48x48, mlp 3 x 48x128
MM_LAYER = 2 * (48 * 48 + 2 * 48 * 16 + 48 * 48 + 3 * 48 * 128)
LOGITS = 2 * 48 * 128


def test_prefill_flops_by_hand():
    n = 10
    # causal: query i sees i+1 keys; QK^T and PV are 2*16 flops per pair per
    # head each; 3 heads, 2 layers
    attn = 2 * sum(3 * 2 * (2 * 16) * (i + 1) for i in range(n))
    assert prefill_flops(S, n) == 2 * n * MM_LAYER + attn + LOGITS


def test_decode_step_flops_and_bytes_by_hand():
    ctx = [5, 9]
    want = sum(2 * MM_LAYER + 2 * 3 * 2 * (2 * 16) * c + LOGITS for c in ctx)
    assert decode_step_flops(S, ctx) == want
    kv = 2 * 2 * 1 * 16 * 2          # K and V, 2 layers, 1 head of 16, bf16
    assert decode_step_bytes(S, ctx) == S.params() * 2 + (5 + 9) * kv


def test_roofline_takes_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    assert roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert roofline_seconds(0, 819e9, p) == pytest.approx(1.0)
    assert roofline_seconds(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_from_hf_config():
    s = DecoderShape.from_hf({
        "num_hidden_layers": 32, "hidden_size": 960, "num_attention_heads": 15,
        "num_key_value_heads": 5, "intermediate_size": 2560, "vocab_size": 49152,
        "tie_word_embeddings": True})
    assert s.head_dim == 64 and s.kv_bytes_per_position() == 2 * 32 * 5 * 64 * 2
    # SmolLM-360M has about 362 million parameters
    assert 3.6e8 < s.params() < 3.65e8


def test_peaks_table_and_unknown_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    assert set(PEAKS) == {"TPU v5 lite"}
