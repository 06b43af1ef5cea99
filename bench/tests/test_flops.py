"""Operation counts against a hand count, and the peak table."""
import pytest

from bench import flops

CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "vocab_size": 32, "num_hidden_layers": 3}


def test_one_layer_by_hand():
    # q: 8x2x4, k and v: 8x1x4 each, o: 2x4x8 -> 64+32+32+64 = 192 MACs;
    # gate, up, down: 3 x 8 x 16 = 384 MACs; 2 operations per MAC
    assert flops.layer_matmul_flops(CFG) == 2 * (192 + 384)
    # scores and weighted sum against 5 positions: 2 x (2 heads x 4 dims x 5)
    assert flops.attention_flops(CFG, 5) == 4 * 2 * 4 * 5
    assert flops.unembed_flops(CFG) == 2 * 8 * 32


def test_sequence_and_decode_agree():
    # a causal pass over n tokens does the work of n decode steps, less the
    # unembedding of every position but the last
    n = 7
    steps = sum(flops.decode_flops(CFG, i) for i in range(1, n + 1))
    assert flops.sequence_flops(CFG, n) == pytest.approx(
        steps - (n - 1) * flops.unembed_flops(CFG))


def test_peaks_by_device_kind():
    row = flops.peak("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
