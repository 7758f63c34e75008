"""Operation and byte counts against hand counts at one shape."""
import pytest

from chipbench import flops as F

CFG = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
       "num_hidden_layers": 3}


def test_linear_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, up 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 128 + 128
    assert F.linear_flops_per_token(CFG) == 2 * per_layer * 3


def test_attention_and_head_by_hand():
    # scores and values: 2 * 2 * heads * head_dim * ctx, every layer
    assert F.attention_flops(CFG, 5) == 4 * 4 * 2 * 5 * 3
    assert F.head_flops_per_token(CFG) == 2 * 8 * 10
    assert F.token_flops(CFG, 5, True) == (F.linear_flops_per_token(CFG)
                                           + 480 + 160)


def test_decode_kernel_cost_counts_live_context_only():
    flops, byts = F.decode_kernel_cost(CFG, 7)
    assert flops == 4 * 4 * 2 * 7 * 3
    # K and V of 7 tokens x 2 kv heads x 2 dims, query and output of
    # 4 heads x 2 dims, bf16, 3 layers
    assert byts == (2 * 7 * 2 * 2 + 2 * 4 * 2) * 2 * 3


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.roofline_seconds(50.0, 1.0, peak) == pytest.approx(0.5)
    assert F.roofline_seconds(50.0, 20.0, peak) == pytest.approx(2.0)
