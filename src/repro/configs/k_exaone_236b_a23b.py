"""K-EXAONE-236B-A23B [hf:LGAI-EXAONE/K-EXAONE-236B-A23B, config.json].

48 layers of GQA attention (64 query / 8 KV heads x 128) in the
repeating pattern "LLLG": three layers attend a 128-token sliding
window, the fourth the whole context. Layer 0 has a dense gated-SiLU
MLP (``first_k_dense_replace`` 1, width 18,432); layers 1-47 hold 128
routed gated-SiLU experts of width 2,048, 8 per token, and one shared
expert. The router scores every expert with a sigmoid, normalises the
top 8 scores to sum 1 and scales them by 2.5 (``n_group`` =
``topk_group`` = 1: no group restriction). RoPE theta 1e6, RMSNorm eps
1e-5, untied head over 153,600 tokens. The one multi-token-prediction
layer serves self-speculation only and is not modelled.

Not in the registry of ``list_configs``: it is served only through
the paged path, by the benchmark's ``exaone_moe`` module, which cuts
its depth and the experts one chip holds.
"""
from repro.configs.base import MOE, MoEConfig, ModelConfig

WINDOW = 128
PATTERN = "LLLG"

CONFIG = ModelConfig(
    name="k-exaone-236b-a23b",
    family=MOE,
    num_layers=48,
    d_model=6144,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    d_ff=2048,
    vocab_size=153600,
    activation="silu",
    rope_theta=1e6,
    norm_eps=1e-5,
    layer_windows=tuple(WINDOW if PATTERN[i % 4] == "L" else 0
                        for i in range(48)),
    dense_layers=1,
    dense_d_ff=18432,
    moe=MoEConfig(num_experts=128, top_k=8, num_shared_experts=1,
                  expert_d_ff=2048, scoring="sigmoid", routed_scale=2.5),
    source="hf:LGAI-EXAONE/K-EXAONE-236B-A23B",
)
