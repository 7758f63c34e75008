"""Operations and bytes the served model needs, from shapes alone.

One multiply-add counts as 2 FLOPs. ``ctx`` is the number of keys a
query attends: position + 1 for a causal token. Weights and caches are
``bytes_per`` bytes an element (bf16: 2).
"""
from __future__ import annotations


def linear_flops_per_token(cfg: dict) -> float:
    """Projections and MLP of every layer, per token (no head)."""
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    mats = 2 if not cfg.get("mlp_gate") else 3
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + mats * d * ff
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def head_flops_per_token(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, ctx: int) -> float:
    """Scores and weighted values of one query token over ``ctx`` keys,
    every layer."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return 4.0 * h * hd * ctx * cfg["num_hidden_layers"]


def token_flops(cfg: dict, ctx: int, needs_logits: bool) -> float:
    return (linear_flops_per_token(cfg) + attention_flops(cfg, ctx)
            + (head_flops_per_token(cfg) if needs_logits else 0.0))


def decode_kernel_cost(cfg: dict, ctx: int, bytes_per: int = 2):
    """(FLOPs, bytes) of the paged decode attention kernel for one live
    row at one decode step, every layer: read the row's ``ctx`` keys
    and values of every KV head and its query, write its output. Bytes
    count the live context only, not the pages a grid happens to
    fetch."""
    h, kv, hd, n = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], cfg["num_hidden_layers"])
    flops = 4.0 * h * hd * ctx * n
    byts = (2.0 * ctx * kv * hd + 2.0 * h * hd) * bytes_per * n
    return flops, byts


def roofline_seconds(flops: float, byts: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])
