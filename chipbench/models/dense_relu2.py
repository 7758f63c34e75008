"""The dense GQA decoder with an ungated squared-ReLU MLP (Nemotron-4,
Minitron): every layer the same, full causal attention, an untied head.

A configuration file names this module with ``"model": "dense_relu2"``;
the harness finds it by that name (``harness.load_model``) and uses
nothing of the block but what is here:

* ``program_config`` / ``program_params``: the program's ModelConfig
  and the whole model in its parameter tree (``repro.models.model``:
  layers stacked on a leading axis, projections as (in, out)
  matrices), made on the device by one jitted call from the seed.
* ``layer_params`` / ``top_params``: one layer's leaves or one
  top-level leaf, drawn from the same keys (``weights.Table``), so the
  reference regenerates a layer at a time.
* ``Forward``: the plain reference in float32 at ``highest``: RMSNorm
  with a scale, rotary embedding on each head's two halves, causal GQA
  attention, the MLP, a final RMSNorm and the head
  (``reference.LayerwiseForward`` around this module's layer).
  ``int8=True`` is the control.
* ``prefill_flops``, ``decode_flops``, ``decode_kernel_cost``: the
  counts that ``step.mfu`` and ``paged_decode_roofline`` divide by.
* ``KERNELS``: kernel key -> the op name the device trace gives it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import reference as R
from chipbench import weights as W

# the paged decode kernel (its ops read "%paged_gqa_decode.<n> = ...")
KERNELS = {"paged_decode": "paged_gqa_decode"}


def _check(cfgj: dict) -> None:
    if cfgj["hidden_act"] != "relu2" or cfgj["mlp_gate"]:
        raise ValueError(
            f"dense_relu2 is the ungated squared-ReLU block; configuration "
            f"{cfgj['name']!r} has hidden_act {cfgj['hidden_act']!r}, "
            f"mlp_gate {cfgj['mlp_gate']!r}")


def program_config(cfgj: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import get_config
    _check(cfgj)
    base = get_config(cfgj["arch"])
    return dataclasses.replace(
        base, num_layers=cfgj["num_hidden_layers"],
        d_model=cfgj["hidden_size"], num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"],
        head_dim=cfgj["head_dim"], d_ff=cfgj["intermediate_size"],
        vocab_size=cfgj["vocab_size"], activation="squared_relu",
        rope_theta=cfgj["rope_theta"], norm_eps=cfgj["norm_eps"],
        tie_embeddings=cfgj["tie_word_embeddings"], dtype=cfgj["dtype"])


# ------------------------------------------------------------- weights
def _shape(cfg: dict, leaf: str):
    d, h, kv, hd, ff, v = (cfg["hidden_size"], cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"],
                           cfg["intermediate_size"], cfg["vocab_size"])
    return {"ln1": (d,), "ln2": (d,), "final_ln": (d,),
            "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "up": (d, ff), "down": (ff, d),
            "embed": (v, d), "lm_head": (d, v)}[leaf]


# a leaf's key is its index in top + layer: the order is part of the
# weights
TABLE = W.Table(top=("embed", "final_ln", "lm_head"),
                layer=("ln1", "wq", "wk", "wv", "wo", "ln2", "up", "down"),
                norms=("ln1", "ln2", "final_ln"), shape=_shape)
layer_params, top_params = TABLE.layer_params, TABLE.top_params


def program_params(cfg: dict, seed: int):
    """The whole model in the program's parameter tree, made on the
    device by one jitted call, in the served dtype."""
    dtype = jnp.dtype(cfg["dtype"])

    def make(seed_key):
        stacked = jax.vmap(lambda i: layer_params(cfg, seed_key, i, dtype))(
            jnp.arange(cfg["num_hidden_layers"]))
        return {
            **{n: top_params(cfg, seed_key, n, dtype) for n in TABLE.top},
            "layers": {
                "ln1": stacked["ln1"], "ln2": stacked["ln2"],
                "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
                "mlp": {"up": stacked["up"], "down": stacked["down"]},
            },
        }
    return jax.jit(make)(W.root_key(seed))


# ----------------------------------------------------------- reference
def _layer(cfg: dict, w: dict, x, n_real, q8: bool):
    eps = cfg["norm_eps"]
    x = x + R.gqa_attention(R.rms(x, w["ln1"], eps), w, cfg, n_real, q8)
    h = R.rms(x, w["ln2"], eps)
    u = jnp.square(jax.nn.relu(R.mm(h, w["up"], q8)))
    return x + R.mm(u, w["down"], q8)


def Forward(cfg: dict, seed: int, int8: bool = False):
    """Reference logits for one configuration and seed (``.logits``)."""
    _check(cfg)
    return R.LayerwiseForward(cfg, seed, int8, layer_params, top_params,
                              _layer)


# -------------------------------------------------------------- counts
def linear_flops_per_token(cfg: dict) -> float:
    """Projections and MLP of every layer, per token (no head)."""
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * d * ff
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def head_flops_per_token(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: dict, ctx: int) -> float:
    """Scores and weighted values of one query token over ``ctx`` keys,
    every layer."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return 4.0 * h * hd * ctx * cfg["num_hidden_layers"]


def prefill_flops(cfg: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` prompt tokens at positions start ..
    start+n-1: their projections and MLP, and each one's attention over
    every key up to its own (no head)."""
    keys = n * start + n * (n + 1) / 2
    return n * linear_flops_per_token(cfg) + attention_flops(cfg, 1) * keys


def decode_flops(cfg: dict, pos: int) -> float:
    """One decoded token at position ``pos``: projections, MLP,
    attention over pos + 1 keys, and the head."""
    return (linear_flops_per_token(cfg) + head_flops_per_token(cfg)
            + attention_flops(cfg, 1) * (pos + 1))


def decode_kernel_cost(cfg: dict, pos: int):
    """(FLOPs, bytes) of the paged decode attention kernel for one live
    row decoding at position ``pos``, every layer: read the row's pos + 1
    keys and values of every KV head and its query, write its output,
    in the served dtype. Bytes count the live context only, not the
    pages a grid happens to fetch."""
    h, kv, hd, n = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], cfg["num_hidden_layers"])
    ctx = pos + 1
    bytes_per = jnp.dtype(cfg["dtype"]).itemsize
    flops = 4.0 * h * hd * ctx * n
    byts = (2.0 * ctx * kv * hd + 2.0 * h * hd) * bytes_per * n
    return flops, byts
