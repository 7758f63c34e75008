"""A second block for the tests: a dense GQA decoder with a gated SiLU
MLP (down(silu(gate(h)) * up(h))), full causal attention and an untied
head. It is served through the program's paged path like any model and
reaches the harness only through the model-module contract
(``harness.MODEL_API``): its configuration, ``dense-silu-gated.json``
beside it, names it, and nothing under ``chipbench/`` outside this
directory does. Tiny sizes only; no benchmark cell runs it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference as R
from chipbench import weights as W

KERNELS = {"paged_decode": "paged_gqa_decode"}


def program_config(cfgj: dict):
    from repro.configs.base import DENSE, ModelConfig
    return ModelConfig(
        name=cfgj["name"], family=DENSE, num_layers=cfgj["num_hidden_layers"],
        d_model=cfgj["hidden_size"], num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"], head_dim=cfgj["head_dim"],
        d_ff=cfgj["intermediate_size"], vocab_size=cfgj["vocab_size"],
        activation="silu", rope_theta=cfgj["rope_theta"],
        norm_eps=cfgj["norm_eps"], tie_embeddings=False, dtype=cfgj["dtype"])


def _shape(cfg: dict, leaf: str):
    d, h, kv, hd, ff, v = (cfg["hidden_size"], cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"],
                           cfg["intermediate_size"], cfg["vocab_size"])
    return {"ln1": (d,), "ln2": (d,), "final_ln": (d,),
            "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "gate": (d, ff), "up": (d, ff),
            "down": (ff, d), "embed": (v, d), "lm_head": (d, v)}[leaf]


TABLE = W.Table(top=("embed", "final_ln", "lm_head"),
                layer=("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up",
                       "down"),
                norms=("ln1", "ln2", "final_ln"), shape=_shape)
layer_params, top_params = TABLE.layer_params, TABLE.top_params


def program_params(cfg: dict, seed: int):
    dtype = jnp.dtype(cfg["dtype"])

    def make(seed_key):
        s = jax.vmap(lambda i: layer_params(cfg, seed_key, i, dtype))(
            jnp.arange(cfg["num_hidden_layers"]))
        return {
            **{n: top_params(cfg, seed_key, n, dtype) for n in TABLE.top},
            "layers": {
                "ln1": s["ln1"], "ln2": s["ln2"],
                "attn": {k: s[k] for k in ("wq", "wk", "wv", "wo")},
                "mlp": {k: s[k] for k in ("gate", "up", "down")},
            },
        }
    return jax.jit(make)(W.root_key(seed))


def _layer(cfg: dict, w: dict, x, n_real, q8: bool):
    eps = cfg["norm_eps"]
    x = x + R.gqa_attention(R.rms(x, w["ln1"], eps), w, cfg, n_real, q8)
    h = R.rms(x, w["ln2"], eps)
    u = jax.nn.silu(R.mm(h, w["gate"], q8)) * R.mm(h, w["up"], q8)
    return x + R.mm(u, w["down"], q8)


def Forward(cfg: dict, seed: int, int8: bool = False):
    return R.LayerwiseForward(cfg, seed, int8, layer_params, top_params,
                              _layer)


def _linear(cfg: dict) -> float:
    d, h, kv, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["intermediate_size"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def _per_key(cfg: dict) -> float:
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["num_hidden_layers"]


def prefill_flops(cfg: dict, start: int, n: int) -> float:
    return n * _linear(cfg) + _per_key(cfg) * (n * start + n * (n + 1) / 2)


def decode_flops(cfg: dict, pos: int) -> float:
    return (_linear(cfg) + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
            + _per_key(cfg) * (pos + 1))


def decode_kernel_cost(cfg: dict, pos: int):
    kv, hd, n = (cfg["num_key_value_heads"], cfg["head_dim"],
                 cfg["num_hidden_layers"])
    size = jnp.dtype(cfg["dtype"]).itemsize
    byts = (2.0 * (pos + 1) * kv * hd
            + 2.0 * cfg["num_attention_heads"] * hd) * size * n
    return _per_key(cfg) * (pos + 1), byts
