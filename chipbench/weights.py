"""Random weights from the seed, made by the benchmark and not by the
program: the served run and the plain reference draw the same numbers
from the same keys, so the reference takes nothing the program made.

Every matrix is normal(0, s) with s the power of two nearest
1/sqrt(fan_in), every norm scale 1 + k/64 with k a rounded
normal(0, 8) clipped to +-24, each drawn in float32 from its own key
and cast to the served dtype. Scaling by a power of two and small
integer arithmetic are exact, so however the compiler fuses or
reassociates them no bit changes. A layer's leaves use
``fold_in(leaf key, layer)``, so the reference can regenerate one layer
at a time, bit for bit, from what the one jitted call for the whole
model made.

``program_params`` lays the numbers out in the program's parameter tree
(``repro.models.model``: layers stacked on a leading axis, projections
as (in, out) matrices); that layout is the one thing this file knows of
the program.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# leaf name -> (shape as a function of the config, fan-in, kind);
# kind "w" = matrix, "norm" = scale vector
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "up", "down")
TOP_LEAVES = ("embed", "final_ln", "lm_head")


def _shape(cfg: dict, leaf: str):
    d, h, kv, hd, ff, v = (cfg["hidden_size"], cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"],
                           cfg["intermediate_size"], cfg["vocab_size"])
    return {"ln1": (d,), "ln2": (d,), "final_ln": (d,),
            "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "up": (d, ff), "down": (ff, d),
            "embed": (v, d), "lm_head": (d, v)}[leaf]


def root_key(seed: int):
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0x7FFFFFFF)


def _leaf_key(seed_key, leaf: str):
    names = TOP_LEAVES + LAYER_LEAVES
    return jax.random.fold_in(seed_key, names.index(leaf))


def draw(cfg: dict, leaf: str, key, dtype):
    shape = _shape(cfg, leaf)
    x = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("ln1", "ln2", "final_ln"):
        x = 1.0 + jnp.round(jnp.clip(8.0 * x, -24.0, 24.0)) / 64.0
    elif leaf != "embed":
        x = x * 2.0 ** -round(math.log2(math.sqrt(shape[0])))
    return x.astype(dtype)


def layer(cfg: dict, seed_key, i, dtype) -> Dict[str, jax.Array]:
    """All leaves of layer ``i`` (traced or concrete index)."""
    return {n: draw(cfg, n, jax.random.fold_in(_leaf_key(seed_key, n), i),
                    dtype) for n in LAYER_LEAVES}


def top(cfg: dict, seed_key, leaf: str, dtype):
    return draw(cfg, leaf, _leaf_key(seed_key, leaf), dtype)


def program_params(cfg: dict, seed: int):
    """The whole model in the program's parameter tree, made on the
    device by one jitted call, in the served dtype."""
    dtype = jnp.dtype(cfg["dtype"])

    def make(seed_key):
        stacked = jax.vmap(lambda i: layer(cfg, seed_key, i, dtype))(
            jnp.arange(cfg["num_hidden_layers"]))
        return {
            "embed": top(cfg, seed_key, "embed", dtype),
            "final_ln": top(cfg, seed_key, "final_ln", dtype),
            "lm_head": top(cfg, seed_key, "lm_head", dtype),
            "layers": {
                "ln1": stacked["ln1"], "ln2": stacked["ln2"],
                "attn": {k: stacked[k] for k in ("wq", "wk", "wv", "wo")},
                "mlp": {"up": stacked["up"], "down": stacked["down"]},
            },
        }
    return jax.jit(make)(root_key(seed))
