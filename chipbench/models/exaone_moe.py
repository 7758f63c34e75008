"""K-EXAONE-236B-A23B (``model_type`` "exaone_moe"): GQA attention in
the layer pattern "LLLG" (three layers over a sliding window of 128
keys, then one over the whole context), a dense gated-SiLU MLP in the
first ``first_k_dense_replace`` layers, then expert layers: 128 routed
gated-SiLU experts, 8 per token, chosen by a sigmoid router whose top-8
scores are normalised to sum 1 and scaled by 2.5, plus one shared
expert. An untied head.

A configuration file names this module with ``"model": "exaone_moe"``.
It holds the published ``config.json`` whole, but for the keys its
``reduced`` lists: ``num_hidden_layers`` (the layers served here: the
dense first layer, then whole periods of the pattern from the second
period on, published layers 4, 5, ...) and ``num_experts`` (the
experts this chip holds, from global expert ``first_expert`` on, of
the ``published`` count that the router scores).

* ``program_config`` / ``program_params``: the program's ModelConfig
  (``repro.configs.k_exaone_236b_a23b`` cut to this file) and the
  served model in its parameter tree: the dense layers stacked under
  "dense_layers", the expert layers under "layers", each expert layer's
  held experts stacked on an expert axis.
* ``layer_params`` / ``top_params``: one layer's leaves or one
  top-level leaf. A leaf's key folds in its place in ``LEAVES``, the
  published index of its layer and, for an expert, the global expert
  index, so the experts held here are the uncut model's experts of the
  same index, and a served layer is the uncut model's layer.
* ``Forward``: the plain reference, float32 at ``highest``, from
  ``reference.py``'s blocks: windowed layers attend their last 128 keys
  (``gqa_attention(..., window=128)``); an expert layer runs its held
  experts densely on every token, each weighted by the token's gate
  for it (0 where the router did not choose it). ``int8=True`` is the
  control.
* ``prefill_flops``, ``decode_flops``, ``decode_kernel_cost`` and
  ``expert_cost``: the counts ``step.mfu``, ``paged_decode_roofline``
  and ``expert_gmm_roofline`` divide by.
* ``KERNELS``: kernel key -> the op name the device trace gives it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# the program's description of the model; a tree without it fails here,
# before anything is built
from repro.configs.k_exaone_236b_a23b import CONFIG as PUBLISHED

from chipbench import reference as R
from chipbench import weights as W

KERNELS = {"paged_decode": "paged_gqa_decode", "expert_gmm": "expert_gmm"}

TOP = ("embed", "final_ln", "lm_head")
# every layer leaf; a dense layer has the first nine, an expert layer
# the first six and the rest. A leaf's key is its index in TOP + LAYER
LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up", "down",
         "router", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
ATTN = ("ln1", "wq", "wk", "wv", "wo", "ln2")
DENSE = ATTN + ("gate", "up", "down")
EXPERT = ("e_gate", "e_up", "e_down")
MOE = ATTN + ("router",) + EXPERT + ("s_gate", "s_up", "s_down")


def _check(cfg: dict) -> None:
    want = {"hidden_act": "silu", "scoring_func": "sigmoid",
            "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
            "tie_word_embeddings": False}
    bad = {k: cfg[k] for k, v in want.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"exaone_moe serves {want}; configuration "
                         f"{cfg['name']!r} has {bad}")


def dims(cfg: dict) -> dict:
    """The numbers the reference blocks and the counts read, under the
    names they read them by."""
    return {**cfg, "norm_eps": cfg["rms_norm_eps"],
            "rope_theta": cfg["rope_parameters"]["rope_theta"]}


def dense_count(cfg: dict) -> int:
    return cfg["first_k_dense_replace"]


def published_layers(cfg: dict) -> list:
    """The published index of each served layer: the leading dense
    ones, then whole periods of the window pattern from the first
    period that holds only expert layers."""
    k, n = dense_count(cfg), cfg["num_hidden_layers"]
    p = len(cfg["sliding_window_pattern"])
    first = -(-k // p) * p
    return list(range(k)) + list(range(first, first + n - k))


def windows(cfg: dict) -> list:
    """Each served layer's window, 0 for full attention."""
    return [cfg["sliding_windows"][j] for j in published_layers(cfg)]


def router_width(cfg: dict) -> int:
    return cfg["published"]["num_experts"]


def program_config(cfgj: dict):
    """The program's ModelConfig for a configuration file."""
    _check(cfgj)
    c = dims(cfgj)
    moe = dataclasses.replace(
        PUBLISHED.moe, num_experts=router_width(c),
        top_k=c["num_experts_per_tok"],
        num_shared_experts=c["num_shared_experts"],
        expert_d_ff=c["moe_intermediate_size"],
        scoring=c["scoring_func"], routed_scale=c["routed_scaling_factor"],
        first_expert=c["first_expert"], experts_held=c["num_experts"])
    return dataclasses.replace(
        PUBLISHED, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["norm_eps"],
        layer_windows=tuple(windows(c)), dense_layers=dense_count(c),
        dense_d_ff=c["intermediate_size"], moe=moe, dtype=c["dtype"])


# ------------------------------------------------------------- weights
def _shape(cfg: dict, leaf: str):
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    ff, f, v = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["vocab_size"])
    return {"ln1": (d,), "ln2": (d,), "final_ln": (d,),
            "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "gate": (d, ff), "up": (d, ff),
            "down": (ff, d), "router": (d, router_width(cfg)),
            "e_gate": (d, f), "e_up": (d, f), "e_down": (f, d),
            "s_gate": (d, f), "s_up": (d, f), "s_down": (f, d),
            "embed": (v, d), "lm_head": (d, v)}[leaf]


def _leaf(cfg: dict, key, leaf: str, dtype):
    kind = "norm" if leaf in ("ln1", "ln2", "final_ln") else \
        "embed" if leaf == "embed" else "matrix"
    # the router is float32 whatever the served dtype
    dt = jnp.float32 if leaf == "router" else dtype
    return W.draw(key, _shape(cfg, leaf), kind, dt)


def _layer(cfg: dict, seed_key, pub, names, dtype) -> dict:
    """The leaves ``names`` of the layer of published index ``pub``
    (traced or concrete); experts stacked over the held ones."""
    out = {}
    for n in names:
        key = jax.random.fold_in(W.leaf_key(seed_key, TOP + LAYER, n), pub)
        if n in EXPERT:
            ids = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
            out[n] = jax.vmap(lambda e, key=key, n=n: _leaf(
                cfg, jax.random.fold_in(key, e), n, dtype))(ids)
        else:
            out[n] = _leaf(cfg, key, n, dtype)
    return out


def layer_params(cfg: dict, seed_key, i: int, dtype):
    """All leaves of served layer ``i`` (a concrete index)."""
    names = DENSE if i < dense_count(cfg) else MOE
    return _layer(cfg, seed_key, published_layers(cfg)[i], names, dtype)


def top_params(cfg: dict, seed_key, leaf: str, dtype):
    return _leaf(cfg, W.leaf_key(seed_key, TOP + LAYER, leaf), leaf, dtype)


def program_params(cfg: dict, seed: int):
    """The served model in the program's parameter tree, made on the
    device by one jitted call, in the served dtype."""
    dtype = jnp.dtype(cfg["dtype"])
    pub = published_layers(cfg)
    k = dense_count(cfg)

    def stack(seed_key, idx, names):
        return jax.vmap(lambda j: _layer(cfg, seed_key, j, names, dtype))(
            jnp.asarray(idx))

    def attn(s):
        return {"ln1": s["ln1"], "ln2": s["ln2"],
                "attn": {n: s[n] for n in ("wq", "wk", "wv", "wo")}}

    def make(seed_key):
        d = stack(seed_key, pub[:k], DENSE)
        m = stack(seed_key, pub[k:], MOE)
        return {
            **{n: top_params(cfg, seed_key, n, dtype) for n in TOP},
            "dense_layers": {**attn(d), "mlp": {n: d[n] for n in
                                                 ("gate", "up", "down")}},
            "layers": {**attn(m), "moe": {
                "router": m["router"], "w_gate": m["e_gate"],
                "w_up": m["e_up"], "w_down": m["e_down"],
                "shared": {"gate": m["s_gate"], "up": m["s_up"],
                           "down": m["s_down"]}}},
        }
    return jax.jit(make)(W.root_key(seed))


# ----------------------------------------------------------- reference
def _glu(h, g, u, dn, q8):
    return R.mm(jax.nn.silu(R.mm(h, g, q8)) * R.mm(h, u, q8), dn, q8)


def gates(cfg: dict, h, router, q8: bool):
    """(T, E) float32: each token's gate for every expert, 0 where the
    router did not choose it: sigmoid scores, the top ``k`` normalised
    to sum 1 and scaled."""
    s = jax.nn.sigmoid(R.mm(h, router, q8))
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    g = cfg["routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(g)


def experts(cfg: dict, w: dict, h, q8: bool):
    """The held experts' part of an expert layer, each run densely on
    every token and weighted by its gate."""
    g = gates(cfg, h, w["router"], q8)
    y = jnp.zeros_like(h)
    for e in range(cfg["num_experts"]):
        ge = g[:, cfg["first_expert"] + e]
        y = y + ge[:, None] * _glu(h, w["e_gate"][e], w["e_up"][e],
                                   w["e_down"][e], q8)
    return y


def block(cfg: dict, w: dict, x, n_real, q8: bool, window):
    """One served layer on float32 leaves ``w``. A padding row more
    than a window past the sequence attends no key and comes out NaN;
    it is zeroed, so that the next layer's keys and values stay finite
    (a masked key's weight is 0, and 0 * NaN is NaN)."""
    eps = cfg["norm_eps"]
    x = x + R.gqa_attention(R.rms(x, w["ln1"], eps), w, cfg, n_real, q8,
                            window=window or None)
    h = R.rms(x, w["ln2"], eps)
    if "gate" in w:
        x = x + _glu(h, w["gate"], w["up"], w["down"], q8)
    else:
        x = x + experts(cfg, w, h, q8) + _glu(h, w["s_gate"], w["s_up"],
                                              w["s_down"], q8)
    return jnp.where(jnp.arange(x.shape[0])[:, None] < n_real, x, 0.0)


class Forward:
    """Reference logits for one configuration and seed (``.logits``):
    the embedding, each served layer and the head are jitted calls, a
    layer's weights drawn inside its call; one compiled program per
    kind of layer (dense or expert) and window."""

    def __init__(self, cfg: dict, seed: int, int8: bool = False):
        _check(cfg)
        self.cfg = c = dims(cfg)
        self.key = W.root_key(seed)
        dtype = jnp.dtype(c["dtype"])
        self.kinds = [(i < dense_count(c), win)
                      for i, win in enumerate(windows(c))]
        pub = published_layers(c)

        def embed(key, tokens):
            return top_params(c, key, "embed", dtype)[tokens].astype(
                jnp.float32)

        def layer(key, pub_i, x, n_real, dense, window):
            w = _layer(c, key, pub_i, DENSE if dense else MOE, dtype)
            w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
            return block(c, w, x, n_real, int8, window)

        def head(key, x):
            g = top_params(c, key, "final_ln", dtype).astype(jnp.float32)
            return R.head_logits(x, g, top_params(c, key, "lm_head", dtype),
                                 c["norm_eps"], int8)
        self._embed, self._head = jax.jit(embed), jax.jit(head)
        self._layer = jax.jit(layer, static_argnums=(4, 5))
        self._pub = pub

    def logits(self, tokens, first: int):
        """Logits (float32) at positions first .. len(tokens)-1."""
        key = self.key
        return R.forward_logits(
            tokens, first, lambda ids: self._embed(key, ids),
            lambda i, x, n: self._layer(key, jnp.int32(self._pub[i]), x, n,
                                        *self.kinds[i]),
            len(self.kinds), lambda x: self._head(key, x))


# -------------------------------------------------------------- counts
def _held_share(cfg: dict) -> float:
    """Expected held (token, expert) pairs per token under uniform
    routing: top-k of E experts, ``num_experts`` of them held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / router_width(cfg)


def linear_flops_per_token(cfg: dict) -> float:
    """Projections, MLPs, router, shared and held experts of every
    served layer, per token (no head)."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    f, n, k = (cfg["moe_intermediate_size"], cfg["num_hidden_layers"],
               dense_count(cfg))
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    dense = 3 * d * cfg["intermediate_size"]
    moe = d * router_width(cfg) + 3 * d * f * (
        cfg["num_shared_experts"] + _held_share(cfg))
    return 2.0 * (n * attn + k * dense + (n - k) * moe)


def _keys(win: int, a: int, b: int) -> float:
    """Keys attended by the queries that see a .. b keys (one more
    each), in a layer of window ``win`` (0: full): sum of min(c, win)."""
    if not win:
        return (a + b) * (b - a + 1) / 2
    lo = min(b, win)
    part = (a + lo) * (lo - a + 1) / 2 if a <= lo else 0.0
    return part + max(0, b - max(a, win + 1) + 1) * win


def _attn_flops(cfg: dict, a: int, b: int) -> float:
    per_key = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * sum(_keys(w, a, b) for w in windows(cfg))


def head_flops_per_token(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` prompt tokens at positions start ..
    start+n-1: projections, MLPs and held experts at their expected
    share, and each token's attention over min(its keys, window) keys
    in each layer (no head)."""
    return n * linear_flops_per_token(cfg) + _attn_flops(cfg, start + 1,
                                                         start + n)


def decode_flops(cfg: dict, pos: int) -> float:
    """One decoded token at position ``pos``: as a prefill token, and
    the head."""
    return (linear_flops_per_token(cfg) + head_flops_per_token(cfg)
            + _attn_flops(cfg, pos + 1, pos + 1))


def decode_kernel_cost(cfg: dict, pos: int):
    """(FLOPs, bytes) of the paged decode attention kernel for one live
    row decoding at position ``pos``, every layer: read the keys and
    values it attends, min(pos + 1, window) or pos + 1, of every KV
    head, and its query; write its output; in the served dtype."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    size = jnp.dtype(cfg["dtype"]).itemsize
    ctx = [min(pos + 1, w) if w else pos + 1 for w in windows(cfg)]
    flops = 4.0 * h * hd * sum(ctx)
    byts = sum((2.0 * c * kv * hd + 2.0 * h * hd) * size for c in ctx)
    return flops, byts


def expected_pairs_and_hits(cfg: dict, rows: int):
    """Under uniform routing, a call of ``rows`` token rows brings
    rows * k * held / E held (token, expert) pairs, and some row chooses
    held * (1 - (1 - k/E) ** rows) of the held experts."""
    share = cfg["num_experts_per_tok"] / router_width(cfg)
    return (rows * _held_share(cfg),
            cfg["num_experts"] * (1.0 - (1.0 - share) ** rows))


def expert_cost(cfg: dict, rows: int):
    """(FLOPs, bytes) of the held experts of one expert layer for one
    call over ``rows`` token rows (``expected_pairs_and_hits``): three
    D x F matmuls a pair; the weights of each chosen held expert read
    once, and each pair's input and output row; in the served dtype."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    size = jnp.dtype(cfg["dtype"]).itemsize
    pairs, hit = expected_pairs_and_hits(cfg, rows)
    return 6.0 * d * f * pairs, (hit * 3 * d * f + pairs * 2 * d) * size
