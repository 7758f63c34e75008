"""Model assembly for all assigned architecture families.

Every family exposes the same functional API:

  init_params(cfg, key)                          -> params
  forward(params, cfg, batch)                    -> logits (B,S,V)
  init_cache(cfg, batch_size, cache_len)         -> cache
  prefill(params, cfg, batch, cache)             -> (last_logits, cache)
  decode_step(params, cfg, token, cache, pos)    -> (logits, cache)

Layer stacks are executed with jax.lax.scan over stacked params so the
lowered HLO is depth-independent (critical for the 96-layer dry-runs).
Heterogeneous stacks (VLM cross-attn every Nth layer, xLSTM block
patterns, Zamba2's weight-shared attention block) are expressed as an
outer scan over repeating groups.

``batch`` dict keys: "tokens" (B,S) int32; optional "frontend"
(B,F,D) precomputed modality embeddings (audio frames / vision patches
— the stubbed frontend, DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (DENSE, ENCDEC, HYBRID, MOE, SSM, VLM,
                                ModelConfig)
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE_MOD
from repro.models import ssm as S

Params = Dict[str, Any]

# Scan unrolling toggle: the dry-run costing pass sets this to True so
# XLA's cost_analysis (which counts a while-loop body ONCE, regardless
# of trip count) sees the real per-layer work. Default 1 = rolled scan.
SCAN_UNROLL = 1

# Per-layer rematerialization: checkpoint every scan body (the standard
# large-model policy — activation memory O(residual stream), one extra
# forward of recompute). Enabled by training.train_step remat="layer";
# §Perf iteration 2 (EXPERIMENTS.md): cuts nemotron-340b train temps
# ~50x vs whole-forward remat.
LAYER_REMAT = False

# Sequence-parallel residual stream (Megatron-SP): between transformer
# blocks the (B, S, D) residual is sharded along S over the model axis,
# so per-layer remat saves 1/tp of the activations and XLA converts the
# block all-reduces into reduce-scatter + all-gather pairs.
# §Perf iteration 4. None = off (baseline).
SEQUENCE_PARALLEL = None   # set to a ParallelContext to enable


def _residual_constraint(x):
    ctx = SEQUENCE_PARALLEL
    if ctx is None or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    if x.shape[1] % ctx.mesh.shape[ctx.model_axis]:
        return x
    spec = P(tuple(ctx.data_axes), ctx.model_axis, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def _scan(f, init, xs, length=None):
    if LAYER_REMAT:
        f = jax.checkpoint(f, prevent_cse=False)
    return jax.lax.scan(f, init, xs, length=length, unroll=SCAN_UNROLL)



# ===========================================================================
# init
# ===========================================================================
def _init_decoder_layer(cfg: ModelConfig, dtype):
    def f(key):
        ks = jax.random.split(key, 4)
        p = {"ln1": jnp.ones((cfg.d_model,), dtype),
             "ln2": jnp.ones((cfg.d_model,), dtype)}
        if cfg.mla is not None:
            p["attn"] = MLA.init_mla(ks[0], cfg, dtype)
        else:
            p["attn"] = L.init_attention(ks[0], cfg, dtype=dtype)
        if cfg.moe is not None:
            p["moe"] = MOE_MOD.init_moe(ks[1], cfg, dtype)
        elif cfg.d_ff:
            p["mlp"] = L.init_mlp(ks[1], cfg, dtype=dtype)
        return p
    return f


def _init_cross_layer(cfg: ModelConfig, dtype):
    def f(key):
        ks = jax.random.split(key, 3)
        return {"ln": jnp.ones((cfg.d_model,), dtype),
                "xattn": L.init_attention(ks[0], cfg, cross=True, dtype=dtype),
                "gate": jnp.zeros((), dtype),
                "ln_mlp": jnp.ones((cfg.d_model,), dtype),
                "mlp": L.init_mlp(ks[1], cfg, dtype=dtype),
                "gate_mlp": jnp.zeros((), dtype)}
    return f


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    p: Params = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(dtype),
        "final_ln": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab_size,
                                    dtype)
    fam = cfg.family
    if fam in (DENSE, MOE):
        p["layers"] = L.stack_init(keys[2], cfg.num_layers,
                                   _init_decoder_layer(cfg, dtype))
    elif fam == VLM:
        n_groups = cfg.num_layers // cfg.cross_attn_every
        p["layers"] = L.stack_init(keys[2], cfg.num_layers,
                                   _init_decoder_layer(cfg, dtype))
        p["layers"] = jax.tree.map(
            lambda a: a.reshape(n_groups, cfg.cross_attn_every, *a.shape[1:]),
            p["layers"])
        p["cross"] = L.stack_init(keys[3], n_groups,
                                  _init_cross_layer(cfg, dtype))
    elif fam == ENCDEC:
        enc_cfg = dataclasses.replace(cfg, moe=None)
        p["encoder"] = L.stack_init(keys[2], cfg.encoder_layers,
                                    _init_decoder_layer(enc_cfg, dtype))
        p["enc_ln"] = jnp.ones((cfg.d_model,), dtype)

        def dec_layer(key):
            ks = jax.random.split(key, 2)
            base = _init_decoder_layer(cfg, dtype)(ks[0])
            base["lnx"] = jnp.ones((cfg.d_model,), dtype)
            base["xattn"] = L.init_attention(ks[1], cfg, cross=True,
                                             dtype=dtype)
            return base
        p["layers"] = L.stack_init(keys[3], cfg.num_layers, dec_layer)
    elif fam == HYBRID:
        every = cfg.ssm.shared_attn_every
        n_groups, rem = divmod(cfg.num_layers, every)
        p["layers"] = L.stack_init(
            keys[2], cfg.num_layers,
            lambda k: {"ln": jnp.ones((cfg.d_model,), dtype),
                       "mamba": S.init_mamba2(k, cfg, dtype)})
        p["layers"] = jax.tree.map(
            lambda a: a.reshape(n_groups, every, *a.shape[1:])
            if rem == 0 else a, p["layers"])
        if rem:  # keep flat; group at runtime
            pass
        ks2 = jax.random.split(keys[3], 3)
        p["shared_attn"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": L.init_attention(ks2[0], cfg, dtype=dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mlp": L.init_mlp(ks2[1], cfg, dtype=dtype)}
    elif fam == SSM:
        pattern = cfg.ssm.block_pattern or ("mlstm",)
        n_groups = cfg.num_layers // len(pattern)
        stacks = {}
        sub = jax.random.split(keys[2], len(pattern))
        for i, kind in enumerate(pattern):
            init = (S.init_mlstm if kind == "mlstm" else S.init_slstm)
            stacks[f"blk{i}_{kind}"] = L.stack_init(
                sub[i], n_groups,
                lambda k, init=init: {"ln": jnp.ones((cfg.d_model,), dtype),
                                      "core": init(k, cfg, dtype)})
        p["layers"] = stacks
    else:
        raise ValueError(f"unknown family {fam}")
    return p


# ===========================================================================
# forward (training / teacher forcing)
# ===========================================================================
def _decoder_block(lp, cfg: ModelConfig, x, positions, parallel, window=None):
    h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        a, _, _ = MLA.mla_attention(lp["attn"], cfg, h, positions)
    else:
        a = L.attention(lp["attn"], cfg, h, positions, window=window)
    x = x + a
    h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        if parallel is None:
            m, aux = MOE_MOD.moe_block(lp["moe"], cfg, h, None)
        else:
            m, aux = MOE_MOD.moe_block_sharded(lp["moe"], cfg, h, parallel,
                                               mode="a2a")
        return x + m, aux["lb_loss"]
    return x + L.mlp(lp["mlp"], cfg, h), jnp.float32(0.0)


def _run_decoder_stack(stacked, cfg, x, positions, parallel, window=None):
    def body(carry, lp):
        x, lb = carry
        x = _residual_constraint(x)
        x, lb_i = _decoder_block(lp, cfg, x, positions, parallel, window)
        return (x, lb + lb_i), None
    (x, lb), _ = _scan(body, (x, jnp.float32(0.0)), stacked)
    return x, lb


def _cross_block(cp, cfg, x, memory):
    h = L.rmsnorm(x, cp["ln"], cfg.norm_eps)
    x = x + jnp.tanh(cp["gate"]) * L.cross_attention(cp["xattn"], cfg, h,
                                                     memory)
    h = L.rmsnorm(x, cp["ln_mlp"], cfg.norm_eps)
    return x + jnp.tanh(cp["gate_mlp"]) * L.mlp(cp["mlp"], cfg, h)


def forward(params: Params, cfg: ModelConfig, batch: Dict,
            parallel=None, window: Optional[int] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits, aux_loss). Teacher-forcing full-sequence pass."""
    _paged_only(cfg)
    tokens = batch["tokens"]
    b, s_len = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s_len), (b, s_len))
    lb = jnp.float32(0.0)
    fam = cfg.family
    w = cfg.attention_window if window is None else window

    if fam in (DENSE, MOE):
        x, lb = _run_decoder_stack(params["layers"], cfg, x, positions,
                                   parallel, w)
    elif fam == VLM:
        memory = batch["frontend"]

        def group(carry, lps):
            x, lb = carry
            x, lb_i = _run_decoder_stack(lps[0], cfg, x, positions,
                                         parallel, w)
            x = _cross_block(lps[1], cfg, x, memory)
            return (x, lb + lb_i), None
        (x, lb), _ = _scan(group, (x, lb),
                                  (params["layers"], params["cross"]))
    elif fam == ENCDEC:
        enc = _encode(params, cfg, batch["frontend"], parallel)

        def dec(carry, lp):
            x, lb = carry
            x = _residual_constraint(x)
            x, lb_i = _decoder_block(lp, cfg, x, positions, parallel, w)
            h = L.rmsnorm(x, lp["lnx"], cfg.norm_eps)
            x = x + L.cross_attention(lp["xattn"], cfg, h, enc)
            return (x, lb + lb_i), None
        (x, lb), _ = _scan(dec, (x, lb), params["layers"])
    elif fam == HYBRID:
        x = _hybrid_forward(params, cfg, x, positions, w)
    elif fam == SSM:
        x = _ssm_forward(params, cfg, x)

    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits, lb


def _encode(params, cfg, frontend, parallel):
    b, f_len, _ = frontend.shape
    pos = jnp.broadcast_to(jnp.arange(f_len), (b, f_len))
    enc_cfg = dataclasses.replace(cfg, moe=None)

    def body(x, lp):
        x = _residual_constraint(x)
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        # bidirectional self-attention over frames
        q, k, v = L._qkv(lp["attn"], enc_cfg, h, h)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        a = L._sdpa(q, k, v, None, enc_cfg.q_per_kv) @ lp["attn"]["wo"]
        x = x + a
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + L.mlp(lp["mlp"], enc_cfg, h), None
    x, _ = _scan(body, frontend, params["encoder"])
    return L.rmsnorm(x, params["enc_ln"], cfg.norm_eps)


def _shared_attn_block(sp, cfg, x, positions, window):
    h = L.rmsnorm(x, sp["ln1"], cfg.norm_eps)
    x = x + L.attention(sp["attn"], cfg, h, positions, window=window)
    h = L.rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + L.mlp(sp["mlp"], cfg, h)


def _hybrid_forward(params, cfg, x, positions, window):
    every = cfg.ssm.shared_attn_every
    n_groups, rem = divmod(cfg.num_layers, every)
    sp = params["shared_attn"]

    def mamba_layer(x, lp):
        x = _residual_constraint(x)
        h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
        y, _ = S.mamba2_forward(lp["mamba"], cfg, h)
        return x + y, None

    if rem == 0:
        def group(x, lps):
            x, _ = _scan(mamba_layer, x, lps)
            return _shared_attn_block(sp, cfg, x, positions, window), None
        x, _ = _scan(group, x, params["layers"])
    else:
        # params kept flat: run groups then remainder
        flat = params["layers"]
        grouped = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(n_groups, every,
                                                    *a.shape[1:]), flat)
        tail = jax.tree.map(lambda a: a[n_groups * every:], flat)

        def group(x, lps):
            x, _ = _scan(mamba_layer, x, lps)
            return _shared_attn_block(sp, cfg, x, positions, window), None
        x, _ = _scan(group, x, grouped)
        x, _ = _scan(mamba_layer, x, tail)
    return x


def _ssm_forward(params, cfg, x):
    pattern = cfg.ssm.block_pattern or ("mlstm",)

    def group(x, lps):
        x = _residual_constraint(x)
        for i, kind in enumerate(pattern):
            lp = lps[f"blk{i}_{kind}"]
            h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
            fwd = S.mlstm_forward if kind == "mlstm" else S.slstm_forward
            y, _ = fwd(lp["core"], cfg, h)
            x = x + y
        return x, None
    x, _ = _scan(group, x, params["layers"])
    return x


def _paged_only(cfg: ModelConfig) -> None:
    """Per-layer windows and leading dense layers exist in the paged
    serving steps only."""
    if cfg.layer_windows or cfg.dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: per-layer windows and leading dense layers are "
            "served through the paged KV cache only (paged=True)")


# ===========================================================================
# decode path (serve_step)
# ===========================================================================
def _shard_tree(tree: Params, shardings) -> Params:
    """device_put every leaf under its sharding — the post-hoc path for
    cache subtrees whose init reshapes/broadcasts after creation (VLM
    grouped kv, SSM state stacks), where creating directly under the
    final sharding isn't possible. ``shardings`` must mirror ``tree``
    with one jax.sharding.Sharding per leaf."""
    if shardings is None:
        return tree
    return jax.tree.map(jax.device_put, tree, shardings)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int,
               frontend_len: Optional[int] = None,
               shardings=None) -> Params:
    """cache_len: max context (or window size for windowed attention).

    ``shardings``: optional pytree of jax shardings mirroring the
    returned cache (distributed/sharding.serving_cache_specs +
    to_named) — KV leaves are created directly under their sharding
    (kv-head dim over the model axis for the sharded serving engine);
    subtrees built by reshape/broadcast are device_put after."""
    _paged_only(cfg)
    dtype = jnp.dtype(cfg.dtype)
    fam = cfg.family
    sh = shardings or {}
    eff_len = min(cache_len, cfg.attention_window) \
        if cfg.attention_window else cache_len
    cache: Params = {}
    if fam in (DENSE, MOE):
        if cfg.mla is not None:
            cache["kv"] = _shard_tree(
                MLA.init_mla_cache(cfg, cfg.num_layers, batch, eff_len,
                                   dtype), sh.get("kv"))
        else:
            cache["kv"] = L.init_kv_cache(cfg, cfg.num_layers, batch,
                                          eff_len, dtype,
                                          shardings=sh.get("kv"))
    elif fam == VLM:
        n_groups = cfg.num_layers // cfg.cross_attn_every
        cache["kv"] = L.init_kv_cache(cfg, cfg.num_layers, batch, eff_len,
                                      dtype)
        cache["kv"] = _shard_tree(jax.tree.map(
            lambda a: a.reshape(n_groups, cfg.cross_attn_every, *a.shape[1:]),
            cache["kv"]), sh.get("kv"))
        f = frontend_len or cfg.frontend_tokens
        hd = cfg.resolved_head_dim
        xshape = (n_groups, batch, f, cfg.num_kv_heads, hd)
        cache["xk"] = L.cache_zeros(xshape, dtype, sh.get("xk"))
        cache["xv"] = L.cache_zeros(xshape, dtype, sh.get("xv"))
    elif fam == ENCDEC:
        cache["kv"] = L.init_kv_cache(cfg, cfg.num_layers, batch, eff_len,
                                      dtype, shardings=sh.get("kv"))
        f = frontend_len or cfg.frontend_tokens
        hd = cfg.resolved_head_dim
        xshape = (cfg.num_layers, batch, f, cfg.num_kv_heads, hd)
        cache["xk"] = L.cache_zeros(xshape, dtype, sh.get("xk"))
        cache["xv"] = L.cache_zeros(xshape, dtype, sh.get("xv"))
    elif fam == HYBRID:
        every = cfg.ssm.shared_attn_every
        n_groups, rem = divmod(cfg.num_layers, every)
        st = S.init_mamba2_state(cfg, batch)
        cache["ssm"] = _shard_tree(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups, every) + a.shape
                                       ).copy() if rem == 0 else
            jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(), st),
            sh.get("ssm"))
        hd = cfg.resolved_head_dim
        kvsh = sh.get("kv") or {}
        kshape = (n_groups, batch, eff_len, cfg.num_kv_heads, hd)
        cache["kv"] = {
            "k": L.cache_zeros(kshape, dtype, kvsh.get("k")),
            "v": L.cache_zeros(kshape, dtype, kvsh.get("v"))}
    elif fam == SSM:
        pattern = cfg.ssm.block_pattern or ("mlstm",)
        n_groups = cfg.num_layers // len(pattern)
        stacks = {}
        for i, kind in enumerate(pattern):
            st = (S.init_mlstm_state if kind == "mlstm"
                  else S.init_slstm_state)(cfg, batch)
            stacks[f"blk{i}_{kind}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy(),
                st)
        cache["ssm"] = _shard_tree(stacks, sh.get("ssm"))
    return cache


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int, shardings=None) -> Params:
    """Paged KV cache: one shared pool of ``num_blocks`` physical
    blocks per layer (models/layers.init_paged_kv_cache). No batch
    axis exists — slots own blocks via the engine's block tables, so
    HBM scales with the ACTUAL length mix, not batch * c_max.

    Supported for the contiguous-cache attention families (dense/MoE,
    fp KV) — the paper's serving model (Llama-3-70B) — with full
    attention or per-layer sliding windows (``cfg.layer_windows``: a
    windowed layer pages every position like a full one and attends
    only its window; the pages behind it stay allocated, since all
    layers share one block table). Leading dense layers of a MoE model
    (``cfg.dense_layers``) keep their pool under "kv_dense".
    ``shardings``: optional cache-shaped pytree of jax shardings (the
    sharded engine's kv-head-split block pool).
    """
    if cfg.family not in (DENSE, MOE) or cfg.mla is not None:
        raise NotImplementedError(
            "paged KV cache supports dense/MoE attention models; "
            f"family={cfg.family!r} mla={cfg.mla is not None}")
    if cfg.attention_window:
        raise NotImplementedError(
            "windowed attention already bounds KV by the window; paging "
            "it would page a ring buffer — unsupported")
    sh = shardings or {}
    cache = {"kv": L.init_paged_kv_cache(
        cfg, cfg.num_layers - cfg.dense_layers, num_blocks, block_size,
        shardings=sh.get("kv"))}
    if cfg.dense_layers:
        cache["kv_dense"] = L.init_paged_kv_cache(
            cfg, cfg.dense_layers, num_blocks, block_size,
            shardings=sh.get("kv_dense"))
    return cache


def _period(windows) -> Tuple:
    """The shortest run of windows (0 -> None, full attention) that
    repeats to give ``windows``."""
    n = len(windows)
    for p in range(1, n + 1):
        if n % p == 0 and all(windows[i] == windows[i % p]
                              for i in range(n)):
            return tuple(w or None for w in windows[:p])


def _period_scan(layer, x, stacked, kv, windows):
    """``layer(x, lp, kv, window) -> (x, kv)`` over stacked layers and
    their KV pools, in periods of ``len(windows)`` layers, each
    period's layers unrolled with their static windows. One period of
    one layer is the plain layer scan. Expert matrices stay whole: a
    layer gets all layers' ``w_gate`` / ``w_up`` / ``w_down`` and its
    index ``layer`` (the grouped-matmul kernel indexes them; a slice
    would be copied for it)."""
    if "moe" in stacked:
        whole = {n: stacked["moe"][n] for n in MOE_MOD.EXPERT_LEAVES}
        rest = {n: a for n, a in stacked["moe"].items() if n not in whole}
        rest["layer"] = jnp.arange(kv["k"].shape[0])
        stacked = dict(stacked, moe=rest)
        inner = layer

        def layer(x, lp, kv, window):
            return inner(x, dict(lp, moe=dict(lp["moe"], **whole)), kv,
                         window)
    p = len(windows)
    if kv["k"].shape[0] == p:
        # one period: no loop; each layer reads static slices and
        # writes its own pool in place
        for j, w in enumerate(windows):
            x, new = layer(x, jax.tree.map(lambda a: a[j], stacked),
                           jax.tree.map(lambda a: a[j], kv), w)
            kv = jax.tree.map(lambda a, b: a.at[j].set(b), kv, new)
        return x, kv
    if p == 1:
        def body(x, inp):
            return layer(x, *inp, windows[0])
        return _scan(body, x, (stacked, kv))
    fold = lambda a: a.reshape(a.shape[0] // p, p, *a.shape[1:])

    def body(x, inp):
        lps, kvs = inp
        new = []
        for j, w in enumerate(windows):
            x, kv_j = layer(x, jax.tree.map(lambda a: a[j], lps),
                            jax.tree.map(lambda a: a[j], kvs), w)
            new.append(kv_j)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *new)
    x, kv = _scan(body, x, jax.tree.map(fold, (stacked, kv)))
    return x, jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), kv)


def _paged_stack(params: Params, cfg: ModelConfig, x, cache: Params,
                 layer) -> Tuple[jnp.ndarray, Params]:
    """The paged steps' layer stack: the leading dense layers
    (``cfg.dense_layers``), then the rest in periods of their
    ``cfg.layer_windows``. ``layer(x, lp, kv, window)`` is one layer."""
    k = cfg.dense_layers
    windows = cfg.layer_windows or (0,) * cfg.num_layers
    cache = dict(cache)
    if k:
        x, cache["kv_dense"] = _period_scan(
            layer, x, params["dense_layers"], cache["kv_dense"],
            _period(windows[:k]))
    x, cache["kv"] = _period_scan(layer, x, params["layers"], cache["kv"],
                                  _period(windows[k:]))
    return x, cache


def _paged_ffn(lp, cfg: ModelConfig, h, parallel, mode: str, valid):
    """The layer's MLP: dense, the dropless held-expert layer on one
    device, or the expert-parallel capacity layer over a mesh."""
    if "mlp" in lp:
        return L.mlp(lp["mlp"], cfg, h)
    if parallel is None:
        return MOE_MOD.moe_held(lp["moe"], cfg, h, valid)
    # the capacity layer takes this layer's experts out of the stacks
    p = {n: a[lp["moe"]["layer"]] if n in MOE_MOD.EXPERT_LEAVES else a
         for n, a in lp["moe"].items() if n != "layer"}
    m, _ = MOE_MOD.moe_block_sharded(p, cfg, h, parallel, mode=mode)
    return m


def _attn_scope(window):
    return jax.named_scope("attn.window" if window else "attn.full")


def paged_decode_step(params: Params, cfg: ModelConfig, token,
                      cache: Params, block_tables, pos, parallel=None,
                      decode_impl: str = "xla", active=None
                      ) -> Tuple[jnp.ndarray, Params]:
    """Paged analog of :func:`decode_step` (dense/MoE branch). token:
    (B,1) int32; block_tables: (B, NB) int32; pos: (B,) per-row
    positions. Math matches decode_step on the gathered pages, so the
    paged engine reproduces dense output tokens exactly. ``active``
    rows with False are provable bitwise no-ops on the block pool."""
    x = params["embed"][token]
    pos = jnp.asarray(pos, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    valid = None if active is None else active[:, None]

    def layer(x, lp, kv, window):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        with _attn_scope(window):
            a, new = L.paged_decode_attention(lp["attn"], cfg, h, kv,
                                              block_tables, pos,
                                              decode_impl=decode_impl,
                                              active=active, window=window)
        x = x + a
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + _paged_ffn(lp, cfg, h, parallel, "psum", valid), new

    x, cache = _paged_stack(params, cfg, x, cache, layer)
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits[:, 0], cache


def paged_prefill_chunk(params: Params, cfg: ModelConfig, tokens,
                        cache: Params, block_tables, start_pos, lengths,
                        parallel=None, all_logits=False
                        ) -> Tuple[jnp.ndarray, Params]:
    """Paged analog of the fused sequence-level chunk prefill
    (:func:`_prefill_chunk_fused`): write the chunk's K/V through the
    block table (per-block dynamic scatter), then attend chunk queries
    over (gathered pages) causally. Same shapes/semantics as
    :func:`prefill_chunk`; rows with lengths == 0 are bitwise no-ops
    on the block pool.

    ``start_pos`` need not be 0 for a fresh request: the engine's
    prefix cache resumes prefill at the first cold token (a
    block-aligned offset), with the leading block-table entries
    aliasing blocks shared with other slots. Those blocks are READ
    (the causal mask spans the whole table) but never written —
    positions < start_pos scatter nothing — which is what keeps shared
    prefixes bitwise stable under concurrent prefill."""
    b, l = tokens.shape
    start_pos = jnp.asarray(start_pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    x = params["embed"][tokens]                          # (B, L, D)
    positions = start_pos[:, None] + jnp.arange(l)[None, :]
    rows = jnp.arange(l)[None, :] < lengths[:, None]      # live tokens

    def layer(x, lp, kv, window):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        with _attn_scope(window):
            q, k, v = L._qkv(lp["attn"], cfg, h, h)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            kv = L.write_chunk_kv_paged(kv, k, v, block_tables, start_pos,
                                        lengths)
            k_all = L.gather_pages(kv["k"], block_tables)
            v_all = L.gather_pages(kv["v"], block_tables)
            s_max = k_all.shape[1]
            keys = jnp.arange(s_max)[None, None, :]
            valid = keys <= positions[:, :, None]
            if window is not None:
                # a band: key j is visible to query i when
                # i - window < j <= i
                valid &= keys > positions[:, :, None] - window
            a = L._sdpa(q, k_all, v_all, valid, cfg.q_per_kv)
            x = x + a @ lp["attn"]["wo"]
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + _paged_ffn(lp, cfg, h, parallel, "a2a", rows), kv

    x, cache = _paged_stack(params, cfg, x, cache, layer)
    head = params.get("lm_head")
    if all_logits:
        # speculative verify path (paged_verify_step): logits at every
        # window position, (B, L, V)
        x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
        return (x @ head if head is not None
                else x @ params["embed"].T), cache
    last = jnp.clip(lengths - 1, 0, l - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits[:, 0], cache


def mixed_step(params: Params, cfg: ModelConfig, tokens, cache: Params,
               pos, lengths, decode_tokens, decode_active, parallel=None,
               window: Optional[int] = None,
               decode_impl: str = "xla") -> Tuple[jnp.ndarray, Params]:
    """Fused prefill+decode dispatch: advance every prefill row by one
    chunk AND every decode row by one token in ONE jitted call (the
    engine's mixed-iteration hot path — previously two back-to-back
    dispatches).

    Per-row mode routing reuses the masked fixed-shape machinery:
    ``lengths[s] > 0`` selects prefill mode (rows with 0 are bitwise
    no-ops in the chunk pass), ``decode_active[s]`` selects decode mode
    (rows with False are bitwise no-ops in the decode pass). The two
    row sets are disjoint, and each sub-computation is EXACTLY the one
    the separate ``prefill_chunk`` / ``decode_step`` dispatches run, so
    fusing preserves output tokens bit-for-bit.

    ``pos`` serves both modes: a prefill row's chunk starts at its
    ``pos``; a decode row's new token sits at its ``pos``.

    tokens: (B, L) zero-padded chunks; decode_tokens: (B, 1) last
    emitted token per decode row. Returns (decode logits (B, V) —
    garbage for non-decode rows — and the cache after BOTH passes).
    """
    _, cache = prefill_chunk(params, cfg, tokens, cache, pos, lengths,
                             parallel=parallel, window=window,
                             decode_impl=decode_impl)
    logits, cache = decode_step(params, cfg, decode_tokens, cache, pos,
                                parallel=parallel, window=window,
                                decode_impl=decode_impl,
                                active=decode_active)
    return logits, cache


def paged_mixed_step(params: Params, cfg: ModelConfig, tokens,
                     cache: Params, block_tables, pos, lengths,
                     decode_tokens, decode_active, parallel=None,
                     decode_impl: str = "xla"
                     ) -> Tuple[jnp.ndarray, Params]:
    """Paged analog of :func:`mixed_step`: one jitted call advances
    prefill rows (:func:`paged_prefill_chunk`) and decode rows
    (:func:`paged_decode_step`) through the shared block pool. Same
    mode-mask semantics; both passes dereference the same block
    tables."""
    _, cache = paged_prefill_chunk(params, cfg, tokens, cache,
                                   block_tables, pos, lengths,
                                   parallel=parallel)
    logits, cache = paged_decode_step(params, cfg, decode_tokens, cache,
                                      block_tables, pos,
                                      parallel=parallel,
                                      decode_impl=decode_impl,
                                      active=decode_active)
    return logits, cache


def prefill(params: Params, cfg: ModelConfig, batch: Dict,
            parallel=None, window: Optional[int] = None
            ) -> Tuple[jnp.ndarray, Params]:
    """Full-prompt prefill; returns (last-token logits, filled cache).

    For attention families the caches are rebuilt from the hidden states
    (recomputing K/V — one extra matmul per layer, which keeps the scan
    carry small); recurrent families return their final states.
    """
    tokens = batch["tokens"]
    b, s_len = tokens.shape
    cache = init_cache(cfg, b, batch.get("cache_len", s_len),
                       frontend_len=(batch["frontend"].shape[1]
                                     if "frontend" in batch else None))
    x, cache = _fill_cache(params, cfg, batch, cache, parallel, window)
    x = L.rmsnorm(x[:, -1:], params["final_ln"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits[:, 0], cache


def _fill_cache(params, cfg, batch, cache, parallel, window):
    """Re-run the stack storing K/V into the decode cache layout."""
    # NOTE: used by tests/examples at small scale; the dry-run decode
    # shapes start from a pre-filled cache via ShapeDtypeStruct.
    tokens = batch["tokens"]
    b, s_len = tokens.shape
    fam = cfg.family
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s_len), (b, s_len))
    w = cfg.attention_window if window is None else window
    eff = cache["kv"]["k"].shape[-3] if "kv" in cache and "k" in cache["kv"] \
        else s_len

    def store_kv(lp, x):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], cfg, h, h)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        v = v
        if eff < s_len:   # windowed ring buffer: keep last ``eff`` entries
            k, v = k[:, -eff:], v[:, -eff:]
            # ring layout: entry for absolute pos p sits at p % eff
            roll = (s_len % eff)
            k = jnp.roll(k, roll, axis=1)
            v = jnp.roll(v, roll, axis=1)
            return k, v
        pad = eff - s_len
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return k, v

    def pack_kv(k, v):
        if cfg.kv_cache_dtype == "int8":
            kq, ks = L.quantize_kv(k)
            vq, vs = L.quantize_kv(v)
            return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        return {"k": k, "v": v}

    if fam in (DENSE, MOE) and cfg.mla is None:
        def body(x, lp):
            k, v = store_kv(lp, x)
            x, _ = _decoder_block(lp, cfg, x, positions, parallel, w)
            return x, pack_kv(k, v)
        x, kv = _scan(body, x, params["layers"])
        cache["kv"] = kv
    elif fam in (DENSE, MOE):
        def body(x, lp):
            h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            a, c_kv, k_r = MLA.mla_attention(lp["attn"], cfg, h, positions)
            pad = cache["kv"]["c_kv"].shape[2] - s_len
            c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
            k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0)))
            x = x + a
            h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                if parallel is None:
                    m, _ = MOE_MOD.moe_block(lp["moe"], cfg, h, None)
                else:
                    m, _ = MOE_MOD.moe_block_sharded(lp["moe"], cfg, h,
                                                     parallel, mode="a2a")
                x = x + m
            else:
                x = x + L.mlp(lp["mlp"], cfg, h)
            return x, {"c_kv": c_kv, "k_r": k_r}
        x, kv = _scan(body, x, params["layers"])
        cache["kv"] = kv
    elif fam == VLM:
        memory = batch["frontend"]

        def group(x, lps):
            lp, cp = lps

            def inner(x, ilp):
                k, v = store_kv(ilp, x)
                x, _ = _decoder_block(ilp, cfg, x, positions, parallel, w)
                return x, {"k": k, "v": v}
            x, kv = _scan(inner, x, lp)
            h = L.rmsnorm(x, cp["ln"], cfg.norm_eps)
            _, xk, xv = L._qkv(cp["xattn"], cfg, h, memory)
            x = _cross_block(cp, cfg, x, memory)
            return x, (kv, xk, xv)
        x, (kv, xk, xv) = _scan(group, x,
                                       (params["layers"], params["cross"]))
        cache["kv"], cache["xk"], cache["xv"] = kv, xk, xv
    elif fam == ENCDEC:
        enc = _encode(params, cfg, batch["frontend"], parallel)

        def body(x, lp):
            k, v = store_kv(lp, x)
            x, _ = _decoder_block(lp, cfg, x, positions, parallel, w)
            h = L.rmsnorm(x, lp["lnx"], cfg.norm_eps)
            _, xk, xv = L._qkv(lp["xattn"], cfg, h, enc)
            x = x + L.cross_attention(lp["xattn"], cfg, h, enc)
            return x, ({"k": k, "v": v}, xk, xv)
        x, (kv, xk, xv) = _scan(body, x, params["layers"])
        cache["kv"], cache["xk"], cache["xv"] = kv, xk, xv
    elif fam == HYBRID:
        x, cache = _hybrid_fill(params, cfg, x, positions, cache, w)
    elif fam == SSM:
        x, cache = _ssm_fill(params, cfg, x, cache)
    return x, cache


def _hybrid_fill(params, cfg, x, positions, cache, w):
    every = cfg.ssm.shared_attn_every
    n_groups, rem = divmod(cfg.num_layers, every)
    assert rem == 0 or True
    sp = params["shared_attn"]
    b, s_len = x.shape[0], x.shape[1]
    eff = cache["kv"]["k"].shape[2]

    def mamba_layer(x, lp):
        h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
        y, hf = S.mamba2_forward(lp["mamba"], cfg, h)
        # conv state: last 3 pre-conv features
        z, xbc, dt = S._split_proj(lp["mamba"], cfg, h)
        conv_state = xbc[:, -3:]
        return x + y, {"h": hf, "conv": conv_state}

    def group(x, lps):
        x, st = _scan(mamba_layer, x, lps)
        h = L.rmsnorm(x, sp["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(sp["attn"], cfg, h, h)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        pad = eff - s_len
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        x = _shared_attn_block(sp, cfg, x, positions, w)
        return x, (st, {"k": k, "v": v})

    if rem == 0:
        x, (st, kv) = _scan(group, x, params["layers"])
        cache["ssm"], cache["kv"] = st, kv
    else:
        flat = params["layers"]
        grouped = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(n_groups, every,
                                                    *a.shape[1:]), flat)
        tail = jax.tree.map(lambda a: a[n_groups * every:], flat)
        x, (st, kv) = _scan(group, x, grouped)
        x, st_tail = _scan(mamba_layer, x, tail)
        cache["ssm"] = jax.tree.map(
            lambda a, b_: jnp.concatenate([a.reshape(-1, *a.shape[2:]), b_]),
            st, st_tail)
        cache["kv"] = kv
    return x, cache


def _ssm_fill(params, cfg, x, cache):
    pattern = cfg.ssm.block_pattern or ("mlstm",)

    def group(x, lps):
        states = {}
        for i, kind in enumerate(pattern):
            lp = lps[f"blk{i}_{kind}"]
            h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
            fwd = S.mlstm_forward if kind == "mlstm" else S.slstm_forward
            y, st = fwd(lp["core"], cfg, h)
            states[f"blk{i}_{kind}"] = st
            x = x + y
        return x, states
    x, states = _scan(group, x, params["layers"])
    cache["ssm"] = states
    return x, cache


def decode_step(params: Params, cfg: ModelConfig, token, cache: Params,
                pos, parallel=None,
                window: Optional[int] = None,
                decode_impl: str = "xla",
                active=None) -> Tuple[jnp.ndarray, Params]:
    """token: (B,1) int32; pos: scalar int (uniform across batch) or
    (B,) per-row positions (continuous batching). ``active``: optional
    (B,) bool mask — rows with active=False are provable no-ops on the
    cache (bit-identical rows out), the invariant the serving engine
    relies on for empty / mid-prefill slots. Their logits are garbage
    and must be ignored by the caller.
    Returns (logits (B,V), new cache)."""
    b = token.shape[0]
    x = params["embed"][token]
    w = cfg.attention_window if window is None else window
    fam = cfg.family
    pos = jnp.asarray(pos, jnp.int32)

    def attn_decode(lp, x, kv):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.mla is not None:
            a, ckv, kr = MLA.mla_decode(lp["attn"], cfg, h, kv["c_kv"],
                                        kv["k_r"], pos, window=w or 0,
                                        active=active)
            new = {"c_kv": ckv, "k_r": kr}
        else:
            a, new = L.decode_attention(lp["attn"], cfg, h, kv, pos,
                                        window=w or 0,
                                        decode_impl=decode_impl,
                                        active=active)
        x = x + a
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            if parallel is None:
                m, _ = MOE_MOD.moe_block(lp["moe"], cfg, h, None)
            else:
                m, _ = MOE_MOD.moe_block_sharded(lp["moe"], cfg, h, parallel,
                                                 mode="psum")
            x = x + m
        else:
            x = x + L.mlp(lp["mlp"], cfg, h)
        return x, new

    if fam in (DENSE, MOE):
        def body(x, inp):
            lp, kv = inp
            return attn_decode(lp, x, kv)
        x, kv = _scan(body, x, (params["layers"], cache["kv"]))
        cache = dict(cache, kv=kv)
    elif fam == VLM:
        def group(x, inp):
            lp, cp, kv, xk, xv = inp

            def inner(x, ii):
                ilp, ikv = ii
                return attn_decode(ilp, x, ikv)
            x, kv = _scan(inner, x, (lp, kv))
            h = L.rmsnorm(x, cp["ln"], cfg.norm_eps)
            q = (h @ cp["xattn"]["wq"]).reshape(b, 1, cfg.num_heads, -1)
            a = L._sdpa(q, xk, xv, None, cfg.q_per_kv) @ cp["xattn"]["wo"]
            x = x + jnp.tanh(cp["gate"]) * a
            h = L.rmsnorm(x, cp["ln_mlp"], cfg.norm_eps)
            x = x + jnp.tanh(cp["gate_mlp"]) * L.mlp(cp["mlp"], cfg, h)
            return x, kv
        x, kv = _scan(group, x, (params["layers"], params["cross"],
                                        cache["kv"], cache["xk"],
                                        cache["xv"]))
        cache = dict(cache, kv=kv)
    elif fam == ENCDEC:
        def body(x, inp):
            lp, kv, xk, xv = inp
            x, new = attn_decode(lp, x, kv)
            h = L.rmsnorm(x, lp["lnx"], cfg.norm_eps)
            q = (h @ lp["xattn"]["wq"]).reshape(b, 1, cfg.num_heads, -1)
            a = L._sdpa(q, xk, xv, None, cfg.q_per_kv) @ lp["xattn"]["wo"]
            x = x + a
            return x, new
        x, kv = _scan(body, x, (params["layers"], cache["kv"],
                                       cache["xk"], cache["xv"]))
        cache = dict(cache, kv=kv)
    elif fam == HYBRID:
        x, cache = _hybrid_decode(params, cfg, x, cache, pos, w, active)
    elif fam == SSM:
        x, cache = _ssm_decode(params, cfg, x, cache, active)

    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    head = params.get("lm_head")
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits[:, 0], cache


def _mask_state(new, old, active):
    """Blend recurrent-state pytrees along the leading batch axis:
    inactive rows keep their old state bit-for-bit."""
    if active is None:
        return new
    def blend(n, o):
        m = active.reshape((-1,) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(blend, new, old)


def prefill_chunk(params: Params, cfg: ModelConfig, tokens, cache: Params,
                  start_pos, lengths, parallel=None,
                  window: Optional[int] = None,
                  decode_impl: str = "xla") -> Tuple[jnp.ndarray, Params]:
    """Batched multi-slot chunked prefill — one fixed-shape call
    advances EVERY slot with a pending chunk by up to L tokens
    (Sarathi-style chunked prefill; paper §3.1's ceil(L_in/C_chunk)
    prefill iterations).

    tokens: (B, L) int32, one zero-padded chunk per batch row, where L
    is the padded bucket length (the trace count is bounded by the
    number of buckets, not by the request-length mix);
    start_pos: (B,) absolute position of each chunk's first token;
    lengths: (B,) valid tokens per row — rows with lengths == 0 are
    provable bitwise no-ops on the cache.

    Returns (last_logits (B, V), cache). last_logits holds each row's
    logits after its final valid token (garbage for idle rows).

    Dense/MoE full-attention models run a fused sequence-level chunk
    (the whole chunk attends the cache + itself causally in one pass);
    other families (MLA, VLM, enc-dec, windowed ring buffers, SSM)
    fall back to a masked per-token decode scan inside the same
    fixed-shape trace.
    """
    b, l = tokens.shape
    w = cfg.attention_window if window is None else window
    start_pos = jnp.asarray(start_pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if cfg.family in (DENSE, MOE) and cfg.mla is None and not w:
        return _prefill_chunk_fused(params, cfg, tokens, cache, start_pos,
                                    lengths, parallel)

    def body(carry, t):
        cache, logits = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, 1)
        act = t < lengths
        lg, cache = decode_step(params, cfg, tok, cache, start_pos + t,
                                parallel=parallel, window=window,
                                decode_impl=decode_impl, active=act)
        logits = jnp.where(act[:, None], lg, logits)
        return (cache, logits), None

    (cache, logits), _ = jax.lax.scan(
        body, (cache, jnp.zeros((b, cfg.vocab_size), jnp.dtype(cfg.dtype))),
        jnp.arange(l))
    return logits, cache


def verify_step(params: Params, cfg: ModelConfig, tokens, cache: Params,
                start_pos, lengths, parallel=None,
                window: Optional[int] = None,
                decode_impl: str = "xla") -> Tuple[jnp.ndarray, Params]:
    """Speculative multi-token verify (DESIGN.md §Speculative decoding):
    advance every row by its [last_tok, draft_1..draft_w] window in ONE
    call and return the logits at EVERY window position, so the caller
    can accept the longest draft prefix matching the model's own greedy
    argmax.

    Exactly the masked :func:`prefill_chunk` machinery — same fused
    sequence-level chunk for dense/MoE full attention, same masked
    per-token decode scan for the other families, same ``lengths == 0
    => bitwise no-op`` idle-row invariant — except the LM head runs
    over all L positions instead of gathering the last one. Rejected
    positions' KV entries are dead weight the next write at that
    position fully overwrites (layers.write_chunk_kv contract), so a
    failed draft costs nothing but the wasted FLOPs.

    tokens: (B, L); start_pos/lengths: (B,). Returns
    (logits (B, L, V), cache); logits rows beyond ``lengths`` and idle
    rows are garbage the caller must mask.
    """
    b, l = tokens.shape
    w = cfg.attention_window if window is None else window
    start_pos = jnp.asarray(start_pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if cfg.family in (DENSE, MOE) and cfg.mla is None and not w:
        return _prefill_chunk_fused(params, cfg, tokens, cache, start_pos,
                                    lengths, parallel, all_logits=True)

    def body(carry, t):
        cache, buf = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, 1)
        act = t < lengths
        lg, cache = decode_step(params, cfg, tok, cache, start_pos + t,
                                parallel=parallel, window=window,
                                decode_impl=decode_impl, active=act)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, lg[:, None].astype(buf.dtype), t, axis=1)
        return (cache, buf), None

    buf0 = jnp.zeros((b, l, cfg.vocab_size), jnp.dtype(cfg.dtype))
    (cache, buf), _ = jax.lax.scan(body, (cache, buf0), jnp.arange(l))
    return buf, cache


def paged_verify_step(params: Params, cfg: ModelConfig, tokens,
                      cache: Params, block_tables, start_pos, lengths,
                      parallel=None) -> Tuple[jnp.ndarray, Params]:
    """Paged analog of :func:`verify_step`: the
    :func:`paged_prefill_chunk` pass with the LM head over all window
    positions. Returns (logits (B, L, V), cache)."""
    return paged_prefill_chunk(params, cfg, tokens, cache, block_tables,
                               start_pos, lengths, parallel=parallel,
                               all_logits=True)


def _prefill_chunk_fused(params, cfg, tokens, cache, start_pos, lengths,
                         parallel, all_logits=False):
    """Sequence-level chunk prefill for contiguous-cache dense/MoE
    attention: write the chunk's K/V into the batched cache in place,
    then attend chunk queries over (cache prefix + chunk) causally.
    ``all_logits=True`` (the speculative verify path) returns the LM
    head over every chunk position, (B, L, V), instead of the per-row
    last valid position."""
    b, l = tokens.shape
    x = params["embed"][tokens]                          # (B, L, D)
    positions = start_pos[:, None] + jnp.arange(l)[None, :]

    def body(x, inp):
        lp, kv = inp
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(lp["attn"], cfg, h, h)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kv = L.write_chunk_kv(kv, k, v, start_pos, lengths)
        if "k_scale" in kv:
            k_all = L.dequantize_kv(kv["k"], kv["k_scale"])
            v_all = L.dequantize_kv(kv["v"], kv["v_scale"])
        else:
            k_all, v_all = kv["k"], kv["v"]
        s_max = k_all.shape[1]
        # query at absolute position p sees cache entries j <= p: the
        # already-filled prefix plus this chunk's own causal triangle
        # (both live in the cache after write_chunk_kv).
        valid = jnp.arange(s_max)[None, None, :] <= positions[:, :, None]
        a = L._sdpa(q, k_all, v_all, valid, cfg.q_per_kv)
        x = x + a @ lp["attn"]["wo"]
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            if parallel is None:
                m, _ = MOE_MOD.moe_block(lp["moe"], cfg, h, None)
            else:
                m, _ = MOE_MOD.moe_block_sharded(lp["moe"], cfg, h, parallel,
                                                 mode="a2a")
            x = x + m
        else:
            x = x + L.mlp(lp["mlp"], cfg, h)
        return x, kv

    x, kv = _scan(body, x, (params["layers"], cache["kv"]))
    cache = dict(cache, kv=kv)
    head = params.get("lm_head")
    if all_logits:
        # speculative verify: the caller needs the greedy continuation
        # at EVERY window position to score its draft tokens
        x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
        return (x @ head if head is not None
                else x @ params["embed"].T), cache
    # gather each row's final valid hidden state BEFORE the LM head so
    # the (vocab) projection runs over 1 position per row, not L
    last = jnp.clip(lengths - 1, 0, l - 1)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = L.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = x @ head if head is not None else x @ params["embed"].T
    return logits[:, 0], cache


def _hybrid_decode(params, cfg, x, cache, pos, w, active=None):
    every = cfg.ssm.shared_attn_every
    n_groups, rem = divmod(cfg.num_layers, every)
    sp = params["shared_attn"]

    def mamba_layer(x, inp):
        lp, st = inp
        h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
        y, st2 = S.mamba2_decode(lp["mamba"], cfg, h, st)
        return x + y, _mask_state(st2, st, active)

    def group(x, inp):
        lps, st, kv = inp
        x, st2 = _scan(mamba_layer, x, (lps, st))
        h = L.rmsnorm(x, sp["ln1"], cfg.norm_eps)
        a, kv2 = L.decode_attention(sp["attn"], cfg, h, kv, pos,
                                    window=w or 0, active=active)
        x = x + a
        h = L.rmsnorm(x, sp["ln2"], cfg.norm_eps)
        x = x + L.mlp(sp["mlp"], cfg, h)
        return x, (st2, kv2)

    if rem == 0:
        x, (st, kv) = _scan(group, x,
                                   (params["layers"], cache["ssm"],
                                    cache["kv"]))
        cache = dict(cache, ssm=st, kv=kv)
    else:
        flat = params["layers"]
        grouped = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(n_groups, every,
                                                    *a.shape[1:]), flat)
        tail = jax.tree.map(lambda a: a[n_groups * every:], flat)
        st_flat = cache["ssm"]
        st_g = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(n_groups, every,
                                                    *a.shape[1:]), st_flat)
        st_t = jax.tree.map(lambda a: a[n_groups * every:], st_flat)
        x, (st2, kv) = _scan(group, x, (grouped, st_g, cache["kv"]))
        x, st_t2 = _scan(mamba_layer, x, (tail, st_t))
        st_new = jax.tree.map(
            lambda a, b_: jnp.concatenate([a.reshape(-1, *a.shape[2:]), b_]),
            st2, st_t2)
        cache = dict(cache, ssm=st_new, kv=kv)
    return x, cache


def _ssm_decode(params, cfg, x, cache, active=None):
    pattern = cfg.ssm.block_pattern or ("mlstm",)

    def group(x, inp):
        lps, sts = inp
        new = {}
        for i, kind in enumerate(pattern):
            key = f"blk{i}_{kind}"
            lp, st = lps[key], sts[key]
            h = L.rmsnorm(x, lp["ln"], cfg.norm_eps)
            fn = S.mlstm_decode if kind == "mlstm" else S.slstm_decode
            y, st2 = fn(lp["core"], cfg, h, st)
            new[key] = _mask_state(st2, st, active)
            x = x + y
        return x, new
    x, st = _scan(group, x, (params["layers"], cache["ssm"]))
    return x, dict(cache, ssm=st)
