"""Shared transformer building blocks (pure functional JAX).

Params are nested dicts of jnp arrays; layer stacks are stored with a
leading layer axis and executed with jax.lax.scan so compile time is
independent of depth. Attention supports full-causal, sliding-window,
GQA, QKV bias, cross-attention, and single-token decode against a KV
cache (contiguous or ring-buffer for windows).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]

NEG_INF = -1e30  # large-negative mask value (bf16-safe)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float = 1.0):
    std = scale / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32)
            * std).astype(dtype)


def stack_init(key, n: int, init_fn):
    """Initialize ``n`` layers with split keys and stack along axis 0."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm(x, gamma, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * gamma


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                     # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------
def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "squared_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name!r}")


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"down": dense_init(ks[2], d_ff, cfg.d_model, dtype)}
    if cfg.activation == "squared_relu":     # no gate branch (nemotron)
        p["up"] = dense_init(ks[0], cfg.d_model, d_ff, dtype)
    else:
        p["up"] = dense_init(ks[0], cfg.d_model, d_ff, dtype)
        p["gate"] = dense_init(ks[1], cfg.d_model, d_ff, dtype)
    return p


def mlp(p: Params, cfg: ModelConfig, x):
    act = activation_fn(cfg.activation)
    h = x @ p["up"]
    if "gate" in p:
        h = act(x @ p["gate"]) * h
    else:
        h = act(h)
    return h @ p["down"]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, dtype, scale=0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, x_kv):
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x_kv @ p["wk"]
    v = x_kv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*x.shape[:-1], cfg.num_heads, hd)
    k = k.reshape(*x_kv.shape[:-1], cfg.num_kv_heads, hd)
    v = v.reshape(*x_kv.shape[:-1], cfg.num_kv_heads, hd)
    return q, k, v


def _sdpa(q, k, v, mask, q_per_kv: int):
    """q: (B,S,H,hd); k,v: (B,T,Hkv,hd); mask: (B|1, S, T) bool or None."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    q = q.reshape(b, s, hkv, q_per_kv, hd)
    scores = jnp.einsum("bsgqd,btgd->bgqst", q, k) / math.sqrt(hd)
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgqst,btgd->bsgqd", w, v)
    return out.reshape(b, s, h * hd)


def causal_mask(s: int, t: int, window: int = 0, offset: int = 0):
    """(s, t) boolean mask; query i attends key j iff j <= i+offset and,
    with a window, i+offset - j < window."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window > 0:
        m &= (qi - kj) < window
    return m


# Block-causal "flash" prefill: above this sequence length, causal
# self-attention runs chunked with online softmax, touching only the
# lower-triangle (i >= j) chunk pairs — ~2x fewer attention FLOPs/bytes
# and no (B,H,S,S) f32 score materialization (§Perf pair D). The
# chunked path uses a dynamic-bound fori_loop, which is not
# reverse-differentiable — training shapes (S=4096) stay below the
# threshold; prefill/serving paths are forward-only.
FLASH_MIN_SEQ = 8192
FLASH_CHUNK = 2048


def attention(p: Params, cfg: ModelConfig, x, positions=None,
              window: Optional[int] = None):
    """Full (or sliding-window) causal self-attention over a sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(p, cfg, x, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    w = cfg.attention_window if window is None else window
    if s >= FLASH_MIN_SEQ and s % FLASH_CHUNK == 0:
        out = _flash_causal(q, k, v, cfg.q_per_kv, w)
    else:
        mask = causal_mask(s, s, w)[None]
        out = _sdpa(q, k, v, mask, cfg.q_per_kv)
    return out @ p["wo"]


def _flash_causal(q, k, v, q_per_kv: int, window: int = 0,
                  chunk: int = 0):
    """Chunked causal attention with online softmax.

    q/k/v: (B, S, H|Hkv, hd). Outer scan over query chunks; inner
    dynamic-bound fori_loop over only the key chunks each query chunk
    can see (block-lower-triangle, window-clipped)."""
    b, s, h, hd = q.shape
    chunk = chunk or min(FLASH_CHUNK, s)   # module var read at call time
    hkv = k.shape[2]
    hd_v = v.shape[-1]
    qpk = q_per_kv
    assert s % chunk == 0, (s, chunk)
    nq = s // chunk
    qg = q.reshape(b, s, hkv, qpk, hd)
    scale = 1.0 / math.sqrt(hd)

    def q_block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, 1)
        m0 = jnp.full((b, hkv, qpk, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, qpk, chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, qpk, chunk, hd_v), jnp.float32)

        def kv_body(j, state):
            m, l, acc = state
            kj = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, 1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, 1)
            sco = jnp.einsum("bqgpd,bkgd->bgpqk", qi, kj) * scale
            sco = sco.astype(jnp.float32)
            qpos = i * chunk + jnp.arange(chunk)
            kpos = j * chunk + jnp.arange(chunk)
            valid = kpos[None, :] <= qpos[:, None]
            if window > 0:
                valid &= (qpos[:, None] - kpos[None, :]) < window
            sco = jnp.where(valid[None, None, None], sco, NEG_INF)
            m_new = jnp.maximum(m, sco.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pij = jnp.exp(sco - m_new[..., None])
            l_new = l * alpha + pij.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bgpqk,bkgd->bgpqd", pij.astype(vj.dtype), vj
            ).astype(jnp.float32)
            return m_new, l_new, acc_new

        lo = jnp.maximum(0, (i * chunk - window) // chunk) if window > 0 \
            else 0
        m, l, acc = jax.lax.fori_loop(lo, i + 1, kv_body, (m0, l0, a0))
        out_i = acc / jnp.maximum(l, 1e-30)[..., None]
        # (b, hkv, qpk, chunk, hd_v) -> (b, chunk, h*hd_v)
        out_i = jnp.moveaxis(out_i, 3, 1).reshape(b, chunk, h * hd_v)
        return None, out_i.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))
    # (nq, b, chunk, h*hd_v) -> (b, s, h*hd_v)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h * hd_v)


def cross_attention(p: Params, cfg: ModelConfig, x, memory):
    """Encoder-decoder / VLM cross-attention (no rope, no mask)."""
    q, k, v = _qkv(p, cfg, x, memory)
    out = _sdpa(q, k, v, None, cfg.q_per_kv)
    return out @ p["wo"]


# -- decode path ------------------------------------------------------------
def cache_zeros(shape, dtype, sharding=None):
    """Zero cache buffer, created DIRECTLY under ``sharding`` (a
    jax.sharding.Sharding or None): a sharded serving cache must never
    materialize replicated first — at real sizes the replicated
    intermediate alone would OOM the very HBM the sharding buys."""
    if sharding is None:
        return jnp.zeros(shape, dtype)
    return jnp.zeros(shape, dtype, device=sharding)


def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, max_seq: int,
                  dtype=None, shardings=None) -> Params:
    """Contiguous KV cache; for windowed attention ``max_seq`` should be
    the window size (ring buffer). With cfg.kv_cache_dtype == "int8"
    the cache halves: int8 values + per-(seq, head) bf16 scales.
    ``shardings``: optional per-leaf dict (keys "k"/"v"/"k_scale"/
    "v_scale") of jax shardings — the serving engine passes its
    kv-head-sharded NamedShardings (distributed/sharding.py
    ``serving_cache_specs``)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    sh = shardings or {}
    hd = cfg.resolved_head_dim
    shape = (n_layers, batch, max_seq, cfg.num_kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        sshape = shape[:-1]
        return {"k": cache_zeros(shape, jnp.int8, sh.get("k")),
                "v": cache_zeros(shape, jnp.int8, sh.get("v")),
                "k_scale": cache_zeros(sshape, dtype, sh.get("k_scale")),
                "v_scale": cache_zeros(sshape, dtype, sh.get("v_scale"))}
    return {"k": cache_zeros(shape, dtype, sh.get("k")),
            "v": cache_zeros(shape, dtype, sh.get("v"))}


def quantize_kv(x):
    """x: (..., hd) -> (int8 values, scales (...,))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(x.dtype)


def dequantize_kv(q, scale):
    return q.astype(scale.dtype) * scale[..., None]


def decode_attention(p: Params, cfg: ModelConfig, x, kv, pos,
                     window: int = 0, decode_impl: str = "xla",
                     active=None):
    """Single-token decode. x: (B,1,D); kv: cache dict with "k"/"v"
    (B,S,Hkv,hd) and optional int8 "k_scale"/"v_scale"; pos: (B,) or
    scalar absolute position of the new token. ``active``: optional (B,)
    bool — rows with active=False leave their cache row BIT-IDENTICAL
    (the continuous-batching invariant: empty / mid-prefill slots must
    never see spurious KV writes). Returns (out, new_kv)."""
    b = x.shape[0]
    k_cache, v_cache = kv["k"], kv["v"]
    quant = "k_scale" in kv
    s_max = k_cache.shape[1]
    pos = jnp.asarray(pos)
    # all sequences at the same position AND no mask: O(1) slice write
    uniform = pos.ndim == 0 and active is None
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    q, k, v = _qkv(p, cfg, x, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    slot = pos % s_max if window > 0 else pos
    new_kv = dict(kv)
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        if uniform:
            dus = jax.lax.dynamic_update_slice_in_dim
            new_kv["k"] = dus(k_cache, kq, slot[0], 1)
            new_kv["v"] = dus(v_cache, vq, slot[0], 1)
            new_kv["k_scale"] = dus(kv["k_scale"], ks, slot[0], 1)
            new_kv["v_scale"] = dus(kv["v_scale"], vs, slot[0], 1)
        else:
            new_kv["k"] = _scatter_slot(k_cache, kq[:, 0], slot, active)
            new_kv["v"] = _scatter_slot(v_cache, vq[:, 0], slot, active)
            new_kv["k_scale"] = _scatter_scalar(kv["k_scale"], ks[:, 0],
                                                slot, active)
            new_kv["v_scale"] = _scatter_scalar(kv["v_scale"], vs[:, 0],
                                                slot, active)
        k_cache = dequantize_kv(new_kv["k"], new_kv["k_scale"])
        v_cache = dequantize_kv(new_kv["v"], new_kv["v_scale"])
    elif uniform:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot[0], 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot[0], 1)
        new_kv["k"], new_kv["v"] = k_cache, v_cache
    else:
        k_cache = _scatter_slot(k_cache, k[:, 0], slot, active)
        v_cache = _scatter_slot(v_cache, v[:, 0], slot, active)
        new_kv["k"], new_kv["v"] = k_cache, v_cache
    # validity: absolute position of cache entry j
    j = jnp.arange(s_max)[None, :]
    if window > 0:
        age = (slot[:, None] - j) % s_max
        valid = (age < jnp.minimum(pos[:, None] + 1, window))
    else:
        valid = j <= pos[:, None]
    if decode_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.gqa_decode(q[:, 0], k_cache, v_cache, valid, active)
        out = out.reshape(b, 1, -1)
    else:
        out = _sdpa(q, k_cache, v_cache, valid[:, None, :], cfg.q_per_kv)
    return out @ p["wo"], new_kv


def _masked_write_idx(slot, s_max, active):
    """Per-row write index with the OOB-drop masking idiom (same as the
    paged pool's scatters): inactive rows write at index ``s_max``,
    which ``mode="drop"`` discards — their cache row stays untouched,
    a bitwise no-op by construction rather than by arithmetic."""
    if active is None:
        return slot
    return jnp.where(active, slot, s_max)


def _scatter_scalar(cache, new, slot, active=None):
    """cache: (B,S,H); new: (B,H); slot: (B,)."""
    idx = _masked_write_idx(slot, cache.shape[1], active)
    b_idx = jnp.arange(cache.shape[0])
    return cache.at[b_idx, idx].set(new, mode="drop")


def _scatter_slot(cache, new, slot, active=None):
    """cache: (B,S,H,hd); new: (B,H,hd); slot: (B,) -> write per batch.
    One indexed scatter-set per call — NOT a one-hot blend over the
    whole cache (the blend read-modify-writes every (S, H, hd) entry of
    every row per layer per decode step; the scatter touches one
    position per row). ``active`` masks rows out via the dropped
    out-of-range index, leaving them bit-identical."""
    idx = _masked_write_idx(slot, cache.shape[1], active)
    b_idx = jnp.arange(cache.shape[0])
    return cache.at[b_idx, idx].set(new, mode="drop")


# -- paged KV cache ---------------------------------------------------------
def init_paged_kv_cache(cfg: ModelConfig, n_layers: int, num_blocks: int,
                        block_size: int, dtype=None,
                        shardings=None) -> Params:
    """Paged KV cache: a shared pool of ``num_blocks`` physical blocks
    of ``block_size`` tokens each, per layer. No per-slot rows exist —
    slots own blocks through a host-side block table (serving engine).
    Layout (n_layers, num_blocks, block_size, Hkv, hd) keeps the
    per-token tail identical to the contiguous cache, so the gather
    ``pages[block_table]`` reproduces a dense row bit-for-bit.
    ``shardings``: optional {"k": ..., "v": ...} jax shardings (the
    sharded serving engine's kv-head-split pool)."""
    if cfg.kv_cache_dtype == "int8":
        raise NotImplementedError("paged KV cache is fp-only for now "
                                  "(int8 scales need a paged layout too)")
    dtype = dtype or jnp.dtype(cfg.dtype)
    sh = shardings or {}
    hd = cfg.resolved_head_dim
    shape = (n_layers, num_blocks, block_size, cfg.num_kv_heads, hd)
    return {"k": cache_zeros(shape, dtype, sh.get("k")),
            "v": cache_zeros(shape, dtype, sh.get("v"))}


def _paged_flat(pages):
    """(P, BLOCK_S, Hkv, hd) -> (P * BLOCK_S, Hkv, hd) token view."""
    p, bs = pages.shape[0], pages.shape[1]
    return pages.reshape(p * bs, *pages.shape[2:]), p, bs


def paged_scatter_tokens(pages, new, flat_idx):
    """Scatter per-token K/V entries into the physical block pool.

    pages: (P, BLOCK_S, Hkv, hd); new: (N, Hkv, hd); flat_idx: (N,)
    flattened physical token index (phys_block * BLOCK_S + offset).
    Out-of-range indices are DROPPED — the masking mechanism: inactive
    rows / padding tokens carry index P*BLOCK_S and the pool stays
    bit-identical (the continuous-batching invariant, paged edition).
    """
    flat, p, bs = _paged_flat(pages)
    flat = flat.at[flat_idx].set(new, mode="drop")
    return flat.reshape(pages.shape)


def gather_pages(pages, block_tables):
    """Materialize per-slot contiguous rows from the block pool:
    (P, BLOCK_S, Hkv, hd) x (B, NB) -> (B, NB*BLOCK_S, Hkv, hd).
    Entry j of a row is the slot's absolute position j, exactly the
    dense cache layout, so downstream attention math is unchanged.
    Rows may ALIAS: with the engine's ref-counted prefix cache, many
    slots' tables point at the same physical prefix blocks — the
    gather simply materializes the shared KV into each row, which is
    why prefix sharing needs no kernel changes (the Pallas paged
    kernel dereferences the same tables via its index maps)."""
    b, nb = block_tables.shape
    bs = pages.shape[1]
    bt = jnp.clip(block_tables, 0, pages.shape[0] - 1)
    return pages[bt].reshape(b, nb * bs, *pages.shape[2:])


def gather_blocks(pages, idx):
    """Device-side gather of exactly the named physical blocks from an
    engine-level paged cache leaf: (L, P, BLOCK_S, ...) x (NB,) ->
    (L, NB, BLOCK_S, ...). This is the swap-OUT half of the host-offload
    KV tier (DESIGN.md §Overload survival): a preempted slot's
    block-table entries are copied device->host verbatim, so a later
    swap-in restores bit-identical KV whatever physical blocks it lands
    in."""
    return jnp.take(pages, idx, axis=1)


def scatter_blocks(pages, vals, idx):
    """Swap-IN half of the host-offload tier: write (L, NB, BLOCK_S,
    ...) block contents back into the pool at freshly allocated
    physical indices ``idx`` (NB,). The blocks were private to the
    preempted slot (shared prefix blocks re-enter through the prefix
    map, not through here — see engine._swap_in), so the overwrite
    can never clobber another slot's live KV."""
    return pages.at[:, idx].set(vals)


def gather_slot_row(leaf, s: int, axis: int):
    """Dense-cache analog of :func:`gather_blocks`: one slot's full
    cache row, (L, B, S, ...) -> (L, S, ...) at batch axis ``axis``."""
    return jax.lax.index_in_dim(leaf, s, axis, keepdims=False)


def scatter_slot_row(leaf, row, s: int, axis: int):
    """Dense-cache analog of :func:`scatter_blocks`: write a swapped
    row back into (possibly another) slot ``s`` at batch axis
    ``axis``."""
    idx = (slice(None),) * axis + (s,)
    return leaf.at[idx].set(row)


def paged_decode_attention(p: Params, cfg: ModelConfig, x, kv,
                           block_tables, pos, decode_impl: str = "xla",
                           active=None, window=None):
    """Single-token decode against a paged KV cache (one layer's block
    pool). x: (B,1,D); kv: {"k","v"} (P, BLOCK_S, Hkv, hd);
    block_tables: (B, NB) int32; pos: (B,) absolute position of the new
    token; window: None, or a static sliding window (the new token
    attends the last ``window`` positions, itself included). Math is
    identical to :func:`decode_attention` on the gathered pages, so
    paged decode reproduces dense decode token-for-token. Returns (out,
    new_kv)."""
    b = x.shape[0]
    p_blocks, bs = kv["k"].shape[0], kv["k"].shape[1]
    nb = block_tables.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    q, k, v = _qkv(p, cfg, x, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    logical = jnp.clip(pos // bs, 0, nb - 1)
    phys = jnp.take_along_axis(block_tables, logical[:, None], axis=1)[:, 0]
    flat_idx = phys * bs + pos % bs
    if active is not None:
        flat_idx = jnp.where(active, flat_idx, p_blocks * bs)   # dropped
    new_kv = {"k": paged_scatter_tokens(kv["k"], k[:, 0], flat_idx),
              "v": paged_scatter_tokens(kv["v"], v[:, 0], flat_idx)}
    if decode_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.paged_gqa_decode(q[:, 0], new_kv["k"], new_kv["v"],
                                    block_tables, pos + 1, active,
                                    window=window)
        out = out.reshape(b, 1, -1)
    else:
        k_all = gather_pages(new_kv["k"], block_tables)
        v_all = gather_pages(new_kv["v"], block_tables)
        valid = jnp.arange(nb * bs)[None, :] <= pos[:, None]
        if window is not None:
            valid &= jnp.arange(nb * bs)[None, :] > pos[:, None] - window
        out = _sdpa(q, k_all, v_all, valid[:, None, :], cfg.q_per_kv)
    return out @ p["wo"], new_kv


def write_chunk_kv_paged(kv: Params, k, v, block_tables, start,
                         lengths) -> Params:
    """Paged analog of :func:`write_chunk_kv`: write one prefill chunk
    per batch row into the block pool through the block table, one
    per-block dynamic scatter instead of a contiguous row update.

    kv: {"k","v"} (P, BLOCK_S, Hkv, hd); k/v: (B, L, Hkv, hd) new
    entries; start: (B,) first absolute position; lengths: (B,) valid
    tokens (0 => bitwise no-op row). Padding tokens scatter to the
    out-of-range index and are dropped."""
    b, l = k.shape[:2]
    p_blocks, bs = kv["k"].shape[0], kv["k"].shape[1]
    nb = block_tables.shape[1]
    pos = start[:, None] + jnp.arange(l)[None, :]             # (B, L)
    logical = jnp.clip(pos // bs, 0, nb - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, L)
    flat = phys * bs + pos % bs
    valid = jnp.arange(l)[None, :] < lengths[:, None]
    flat = jnp.where(valid, flat, p_blocks * bs).reshape(-1)
    new_k = paged_scatter_tokens(kv["k"], k.reshape(b * l, *k.shape[2:]),
                                 flat)
    new_v = paged_scatter_tokens(kv["v"], v.reshape(b * l, *v.shape[2:]),
                                 flat)
    return {"k": new_k, "v": new_v}


# -- chunked prefill (batched multi-slot) -----------------------------------
def write_chunk_kv(kv: Params, k, v, start, lengths) -> Params:
    """Blend-write one prefill chunk per batch row into contiguous KV
    caches at per-row offsets.

    kv: cache dict with "k"/"v" (B,S,Hkv,hd) (+ optional int8 scales);
    k/v: (B,L,Hkv,hd) new entries; start: (B,) first absolute position;
    lengths: (B,) valid token count (0 => that row is a bitwise no-op).

    Rows whose chunk is shorter than L keep the old cache contents at
    the padded positions, so a single padded-bucket trace serves every
    chunk length without corrupting neighbouring cache entries.

    Overwrite contract (speculative decoding relies on it): a write at
    position p REPLACES that cache entry completely — nothing is
    accumulated or ring-buffered at the full-attention offsets this
    function addresses. Entries at positions >= a row's current length
    are therefore dead weight: the causal mask (j <= position) hides
    them from every query until a later write at the same position
    replaces them. That is what makes a REJECTED draft token's KV entry
    harmless — the retried decode at that position overwrites it before
    any query can attend it. Windowed ring-buffer caches violate this
    (their modular offsets alias live history), which is why the engine
    refuses spec_k > 1 for attention_window configs.
    """
    new_kv = dict(kv)
    if "k_scale" in kv:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_kv["k"] = _blend_rows(kv["k"], kq, start, lengths)
        new_kv["v"] = _blend_rows(kv["v"], vq, start, lengths)
        new_kv["k_scale"] = _blend_rows(kv["k_scale"], ks, start, lengths)
        new_kv["v_scale"] = _blend_rows(kv["v_scale"], vs, start, lengths)
    else:
        new_kv["k"] = _blend_rows(kv["k"], k, start, lengths)
        new_kv["v"] = _blend_rows(kv["v"], v, start, lengths)
    return new_kv


def _blend_rows(cache, new, start, lengths):
    """Per-row dynamic_update_slice of ``new`` (B,L,...) into ``cache``
    (B,S,...) at offset ``start``, keeping old values where the token
    index >= lengths. Handles the start+L > S overhang (the final chunk
    of a near-capacity prompt) by clamping the window and rolling the
    chunk so every valid token still lands at its absolute position."""
    s_max, l = cache.shape[1], new.shape[1]

    def row(c, nw, st, ln):
        st_eff = jnp.clip(st, 0, s_max - l)
        shift = st - st_eff                       # >0 only on overhang
        rolled = jnp.roll(nw, shift, axis=0)
        w = jnp.arange(l)
        keep = (w >= shift) & ((w - shift) < ln)
        keep = keep.reshape((l,) + (1,) * (nw.ndim - 1))
        cur = jax.lax.dynamic_slice_in_dim(c, st_eff, l, 0)
        blended = jnp.where(keep, rolled, cur)
        return jax.lax.dynamic_update_slice_in_dim(c, blended, st_eff, 0)

    return jax.vmap(row)(cache, new, start, lengths)
