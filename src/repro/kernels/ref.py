"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def gqa_decode_ref(q, k_cache, v_cache, valid):
    """q: (B,H,hd); caches (B,S,Hkv,hd); valid (B,S). -> (B, H*hd)."""
    b, h, hd = q.shape
    hkv = k_cache.shape[2]
    qpk = h // hkv
    qg = q.reshape(b, hkv, qpk, hd).astype(jnp.float32)
    k = k_cache.astype(jnp.float32)
    v = v_cache.astype(jnp.float32)
    s = jnp.einsum("bgqd,bsgd->bgqs", qg, k) / math.sqrt(hd)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqs,bsgd->bgqd", w, v)
    return o.reshape(b, h * hd).astype(q.dtype)


def paged_gqa_decode_ref(q, k_pages, v_pages, block_tables, seq_lens,
                         window=None):
    """Paged decode oracle: gather each slot's blocks into a contiguous
    row through the block table, then run the dense reference.

    q: (B,H,hd); k_pages/v_pages: (P, BLOCK_S, Hkv, hd) physical block
    pool; block_tables: (B, NB) int32; seq_lens: (B,) valid tokens;
    window: None, or only a row's last ``window`` keys are valid.
    -> (B, H*hd)."""
    b, nb = block_tables.shape
    block_s = k_pages.shape[1]
    bt = jnp.clip(block_tables, 0, k_pages.shape[0] - 1)
    k = k_pages[bt].reshape(b, nb * block_s, *k_pages.shape[2:])
    v = v_pages[bt].reshape(b, nb * block_s, *v_pages.shape[2:])
    j = jnp.arange(nb * block_s)[None, :]
    valid = j < seq_lens[:, None]
    if window is not None:
        valid = valid & (j >= seq_lens[:, None] - window)
    return gqa_decode_ref(q, k, v, valid)


def expert_gmm_ref(x, w, tile_expert, live_tiles, tm):
    """Grouped-matmul oracle: row tile i (``tm`` rows of ``x``) times
    its expert's matrix ``w[tile_expert[i]]``, for the first
    ``live_tiles`` tiles; the rows of the other tiles are zeros (the
    kernel leaves them unset). -> (M, N) float32."""
    m = x.shape[0]
    tiles = x.reshape(m // tm, tm, x.shape[1]).astype(jnp.float32)
    out = jnp.einsum("tmk,tkn->tmn", tiles,
                     w[tile_expert].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    live = jnp.arange(m // tm) < live_tiles
    return jnp.where(live[:, None, None], out, 0.0).reshape(m, -1)


def textrank_ref(sim, damping: float = 0.85, iters: int = 30):
    """sim: (N, N) unpadded similarity matrix. -> (N,) PageRank."""
    n = sim.shape[0]
    w = sim.astype(jnp.float32) * (1.0 - jnp.eye(n))
    colsum = w.sum(axis=0)
    colsum = jnp.where(colsum <= 0.0, 1.0, colsum)
    wn = w / colsum[None, :]
    p = jnp.full((n,), 1.0 / n)
    for _ in range(iters):
        p = (1.0 - damping) / n + damping * (wn @ p)
    return p
