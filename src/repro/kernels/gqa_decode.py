"""Flash-decode GQA attention Pallas kernel (TPU target).

One decoded token per sequence attends over a (possibly ring-buffered)
KV cache. Grid = (batch, kv_heads, kv_blocks); the kv-block axis is
innermost so the online-softmax accumulators (m, l, acc) live in VMEM
scratch across the KV sweep and the output is written once on the last
block. Block shapes keep the MXU busy: the q tile is
(q_per_kv x head_dim) — all query heads of one KV group at once — and
K/V stream in (BLOCK_S x head_dim) tiles, 128-aligned.

This is the serving engine's decode hot spot (paper §3.1: decode
iterations dominate slot occupancy, E[S] ~ L_out * t_iter).
Validated in interpret mode against repro.kernels.ref.gqa_decode_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(act_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, blocks: int):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Continuous-batching mask: rows whose slot is mid-prefill or empty
    # skip the whole KV sweep — no flops spent, and the finalize below
    # emits exact zeros for them (the engine ignores those rows).
    active = act_ref[pl.program_id(0)] != 0

    @pl.when(active)
    def _sweep():
        q = q_ref[0, 0]                    # (qpk, hd)
        k = k_ref[0, 0]                    # (blk, hd)
        v = v_ref[0, 0]                    # (blk, hd)
        valid = valid_ref[0] != 0          # (1, blk)

        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                   # (qpk, blk)

        m_prev = m_ref[...]                                # (qpk,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                    # (qpk, blk)
        p = jnp.where(valid, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jnp.dot(p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(sb == blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def gqa_decode(q, k_cache, v_cache, valid, active=None, *,
               block_s: int = DEFAULT_BLOCK_S, interpret: bool):
    """q: (B, H, hd); k_cache/v_cache: (B, S, Hkv, hd); valid: (B, S)
    bool; active: optional (B,) bool — rows with active=False skip the
    KV sweep entirely and return zeros (continuous-batching no-op rows).
    Returns (B, H*hd). ``interpret=True`` runs the kernel body in
    Python (CPU validation); ``interpret=False`` lowers it for the TPU.

    A context that is not a multiple of ``block_s`` is padded up to one
    with invalid positions (the padding fuses into the head-major
    transpose copy); the ``valid`` mask rides as int32 (B, 1, S) so its
    block's last two dims meet the TPU's (8, 128) tiling rule."""
    b, h, hd = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    qpk = h // hkv
    block_s = min(block_s, s_max)
    pad = -s_max % block_s
    blocks = (s_max + pad) // block_s
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, hkv, qpk, hd)
    kt = jnp.swapaxes(k_cache, 1, 2)       # (B, Hkv, S, hd)
    vt = jnp.swapaxes(v_cache, 1, 2)
    vm = valid.astype(jnp.int32).reshape(b, 1, s_max)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vm = jnp.pad(vm, ((0, 0), (0, 0), (0, pad)))
    if active is None:
        act = jnp.ones((b,), jnp.int32)
    else:
        act = active.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,             # active
        grid=(b, hkv, blocks),
        in_specs=[
            pl.BlockSpec((1, 1, qpk, hd),
                         lambda b_, h_, s_, act_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, s_, act_: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, s_, act_: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_s),
                         lambda b_, h_, s_, act_: (b_, 0, s_)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, hd),
                               lambda b_, h_, s_, act_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blocks=blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, qpk, hd), q.dtype),
        interpret=interpret,
        name="gqa_decode",
    )(act, qg, kt, vt, vm)
    return out.reshape(b, h * hd)
