"""Paged flash-decode GQA attention Pallas kernel (TPU target).

Decode attention over a PAGED KV cache: K/V live in a shared pool of
fixed-size blocks (``(BLOCK_S, head_dim)`` tiles per kv head) and each
slot owns a *block table* mapping its logical block index to a physical
block id. Grid = (slot, kv_head, logical_block); the logical-block axis
is innermost so the online-softmax accumulators (m, l, acc) live in
VMEM scratch across the sweep, exactly like the contiguous
``gqa_decode`` kernel — the only change is WHERE each K/V tile comes
from: the block table is a scalar-prefetch operand
(``PrefetchScalarGridSpec``), so the BlockSpec index_map dereferences
``block_tables[slot, logical_block]`` to pick the physical tile to DMA
into VMEM. No contiguous per-slot cache row exists anywhere.

This is the runtime analog of the paper's "hard hardware boundary ->
software parameter" move: the dense engine reserves a worst-case
``(n_max, c_max)`` row per slot, while the paged pool sizes HBM for
the *actual* length mix (profiles.n_max_paged) and the block table
absorbs the indirection.

Validated in interpret mode against
``repro.kernels.ref.paged_gqa_decode_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(bt_ref, sl_ref, act_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                         blocks: int, block_s: int):
    b = pl.program_id(0)
    j = pl.program_id(2)                   # logical block index

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = sl_ref[b]                    # valid tokens for this slot
    active = act_ref[b] != 0
    base = j * block_s

    # Blocks fully past the slot's length carry no live KV: skip the
    # whole tile (their block-table entry may be stale/unallocated —
    # the index_map already clamped the DMA to a real physical block,
    # we just never look at the bytes).
    @pl.when(active & (base < seq_len))
    def _sweep():
        q = q_ref[0, 0]                    # (qpk, hd)
        k = k_ref[0, 0]                    # (block_s, hd)
        v = v_ref[0, 0]                    # (block_s, hd)
        offs = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        valid = offs < seq_len             # (1, block_s)

        s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)               # (qpk, block_s)

        m_prev = m_ref[...]                            # (qpk,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                # (qpk, block_s)
        p = jnp.where(valid, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jnp.dot(p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode(q, k_pages, v_pages, block_tables, seq_lens,
                     active=None, *, interpret: bool):
    """q: (B, H, hd); k_pages/v_pages: (P, BLOCK_S, Hkv, hd) shared
    physical block pool (token-major, the cache layout); block_tables:
    (B, NB) int32 logical->physical block map; seq_lens: (B,) int32
    valid tokens per slot (pos + 1); active: optional (B,) bool — rows
    with active=False skip the sweep entirely and return zeros.
    Returns (B, H*hd). ``interpret=True`` runs the kernel body in
    Python (CPU validation); ``interpret=False`` lowers it for the TPU."""
    b, h, hd = q.shape
    p_blocks, block_s, hkv = k_pages.shape[0], k_pages.shape[1], \
        k_pages.shape[2]
    nb = block_tables.shape[1]
    qpk = h // hkv
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, hkv, qpk, hd)
    kt = jnp.swapaxes(k_pages, 1, 2)       # (P, Hkv, BLOCK_S, hd)
    vt = jnp.swapaxes(v_pages, 1, 2)
    bt = jnp.clip(block_tables.astype(jnp.int32), 0, p_blocks - 1)
    sl = seq_lens.astype(jnp.int32)
    if active is None:
        act = jnp.ones((b,), jnp.int32)
    else:
        act = active.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # block_tables, seq_lens, active
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, qpk, hd),
                         lambda b_, h_, j_, bt_, sl_, act_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, j_, bt_, sl_, act_:
                         (bt_[b_, j_], h_, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, j_, bt_, sl_, act_:
                         (bt_[b_, j_], h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qpk, hd),
                               lambda b_, h_, j_, bt_, sl_, act_:
                               (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk,), jnp.float32),
            pltpu.VMEM((qpk, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, blocks=nb,
                          block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, qpk, hd), q.dtype),
        interpret=interpret,
        name="paged_gqa_decode",
    )(bt, sl, act, qg, kt, vt)
    return out.reshape(b, h * hd)
