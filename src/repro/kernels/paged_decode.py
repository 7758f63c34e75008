"""Paged flash-decode GQA attention Pallas kernel (TPU target).

Decode attention over a PAGED KV cache: K/V live in a shared pool of
fixed-size pages, token-major ``(P, BLOCK_S, Hkv, hd)`` (the cache
layout: one page of every KV head is one contiguous run), and each slot
owns a *block table* mapping its logical page index to a physical page
id. No contiguous per-slot cache row, and no head-major copy of the
pool, exists anywhere.

The sweep touches live pages only. The grid is one step per slot. The
pool stays where it is (``memory_space=pl.ANY``) and the kernel copies
whole pages, all KV heads at once (``pltpu.make_async_copy`` on
``k_hbm.at[page]``), a compute block of several pages at a time, into
one of two VMEM buffers: the copy of the next block (the slot's next
one, or the first one of the next live slot) starts before the current
block is computed, so fetch and compute overlap across blocks and across
slots. A row of ``seq_len`` tokens copies ``ceil(seq_len / BLOCK_S)``
pages and reads no block-table entry past them; an inactive row copies
nothing and returns exact zeros; a partly filled last page, and the
unfilled pages of a last block, are masked per token.

The pages per compute block come from the shapes alone
(:func:`pages_per_block`): as many as fill ``BLOCK_BYTES`` of K (1,024
tokens of 8 bf16 heads x 128), at most the table's width: a block's
dots cost about the same whatever its size, so larger blocks spread that
cost over more keys, and two buffers each of K and V at this size take
8 MiB of VMEM. Inside a block each KV head's keys and values are picked
out of the token-major buffer by a strided load (bf16 pairs of heads
share one 32-bit load, and moving each half into the high bits is the
exact f32 upcast); the head's whole query group then takes one dot
against them. Scores, softmax state and accumulation are f32, as in the
oracle; only the summation order follows the block size.

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 2 << 20          # K bytes of one compute block (V the same)


def pages_per_block(nb: int, block_s: int, hkv: int, hd: int,
                    itemsize: int) -> int:
    """Pages per compute block: as many as hold ``BLOCK_BYTES`` of K,
    at least one, at most the block table's ``nb``."""
    page_bytes = block_s * hkv * hd * itemsize
    return max(1, min(nb, BLOCK_BYTES // page_bytes))


def _heads(block, hkv: int, tokens: int):
    """Yield each KV head's ``(tokens, hd)`` rows of one token-major VMEM
    block (``(pages, block_s, hkv, hd)``), as f32."""
    buf = block.reshape(tokens * hkv, block.shape[-1])   # row t * hkv + g
    if buf.dtype == jnp.bfloat16 and hkv % 2 == 0:
        # one 32-bit row holds heads 2j (low half) and 2j+1 (high half)
        words = buf.bitcast(jnp.uint32)
        for j in range(hkv // 2):
            w = words[pl.ds(j, tokens, stride=hkv // 2), :]
            yield pltpu.bitcast(w << 16, jnp.float32)
            yield pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)
    else:
        for g in range(hkv):
            yield buf[pl.ds(g, tokens, stride=hkv), :].astype(jnp.float32)


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, m_ref, l_ref, acc_ref, st_ref,
                         *, scale: float, slots: int, nb: int, block_s: int,
                         ppb: int):
    b = pl.program_id(0)
    hkv = k_buf.shape[3]
    tokens = ppb * block_s

    @pl.when(b == 0)
    def _init_state():
        st_ref[0] = -1                     # slot whose first block is in flight
        st_ref[1] = 0                      # the buffer it lands in

    def pages(slot, blk, buf, wait: bool):
        """Start (or wait for) the copies of block ``blk`` of ``slot``:
        its live pages only."""
        first = blk * ppb
        n = jnp.minimum(ppb, pl.cdiv(len_ref[slot], block_s) - first)

        def one(p, carry):
            page = bt_ref[slot * nb + first + p]
            for hbm, vmem, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                cp = pltpu.make_async_copy(hbm.at[page], vmem.at[buf, p],
                                           sems.at[kv, buf])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        lax.fori_loop(0, n, one, 0)

    seq_len = len_ref[b]
    n_blocks = pl.cdiv(pl.cdiv(seq_len, block_s), ppb)

    @pl.when(seq_len > 0)
    def _sweep():
        # the next slot with live pages, whose first block this slot fetches
        nxt = lax.while_loop(
            lambda c: (c < slots) & (len_ref[jnp.minimum(c, slots - 1)] == 0),
            lambda c: c + 1, b + 1)

        @pl.when(st_ref[0] != b)
        def _first():
            pages(b, 0, st_ref[1], wait=False)

        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def block(i, buf):
            nbuf = 1 - buf

            @pl.when(i + 1 < n_blocks)
            def _next_block():
                pages(b, i + 1, nbuf, wait=False)

            @pl.when((i + 1 == n_blocks) & (nxt < slots))
            def _next_slot():
                pages(nxt, 0, nbuf, wait=False)

            pages(b, i, buf, wait=True)
            base = i * tokens
            live_col = base + lax.broadcasted_iota(jnp.int32, (tokens, 1), 0) \
                < seq_len
            live_row = base + lax.broadcasted_iota(jnp.int32, (1, tokens), 1) \
                < seq_len
            ks = _heads(k_buf.at[buf], hkv, tokens)
            vs = _heads(v_buf.at[buf], hkv, tokens)
            for g, (k, v) in enumerate(zip(ks, vs)):
                q = q_ref[0, g].astype(jnp.float32)            # (qpk, hd)
                s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                s = jnp.where(live_row, s * scale, NEG_INF)    # (qpk, tokens)
                m_prev = m_ref[g]                              # (qpk, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(live_row, jnp.exp(s - m_new), 0.0)
                # rows past the live length may hold stale or unset bytes
                v = jnp.where(live_col, v, 0.0)
                l_ref[g] = l_ref[g] * alpha + p.sum(axis=1, keepdims=True)
                acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                    p, v, preferred_element_type=jnp.float32)
                m_ref[g] = m_new
            return nbuf

        st_ref[1] = lax.fori_loop(0, n_blocks, block, st_ref[1])
        st_ref[0] = nxt
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    @pl.when(seq_len <= 0)
    def _inactive():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gqa_decode(q, k_pages, v_pages, block_tables, seq_lens,
                     active=None, *, interpret: bool):
    """q: (B, H, hd); k_pages/v_pages: (P, BLOCK_S, Hkv, hd) shared
    physical block pool (token-major, the cache layout); block_tables:
    (B, NB) int32 logical->physical block map, read only below each
    row's live length; seq_lens: (B,) int32 valid tokens per slot
    (pos + 1); active: optional (B,) bool — rows with active=False
    copy nothing and return zeros. Returns (B, H*hd). ``interpret=True``
    runs the kernel body in Python (CPU validation);
    ``interpret=False`` lowers it for the TPU."""
    b, h, hd = q.shape
    block_s, hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    qpk = h // hkv
    ppb = pages_per_block(nb, block_s, hkv, hd, k_pages.dtype.itemsize)

    qg = q.reshape(b, hkv, qpk, hd)
    bt = block_tables.astype(jnp.int32).reshape(b * nb)
    lens = jnp.clip(seq_lens.astype(jnp.int32), 0, nb * block_s)
    if active is not None:
        lens = jnp.where(active, lens, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,             # block tables, live lengths
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, qpk, hd), lambda s, bt_, len_: (s, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, qpk, hd),
                               lambda s, bt_, len_: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, block_s, hkv, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb, block_s, hkv, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # (K/V, buffer)
            pltpu.VMEM((hkv, qpk, 1), jnp.float32),
            pltpu.VMEM((hkv, qpk, 1), jnp.float32),
            pltpu.VMEM((hkv, qpk, hd), jnp.float32),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / math.sqrt(hd),
                          slots=b, nb=nb, block_s=block_s, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, qpk, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_gqa_decode",
    )(bt, lens, qg, k_pages, v_pages)
    return out.reshape(b, h * hd)
