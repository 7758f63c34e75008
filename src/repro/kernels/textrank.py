"""TextRank power-iteration Pallas kernel (TPU target).

The C&R compressor's hot spot (paper §5.2 step 2): PageRank over the
sentence-similarity graph. For gateway prompts the graph is small
(N <= 1024 sentences), so the whole column-normalized weight matrix
fits in VMEM; the kernel runs the full damped power iteration on-chip
(matvec per step on the MXU) and writes the stationary vector once —
no HBM round-trips between iterations, which is the TPU-native
adaptation of the CPU pipeline (DESIGN.md §3).

Matrices are padded to a multiple of 128 (MXU lane alignment) by
ops.textrank_scores; padding columns/rows are masked inside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _textrank_kernel(sim_ref, n_ref, p_ref, *, damping: float, iters: int,
                     n_pad: int):
    sim = sim_ref[...].astype(jnp.float32)            # (Np, Np) padded
    n_real = n_ref[0]
    idx = jax.lax.iota(jnp.int32, n_pad)
    live = idx < n_real                               # (Np,)
    mask2 = live[:, None] & live[None, :]
    w = jnp.where(mask2, sim, 0.0)
    w = jnp.where(idx[:, None] == idx[None, :], 0.0, w)   # zero diagonal
    colsum = w.sum(axis=0)
    colsum = jnp.where(colsum <= 0.0, 1.0, colsum)
    wn = w / colsum[None, :]                          # column-normalized
    n_f = n_real.astype(jnp.float32)
    p0 = jnp.where(live, 1.0 / n_f, 0.0)

    def step(_, p):
        p = (1.0 - damping) / n_f + damping * (wn @ p)
        return jnp.where(live, p, 0.0)

    p = jax.lax.fori_loop(0, iters, step, p0)
    p_ref[...] = p.astype(p_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("damping", "iters", "interpret"))
def textrank_pallas(sim_padded, n_real, damping: float = 0.85,
                    iters: int = 30, *, interpret: bool):
    """sim_padded: (Np, Np) with Np % 128 == 0; n_real: () int32 actual
    sentence count. Returns the (Np,) PageRank vector (zeros in pad)."""
    n_pad = sim_padded.shape[0]
    assert n_pad % 128 == 0, n_pad
    return pl.pallas_call(
        functools.partial(_textrank_kernel, damping=damping, iters=iters,
                          n_pad=n_pad),
        grid=(1,),
        in_specs=[pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((n_pad,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        interpret=interpret,
    )(sim_padded, n_real.reshape(1))
