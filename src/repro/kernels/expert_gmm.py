"""Grouped matmul of expert-sorted rows (Pallas, TPU target): the held
experts of a dropless expert layer (``models/moe.py``'s
``moe_held``) in one call.

The rows arrive sorted by expert, each expert's rows starting at a
multiple of the row tile ``tm`` and padded to whole tiles, so every row
tile belongs to one expert: ``tile_expert[i]`` names it. The grid runs
over the ``live_tiles`` tiles that hold rows (a traced count: no step is
spent on the rest of the static buffer), then over output and
contraction blocks; each step multiplies one ``(tm, tk)`` block of rows
by the ``(tk, tn)`` block of its tile's expert, accumulating in f32. An
expert's weights are read once per tile of its rows, so a call reads
each expert that has rows about once, and none that has none. Rows of
tiles past ``live_tiles`` are left unset.

Validated in interpret mode against ``repro.kernels.ref.expert_gmm_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block(dim: int) -> int:
    """The largest of 1024 .. 128 that divides ``dim``, else all of it."""
    for b in (1024, 512, 256, 128):
        if dim % b == 0:
            return b
    return dim


def _kernel(te_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tm", "out_dtype", "interpret"))
def expert_gmm(x, w, tile_expert, live_tiles, *, tm: int, out_dtype,
               interpret):
    """x: (M, K) rows sorted by expert in whole tiles of ``tm`` rows;
    w: (E, K, N) the held experts' matrices; tile_expert: (M // tm,)
    int32 expert of each row tile; live_tiles: int32 scalar, tiles that
    hold rows (the first ones). Returns (M, N) in ``out_dtype``; rows of
    tiles past ``live_tiles`` are unset. ``interpret=True`` runs the
    kernel body in Python (CPU validation)."""
    m, kd = x.shape
    n = w.shape[2]
    tk, tn = _block(kd), _block(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,             # tile -> expert
        grid=(live_tiles, n // tn, kd // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k, te: (i, k)),
            pl.BlockSpec((None, tk, tn), lambda i, j, k, te: (te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_gmm",
    )(tile_expert.astype(jnp.int32), x, w)
