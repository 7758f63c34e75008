"""jit'd public wrappers for the Pallas kernels.

Each call decides how its kernel runs from the platform JAX is using
at that moment (nothing is decided at import): on a TPU the kernel is
lowered and compiled for the chip; on the CPU — and only when the CPU
was asked for explicitly, ``JAX_PLATFORMS=cpu`` — the kernel body runs
in Pallas interpret mode, which is how the tests validate it. Any
other platform, or a CPU that JAX fell back to because no accelerator
was found, raises instead of quietly running the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.expert_gmm import expert_gmm as _expert_gmm
from repro.kernels.gqa_decode import gqa_decode as _gqa_pallas
from repro.kernels.paged_decode import paged_gqa_decode as _paged_pallas
from repro.kernels.textrank import textrank_pallas


def interpret_mode() -> bool:
    """False on a TPU (compiled kernel), True on an explicitly chosen
    CPU (interpret mode); raises on anything else."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu" and jax.config.jax_platforms == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels need a TPU, or JAX_PLATFORMS=cpu for interpret "
        f"mode; JAX is on {platform!r} with JAX_PLATFORMS="
        f"{jax.config.jax_platforms!r}")


def gqa_decode(q, k_cache, v_cache, valid, active=None, block_s: int = 512):
    """Flash-decode attention; see kernels/gqa_decode.py. ``active``
    (B,) bool masks out continuous-batching rows that carry no live
    decode this step (their output is exactly zero)."""
    return _gqa_pallas(q, k_cache, v_cache, valid, active, block_s=block_s,
                       interpret=interpret_mode())


def paged_gqa_decode(q, k_pages, v_pages, block_tables, seq_lens,
                     active=None, window=None):
    """Paged flash-decode attention over a block-table-indexed KV pool,
    optionally over a static sliding ``window`` of keys; see
    kernels/paged_decode.py. Inactive rows return exact zeros."""
    return _paged_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                         active, interpret=interpret_mode(), window=window)


def expert_gmm(x, w, tile_expert, live_tiles, tm, out_dtype):
    """Grouped matmul of expert-sorted row tiles; see
    kernels/expert_gmm.py."""
    return _expert_gmm(x, w, tile_expert, live_tiles, tm=tm,
                       out_dtype=out_dtype, interpret=interpret_mode())


def textrank_scores(sim: np.ndarray, damping: float = 0.85,
                    iters: int = 30) -> np.ndarray:
    """Drop-in replacement for compression.textrank_scores_np: pads the
    similarity matrix to 128 alignment and runs the on-chip power
    iteration."""
    n = sim.shape[0]
    if n == 0:
        return np.zeros(0)
    n_pad = max(128, ((n + 127) // 128) * 128)
    padded = jnp.zeros((n_pad, n_pad), jnp.float32)
    padded = padded.at[:n, :n].set(jnp.asarray(sim, jnp.float32))
    p = textrank_pallas(padded, jnp.int32(n), damping=damping, iters=iters,
                        interpret=interpret_mode())
    return np.asarray(p[:n])
