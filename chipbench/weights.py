"""Random weights from the seed, made by the benchmark and not by the
program: the served run and the plain reference draw the same numbers
from the same keys, so the reference takes nothing the program made.

Every matrix is normal(0, s) with s the power of two nearest
1/sqrt(fan_in), every norm scale 1 + k/64 with k a rounded
normal(0, 8) clipped to +-24, an embedding normal(0, 1), each drawn in
float32 from its own key and cast to the served dtype. Scaling by a
power of two and small integer arithmetic are exact, so however the
compiler fuses or reassociates them no bit changes.

A model module (``models/<name>.py``) names its leaves in one ordered
table (``Table``); a leaf's key is ``fold_in(seed key, its index in
that table)``, and a layer's leaf folds in the layer index besides (a
module may fold in more, such as an expert index), so the reference can
regenerate one layer at a time, bit for bit, from what the one jitted
call for the whole model made. What the leaves are, their shapes and
the program's parameter tree are the module's business.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp


def root_key(seed: int):
    k = jax.random.key(int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0x7FFFFFFF)


def leaf_key(seed_key, leaves: Sequence[str], leaf: str):
    """The key of ``leaf``, by its place in the module's leaf table."""
    return jax.random.fold_in(seed_key, list(leaves).index(leaf))


def draw(key, shape, kind: str, dtype):
    """One leaf: ``kind`` "matrix" (fan-in ``shape[0]``), "norm" or
    "embed"."""
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + jnp.round(jnp.clip(8.0 * x, -24.0, 24.0)) / 64.0
    elif kind == "matrix":
        x = x * 2.0 ** -round(math.log2(math.sqrt(shape[0])))
    elif kind != "embed":
        raise ValueError(f"unknown leaf kind {kind!r}")
    return x.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Table:
    """A module's leaves: the top-level ones, then those of every layer,
    in one order (a leaf's key is its index in ``top + layer``, so the
    order is part of the weights); which are norm scales; and
    ``shape(cfg, leaf)``. Every other leaf but ``embed`` is a matrix."""
    top: tuple
    layer: tuple
    norms: tuple
    shape: Callable

    def _draw(self, cfg: dict, leaf: str, key, dtype):
        kind = "norm" if leaf in self.norms else \
            "embed" if leaf == "embed" else "matrix"
        return draw(key, self.shape(cfg, leaf), kind, dtype)

    def layer_params(self, cfg: dict, seed_key, i, dtype):
        """All leaves of layer ``i`` (traced or concrete index)."""
        leaves = self.top + self.layer
        return {n: self._draw(cfg, n, jax.random.fold_in(
            leaf_key(seed_key, leaves, n), i), dtype) for n in self.layer}

    def top_params(self, cfg: dict, seed_key, leaf: str, dtype):
        return self._draw(cfg, leaf, leaf_key(seed_key, self.top
                                              + self.layer, leaf), dtype)
